"""Framework-side microbenchmarks: batched design evaluation throughput
(the optimizer's hot loop the Pallas kernels target), PHV computation, and
the flit simulator. On this CPU container the jnp reference paths execute;
the same entry points run the Pallas kernels on TPU (Evaluator
backend="auto" resolves per platform).

Emits BENCH_netsim.json next to the repo root with the simulator
vectorized-vs-reference numbers so CHANGES.md entries can cite them."""

from __future__ import annotations

import json
import os

import numpy as np

from repro.core import (Evaluator, RegressionForest, hypervolume,
                        random_design, spec_36, spec_64, traffic_matrix)
from repro.core import netsim
from repro.core.features import design_features_batch
from repro.core.pareto import hypervolume_with_batch
from repro.core.stage import _meta_greedy

from .common import Timer, row


def _min_of(fn, n: int = 5) -> float:
    """Best-of-N wall time (seconds). Noisy-neighbor load on shared
    containers makes single-pass timings swing several x; the min is the
    stable floor (and the first pass is inside the N, so warm numbers can
    never look slower than cold ones again)."""
    best = np.inf
    for _ in range(n):
        with Timer() as t:
            fn()
        best = min(best, t.dt)
    return best


def main(reduced: bool = False) -> None:
    spec = spec_36() if reduced else spec_64()
    f = traffic_matrix(spec, "BFS")
    ev = Evaluator(spec, f)
    rng = np.random.default_rng(0)
    designs = [random_design(spec, rng) for _ in range(64)]
    ev.batch(designs[:8])  # warm compile
    with Timer() as t:
        ev.batch(designs)
    row("eval_batch64", t.dt / 64 * 1e6,
        f"designs_per_s={64/t.dt:.1f};backend={ev.backend}")

    pts = rng.uniform(size=(24, 4))
    with Timer() as t:
        for _ in range(50):
            hypervolume(pts, np.full(4, 1.5))
    row("phv_24pts_4obj", t.dt / 50 * 1e6, "hso_recursive+2d_staircase")

    # Batched greedy scoring: PHV(S ∪ {d}) for a whole neighborhood.
    cands = rng.uniform(size=(48, 4)) * 1.4
    with Timer() as t:
        for _ in range(20):
            hypervolume_with_batch(pts, cands, np.full(4, 1.5))
    row("phv_with_batch48", t.dt / 20 * 1e6, "excl_contributions")

    d = spec.mesh_design()
    bench = {"spec": spec.n_tiles, "cycles": 1000}
    netsim.clear_caches()
    with Timer() as t:
        netsim.simulate(spec, d, f, cycles=1000, warmup=200)
    row("netsim_1kcycles", t.dt * 1e6, f"cycles_per_s={1000/t.dt:.0f}")
    bench["vectorized_cold_us"] = t.dt * 1e6
    # Warm timing: min-of-N with the first (still table-warm) pass discarded
    # by the min — a single pass under load used to report warm > cold.
    warm = _min_of(lambda: netsim.simulate(spec, d, f, cycles=1000, warmup=200))
    row("netsim_1kcycles_warm", warm * 1e6,
        f"cycles_per_s={1000/warm:.0f};cached_tables;min_of_5")
    bench["vectorized_warm_us"] = warm * 1e6
    ref = _min_of(
        lambda: netsim.simulate_reference(spec, d, f, cycles=1000, warmup=200),
        n=3)
    row("netsim_reference_1kcycles", ref * 1e6,
        f"cycles_per_s={1000/ref:.0f};legacy_loop;min_of_3")
    bench["reference_us"] = ref * 1e6
    bench["speedup_cold"] = bench["reference_us"] / bench["vectorized_cold_us"]
    bench["speedup_warm"] = bench["reference_us"] / bench["vectorized_warm_us"]

    # Batched sweep: designs x scales amortize tables + the cycle loop.
    sweep = [spec.mesh_design()] + [random_design(spec, rng) for _ in range(7)]
    scales = tuple(s / max(f.sum(), 1e-9) for s in (4.0, 16.0))
    n_sims = len(sweep) * len(scales)
    with Timer() as t:
        netsim.simulate_batch(spec, sweep, f, scales=scales,
                              cycles=1000, warmup=250)
    row("netsim_batch16x1k", t.dt / n_sims * 1e6,
        f"sims={n_sims};sims_per_s={n_sims/t.dt:.1f}")
    bench["batch_us_per_sim"] = t.dt / n_sims * 1e6

    # Flat-forest inference: the MOO-STAGE surrogate hot path. Train size
    # matches a late-run aggregated trajectory set.
    frng = np.random.default_rng(1)
    xtr = frng.uniform(-1, 1, size=(4096, 16))
    ytr = (xtr[:, 0] * 2 + np.sin(3 * xtr[:, 1]) + 0.5 * xtr[:, 2] ** 2
           + 0.1 * frng.normal(size=4096))
    forest = RegressionForest(n_trees=24, max_depth=9, seed=0).fit(xtr, ytr)
    xq = frng.uniform(-1, 1, size=(4096, 16))
    forest.predict(xq, backend="jnp")  # compile
    t_ref = _min_of(lambda: forest.predict_reference(xq), n=3)
    t_np = _min_of(lambda: forest.predict(xq, backend="numpy"))
    t_jnp = _min_of(lambda: forest.predict(xq, backend="jnp"), n=7)
    t_best = min(t_np, t_jnp)
    row("forest_predict_4k", t_best * 1e6,
        f"speedup_vs_recursive={t_ref/t_best:.1f}x;numpy={t_np*1e6:.0f}us;"
        f"jnp={t_jnp*1e6:.0f}us;ref={t_ref*1e6:.0f}us")
    bench["forest_predict_4k_us"] = t_best * 1e6
    bench["forest_predict_4k_numpy_us"] = t_np * 1e6
    bench["forest_predict_4k_jnp_us"] = t_jnp * 1e6
    bench["forest_reference_4k_us"] = t_ref * 1e6
    bench["forest_speedup_4k"] = t_ref / t_best

    # Meta-search step: batched feature extraction + one flat predict per
    # sampled neighborhood (no objective evaluations are spent here).
    srng = np.random.default_rng(2)
    designs = [random_design(spec, srng) for _ in range(64)]
    feats = design_features_batch(spec, designs)
    labels = feats[:, 0] + feats[:, 13]
    meta_model = RegressionForest(n_trees=24, max_depth=9, seed=0).fit(feats, labels)
    steps = 10

    def meta():
        _meta_greedy(spec, meta_model, designs[0], np.random.default_rng(3),
                     n_swaps=24, n_link_moves=24, max_steps=steps)

    meta()  # warm the fused scorer's shape-cache (default backend="fused")
    t_meta = _min_of(meta, n=3)
    row("stage_meta_search", t_meta / steps * 1e6,
        f"us_per_step;neighborhood=48;steps<={steps};backend=fused")
    bench["stage_meta_search_us_per_step"] = t_meta / steps * 1e6

    # Steady-state fused scoring dispatch (core.fused): one MetaScorer,
    # one padded neighborhood, score_moves only — isolates the per-step
    # device pipeline (move->featurize->normalize->traverse->argmax) from
    # the rng sampling and accept bookkeeping the row above includes.
    from repro.core.fused import MetaScorer
    from repro.core.problem import sample_neighbor_moves

    sc = MetaScorer(spec, meta_model)
    mv = sample_neighbor_moves(spec, designs[0], np.random.default_rng(4),
                               n_swaps=24, n_link_moves=24)
    sc.score_moves(mv)  # warm compile
    reps = 20

    def fused_steps():
        for _ in range(reps):
            sc.score_moves(mv)

    t_fused = _min_of(fused_steps, n=3)
    row("stage_fused", t_fused / reps * 1e6,
        f"us_per_step;score_moves;B={len(mv)};one_dispatch")
    bench["stage_fused_us_per_step"] = t_fused / reps * 1e6

    # Distributed multi-start dispatch: 4 process workers (spawn start
    # method — each child pays interpreter + jax import, which dominates
    # this row; the search itself is a small spec_tiny budget). Tracks the
    # coordinator round trip: plan -> ProcessPoolExecutor fan-out ->
    # Pareto-union merge. Timed once: the spawn cost IS the measurement,
    # and it is stable (import-bound, not load-bound).
    from repro.core import spec_tiny
    from repro.noc.api import Budget, NocProblem
    from repro.noc.api import run as noc_run

    # A TPU belongs to one process, so the process executor is refused
    # there (repro.dist.worker.ShardPool) and the row is skipped.
    import jax as _jax

    dist_problem = NocProblem(spec=spec_tiny(), traffic="BFS")
    if _jax.default_backend() != "tpu":
        dist_cfg = {"n_workers": 4, "executor": "process", "iters_max": 2,
                    "n_swaps": 6, "n_link_moves": 6, "max_local_steps": 20}
        with Timer() as t:
            dist_res = noc_run(dist_problem, "stage_dist",
                               budget=Budget(max_evals=400, seed=0),
                               config=dist_cfg)
        row("stage_dist_4w", t.dt * 1e6,
            f"workers=4;process;evals={dist_res.n_evals};"
            f"pareto={len(dist_res.designs)}")
        bench["stage_dist_4w_us"] = t.dt * 1e6

    # shard_map executor (DESIGN.md §12): in-order shards whose evaluator
    # batches run as ONE multi-device program each. On this 1-device CPU
    # container the mesh is trivial — the row tracks the shard_map
    # dispatch overhead vs the serial executor; on a real multi-device
    # host the same row shows the batch-parallel win.
    spmd_cfg = {"n_workers": 2, "executor": "spmd", "iters_max": 2,
                "n_swaps": 6, "n_link_moves": 6, "max_local_steps": 20}
    with Timer() as t:
        spmd_res = noc_run(dist_problem, "stage_dist",
                           budget=Budget(max_evals=400, seed=0),
                           config=spmd_cfg)
    row("stage_spmd_2w", t.dt * 1e6,
        f"workers=2;spmd;ndev={_jax.device_count()};"
        f"evals={spmd_res.n_evals}")
    bench["stage_spmd_2w_us"] = t.dt * 1e6

    # Crash-safe round checkpoints (DESIGN.md §9): coordinator state is
    # persisted atomically after every sync round. The row is the save
    # cost per round; the note quotes it against round wall time — the
    # observability tax must stay a rounding error (target < 2%).
    import shutil
    import tempfile

    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        sync_cfg = {"n_workers": 4, "executor": "serial", "sync_every": 1,
                    "iters_max": 2, "n_swaps": 6, "n_link_moves": 6,
                    "max_local_steps": 20}
        with Timer() as t:
            ck_res = noc_run(dist_problem, "stage_dist",
                             budget=Budget(max_evals=400, seed=0),
                             config=sync_cfg, checkpoint_dir=ckpt_dir)
        ck = ck_res.extra["checkpoint"]
        n_rounds_run = max(ck["n_saves"], 1)
        per_round_us = ck["save_s"] / n_rounds_run * 1e6
        pct = 100.0 * ck["save_s"] / t.dt
        row("stage_dist_ckpt_4w", per_round_us,
            f"saves={ck['n_saves']};pct_of_round_wall={pct:.2f}%;"
            f"serial;target<2%")
        bench["stage_dist_ckpt_4w_us"] = per_round_us
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    # Multi-tenant optimization service (DESIGN.md §10). Two rows: the
    # admission path (validate + canonical key + journal-free admit — the
    # per-request tax every tenant pays at the door), and 8 concurrent
    # requests multiplexed over one 4-slot fleet (wave-pump throughput;
    # dominated by the optimization itself, which is the point — the
    # service layer must not add more than routing overhead on top).
    from repro.noc.server import Client

    serve_problem = dist_problem.to_json()
    serve_req_cfg = {"n_workers": 2, "sync_every": 1, "iters_max": 2,
                     "n_swaps": 6, "n_link_moves": 6, "max_local_steps": 20}
    serve_fleet = dict(n_workers=4, executor="serial", max_queue=64,
                       max_inflight_per_tenant=64)
    n_sub = 32
    with Client.local(**serve_fleet) as cl:
        with Timer() as t:
            for i in range(n_sub):
                ack = cl.submit(serve_problem,
                                Budget(max_evals=60, seed=1000 + i).to_json(),
                                dict(serve_req_cfg))
                assert "error" not in ack, ack
        submit_us = t.dt / n_sub * 1e6
    row("serve_submit_overhead", submit_us,
        "validate+canonical_key+admit;per_submit")
    bench["serve_submit_overhead_us"] = submit_us

    with Client.local(**serve_fleet) as cl:
        acks = [cl.submit(serve_problem,
                          Budget(max_evals=60, seed=i).to_json(),
                          dict(serve_req_cfg), tenant=f"t{i}")
                for i in range(8)]
        with Timer() as t:
            cl.drain()
        n_done = sum(1 for a in acks
                     if cl.status(a["id"])["status"] == "done")
    row("serve_8req_4w", t.dt * 1e6,
        f"requests=8;serial_fleet;done={n_done}")
    bench["serve_8req_4w_us"] = t.dt * 1e6

    # Model-derived traffic generation (repro.workloads, DESIGN.md §11):
    # matrix synthesis is pure numpy flow accounting and must stay cheap
    # enough to build scenarios on the fly at admission time.
    from repro.workloads import LLM_STUDY_SCENARIOS, parse_scenario, \
        scenario_matrix

    gen_spec = spec_64()
    scen = [parse_scenario(s) for s in LLM_STUDY_SCENARIOS]

    def gen_all():
        for arch, phase in scen:
            scenario_matrix(gen_spec, arch, phase)

    gen_all()  # warm (model-config imports)
    t_gen = _min_of(gen_all)
    row("traffic_model_gen", t_gen / len(scen) * 1e6,
        f"scenarios={len(scen)};spec=64;per_matrix")
    bench["traffic_model_gen_us"] = t_gen / len(scen) * 1e6

    # Reduced cross-execution cell of the LLM agnostic study: 2 paper apps
    # x 2 LLM scenarios + 2 AVG rows on spec_tiny — tracks the end-to-end
    # optimize+cross-evaluate path the fig9 --workloads llm study scales up.
    from repro.core.agnostic import OptimizeBudget
    from repro.workloads import run_cross_workload_study

    cross_budget = OptimizeBudget(iters_max=1, n_swaps=4, n_link_moves=4,
                                  max_local_steps=6)
    with Timer() as t:
        cross = run_cross_workload_study(
            spec_tiny(), ("BFS", "BP"),
            ("yi-6b:train.fwd", "qwen3-moe-30b-a3b:serve.decode"),
            "case3", cross_budget)
    s = cross["summary"]
    row("agnostic_llm_cross", t.dt * 1e6,
        f"paper_on_llm_avg=+{s['paper_on_llm_avg']*100:.1f}%;"
        f"llm_on_paper_avg=+{s['llm_on_paper_avg']*100:.1f}%;tiny")
    bench["agnostic_llm_cross_us"] = t.dt * 1e6

    # Incremental routing-table deltas at the spec_large tier (DESIGN.md
    # §13): per-link-move table update vs the full host rebuild (one
    # Floyd–Warshall pass plus the edge-list next hop) it saves. The delta
    # leads the full rebuild by a few times, no longer by a hundred;
    # apsp_delta_256_us, the delta alone, is the tracked number.
    from repro.core import routing, spec_large
    from repro.core.objectives import design_cost_np

    lspec = spec_large()
    lcost = design_cost_np(lspec, lspec.mesh_design().adj)
    tab = routing.host_tables(lcost)
    t_full = _min_of(lambda: routing.host_tables(lcost), n=2)
    drng = np.random.default_rng(5)
    dmv = sample_neighbor_moves(lspec, lspec.mesh_design(), drng,
                                n_swaps=0, n_link_moves=8)
    w_hop = float(np.float32(lspec.router_stages))

    def one_delta(k):
        add = (int(dmv.add[k, 0]), int(dmv.add[k, 1]))
        w = w_hop + float(np.float32(lspec.link_delay[add]))
        r = routing.delta_link_move(
            tab, (int(dmv.rem[k, 0]), int(dmv.rem[k, 1])), add, w)
        assert r is not None  # fallback would poison the timing
        return r

    one_delta(0)  # warm (numpy buffers, eps cache)
    times = []
    for k in range(dmv.rem.shape[0]):
        with Timer() as t:
            one_delta(k)
        times.append(t.dt)
    t_delta = float(np.median(times))
    row("apsp_delta_256", t_delta * 1e6,
        f"median_of_{len(times)};full_rebuild={t_full*1e6:.0f}us;"
        f"speedup={t_full/t_delta:.0f}x;n=256")
    bench["apsp_delta_256_us"] = t_delta * 1e6
    bench["apsp_full_256_us"] = t_full * 1e6
    bench["apsp_delta_speedup_256"] = t_full / t_delta

    # Incremental Pareto-front maintenance: 1k 4-objective inserts into
    # the sorted-front archive (the local_search/stage union path).
    from repro.core.pareto import ParetoArchive

    prng = np.random.default_rng(6)
    stream = prng.uniform(size=(1000, 4))

    def insert_1k():
        arch = ParetoArchive(4)
        for i, p in enumerate(stream):
            arch.insert(p, tag=i)
        return arch

    front = len(insert_1k())  # warm
    t_ins = _min_of(insert_1k, n=3)
    row("pareto_insert_1k", t_ins / 1000 * 1e6,
        f"us_per_insert;final_front={front};n_obj=4")
    bench["pareto_insert_1k_us"] = t_ins / 1000 * 1e6

    out = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "BENCH_netsim.json")
    with open(out, "w") as fh:
        json.dump(bench, fh, indent=2)


if __name__ == "__main__":
    main()
