#!/usr/bin/env python3
"""Bring-up check: the NoC design search runs end to end on a TPU.

    python3 chip_smoke.py              # phases a-d on one chip
    python3 chip_smoke.py --chips 4    # stage_dist across four chips only

Phases (one chip), through the public API:

  a. Evaluator conformance at 64 and 256 tiles: a batch of random designs
     through ``Evaluator(backend="pallas")`` (the compiled min-plus kernel).
     Its APSP distances equal the host oracle ``routing.apsp_np`` and its
     objective rows equal ``Evaluator(backend="jnp")`` on the same chip,
     exactly (every finite path cost is a small integer, exact in f32).
     The compiled path-walk kernel (``kernels.ops.walk_accumulate``, the
     link report's walk) on one of those designs equals the host oracle
     ``walk_accumulate_np``: exactly under integer traffic, and within
     rtol 1e-4 under the phase's real traffic (summation order differs).
  b. Paper deployment: registry ``stage_batch`` on ``spec_64`` with the
     paper's AVG traffic at a fixed budget; the RunResult round-trips
     through JSON and the evaluation count stays within budget.
  c. System-level deployment: ``stage_batch`` on ``spec_large`` (256 tiles)
     with model-derived MoE training traffic — the incremental table-delta
     path next to the on-device objective walk.
  d. Service: two identical requests through ``Client.local``; the second
     is a cache hit with ``n_evals == 0``.

``--chips 4`` runs only ``stage_dist`` with four workers under the ``spmd``
and ``jax`` executors, each compared with ``serial`` (payloads equal with
wall clocks zeroed), and checks that an spmd batch spans four devices.

Each phase prints one JSON line (wall time, compile time, compile-cache
hits, evaluations, PHV, resolved backends). These are single bring-up runs,
not benchmark numbers. The last line is ``{"ok": true, "device": ...}`` and
appears only when every phase passed on a TPU; otherwise the exit code is
nonzero.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

# No TPU library log files under /tmp (set before JAX is imported).
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: backends each decision must resolve to on a TPU
EXPECTED = {"routing": "pallas", "forest": "jnp", "meta": "fused"}
#: run the Pallas kernels through the interpreter (CPU rehearsal of the
#: phase functions; main() itself refuses to run without a TPU)
INTERPRET = False
SEED = 0


class CheckFailed(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


class CompileMonitor:
    """Compile seconds and persistent-cache hits/misses, from JAX's own
    monitoring events, attributed to the phase that is running."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon

        self.cur: dict | None = None
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if self.cur is not None and event in self._DURATIONS:
            self.cur["compile_s"] += duration

    def _on_event(self, event, **_):
        if self.cur is None:
            return
        if event == "/jax/compilation_cache/cache_hits":
            self.cur["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cur["cache_misses"] += 1

    @contextlib.contextmanager
    def phase(self, rec: dict):
        rec.update(compile_s=0.0, cache_hits=0, cache_misses=0)
        self.cur = rec
        t0 = time.perf_counter()
        try:
            yield rec
        finally:
            rec["wall_s"] = time.perf_counter() - t0
            self.cur = None


def expect_backends(rec: dict, **got) -> None:
    rec.setdefault("backends", {}).update(got)
    for k, v in got.items():
        check(v == EXPECTED[k], f"{k} backend resolved to {v!r}, "
                                f"expected {EXPECTED[k]!r} on a TPU")


def run_summary(rec: dict, res) -> None:
    rec.update(evals=int(res.n_evals), calls=int(res.n_calls),
               pareto=len(res.designs), phv=float(res.phv()),
               run_wall_s=float(res.wall_s))


# ---------------------------------------------------------------- phases
def phase_evaluator(rec: dict) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Evaluator, random_design, routing
    from repro.core.objectives import design_cost_np
    from repro.core.problem import spec_64, spec_large
    from repro.noc import NocProblem

    rec["specs"] = {}
    for name, spec, traffic in (
            ("spec_64", spec_64(), "BFS"),
            ("spec_large", spec_large(),
             {"model": "qwen3-moe-30b-a3b", "phase": "train.fwd"})):
        f = NocProblem(spec=spec, traffic=traffic).traffic_matrix()
        rng = np.random.default_rng(SEED)
        designs = [random_design(spec, rng) for _ in range(64)]
        ev_k = Evaluator(spec, f, backend="pallas", interpret=INTERPRET)
        ev_j = Evaluator(spec, f, backend="jnp")
        expect_backends(rec, routing=routing.resolve_backend("auto"))

        adjs = jnp.asarray(np.stack([d.adj for d in designs]))
        costs = np.asarray(ev_k._cost_fn(adjs))
        check(np.array_equal(
            costs, np.stack([design_cost_np(spec, d.adj) for d in designs])),
            f"{name}: device cost matrices differ from the host's")
        iters = ev_k.consts.apsp_iters
        dist_k = np.concatenate([
            np.asarray(routing.apsp_batched(
                jnp.asarray(costs[i:i + ev_k.max_batch]), iters,
                backend=ev_k.backend, interpret=INTERPRET))
            for i in range(0, len(designs), ev_k.max_batch)])
        dist_h = np.stack([routing.apsp_np(c, iters) for c in costs])
        check(np.array_equal(dist_k, dist_h),
              f"{name}: kernel APSP differs from apsp_np in "
              f"{int((dist_k != dist_h).sum())} entries")

        objs_k = ev_k.batch(designs)
        objs_j = ev_j.batch(designs)
        check(np.array_equal(objs_k, objs_j),
              f"{name}: pallas and jnp objective rows differ")
        finite = np.isfinite(objs_k).all(axis=1)
        n_finite = int(finite.sum())
        check(n_finite > 0, f"{name}: no design evaluated finite")
        i = int(np.argmax(finite))
        walk = _check_walk(name, ev_k.consts, designs[i], costs[i], dist_h[i],
                           f)
        rec["specs"][name] = {"designs": len(designs), "finite": n_finite,
                              "evals": ev_k.n_evals, "calls": ev_k.n_calls,
                              "walk": walk}


def _check_walk(name, consts, design, cost, dist, f) -> dict:
    """The compiled path-walk kernel against the scalar-loop host oracle."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import routing
    from repro.kernels import ops
    from repro.kernels.ref import walk_accumulate_np

    n = len(design.perm)
    nh = routing.next_hop_np(cost, dist)
    off = 1.0 - np.eye(n, dtype=np.float32)
    f_int = (np.random.default_rng(SEED).integers(0, 16, (n, n))
             * off).astype(np.float32)
    perm = np.asarray(design.perm)
    f_real = (np.asarray(f)[perm][:, perm] * off).astype(np.float32)
    delay = np.asarray(consts.link_delay)
    out = {}
    for label, fs, exact in (("int", f_int, True), ("real", f_real, False)):
        got = [np.asarray(x) for x in ops.walk_accumulate(
            jnp.asarray(nh), jnp.asarray(fs), jnp.asarray(delay),
            max_hops=consts.max_hops, use_kernel=True, interpret=INTERPRET)]
        want = walk_accumulate_np(nh, fs, delay, max_hops=consts.max_hops)
        for field, g, w in zip(("hops", "dsum", "util", "visits"), got, want):
            ok = (np.array_equal(g, w) if exact else
                  np.allclose(g, w, rtol=1e-4, atol=1e-6 * float(w.max())))
            check(ok, f"{name}: walk kernel {field} differs from "
                      f"walk_accumulate_np under {label} traffic (max abs "
                      f"diff {float(np.abs(g - w).max())})")
        out[label] = {"max_abs_diff": max(float(np.abs(g - w).max())
                                          for g, w in zip(got, want))}
    return out


def _stage_batch(rec: dict, problem, budget, cfg: dict) -> None:
    import numpy as np

    from repro.core.forest import resolve_forest_backend
    from repro.noc import RunResult, run

    ev = problem.evaluator()
    res = run(problem, "stage_batch", budget=budget, config=cfg, ev=ev)
    run_summary(rec, res)
    n_cand = cfg["n_starts"] * (cfg["n_swaps"] + cfg["n_link_moves"])
    expect_backends(
        rec, routing=ev.backend,
        forest=resolve_forest_backend(problem.forest_backend, batch=n_cand),
        meta=res.config["meta_backend"])
    rec["delta"] = {"on": bool(ev.delta_on), **ev.delta_stats}

    back = RunResult.from_json(json.loads(json.dumps(res.to_json())))
    check(np.array_equal(back.objs, res.objs)
          and [d.key() for d in back.designs] == [d.key() for d in res.designs],
          "RunResult JSON round trip changed the front")
    # Drivers check the budget per lockstep step: at most one step over.
    check(0 < res.n_evals <= budget.max_evals + n_cand,
          f"evaluations {res.n_evals} outside budget {budget.max_evals}")
    check(len(res.designs) > 0 and np.isfinite(res.phv()) and res.phv() > 0,
          "empty front or non-positive PHV")


def phase_paper(rec: dict) -> None:
    from repro.core.problem import spec_64
    from repro.core.traffic import APP_NAMES
    from repro.noc import Budget, NocProblem

    problem = NocProblem(spec=spec_64(), traffic=tuple(APP_NAMES))
    _stage_batch(rec, problem, Budget(max_evals=1500, seed=SEED),
                 {"n_starts": 4, "iters_max": 3, "n_swaps": 16,
                  "n_link_moves": 16, "max_local_steps": 30})


def phase_system(rec: dict) -> None:
    from repro.core.problem import spec_large
    from repro.noc import Budget, NocProblem

    problem = NocProblem(spec=spec_large(),
                         traffic={"model": "qwen3-moe-30b-a3b",
                                  "phase": "train.fwd"})
    _stage_batch(rec, problem, Budget(max_evals=400, seed=SEED),
                 {"n_starts": 2, "iters_max": 2, "n_swaps": 8,
                  "n_link_moves": 8, "max_local_steps": 15})
    check(rec["delta"]["on"], "spec_large did not take the delta path")


def phase_service(rec: dict) -> None:
    import numpy as np

    from repro.core.problem import spec_64
    from repro.noc import Budget, NocProblem, RunResult
    from repro.noc.server import Client

    pj = NocProblem(spec=spec_64(), traffic="BFS").to_json()
    bj = Budget(max_evals=300, seed=SEED).to_json()
    cfg = {"iters_max": 2, "n_swaps": 8, "n_link_moves": 8,
           "max_local_steps": 10}
    with Client.local(n_workers=2, executor="serial") as client:
        first = client.submit(pj, bj, cfg)
        check(first.get("status") == "queued", f"first submit: {first}")
        client.drain()
        res = client.result(first["id"])
        check(isinstance(res, RunResult), f"first request failed: {res}")
        run_summary(rec, res)
        check(res.n_evals > 0 and len(res.designs) > 0, "empty first result")
        dup = client.submit(pj, bj, cfg)
        check(dup.get("cache_hit") is True and dup.get("status") == "done",
              f"duplicate request was not a cache hit: {dup}")
        hit = client.result(dup["id"])
        check(isinstance(hit, RunResult) and hit.n_evals == 0
              and np.array_equal(hit.objs, res.objs),
              "cache hit did not return the first front at n_evals == 0")
    rec["cache_hit_evals"] = hit.n_evals


def _payload(res) -> str:
    """Canonical payload: wall clocks zeroed, driver-naming fields left out
    (the same canon as the distributed-driver tests)."""
    j = res.to_json()
    j["history"] = [[0.0] + row[1:] for row in j["history"]]
    keep = ("problem", "budget", "obj_idx", "designs", "objs", "history",
            "n_evals", "n_calls", "exhausted")
    return json.dumps({k: j[k] for k in keep}, sort_keys=True)


def phase_dist4(rec: dict) -> None:
    import jax
    import numpy as np

    from repro.core import random_design
    from repro.core.evaluate import make_spmd_mesh, spmd_scope
    from repro.core.problem import spec_64
    from repro.core.traffic import APP_NAMES
    from repro.noc import Budget, NocProblem, run

    problem = NocProblem(spec=spec_64(), traffic=tuple(APP_NAMES))
    budget = Budget(max_evals=800, seed=SEED)
    base = {"n_workers": 4, "iters_max": 2, "n_swaps": 8, "n_link_moves": 8,
            "max_local_steps": 15}
    results = {}
    for ex in ("serial", "spmd", "jax"):
        t0 = time.perf_counter()
        results[ex] = run(problem, "stage_dist", budget=budget,
                          config=dict(base, executor=ex))
        check(not results[ex].extra.get("worker_failures"),
              f"{ex}: worker failures {results[ex].extra['worker_failures']}")
        rec[ex] = {"wall_s": time.perf_counter() - t0,
                   "evals": int(results[ex].n_evals),
                   "phv": float(results[ex].phv())}
    for ex in ("spmd", "jax"):
        check(_payload(results[ex]) == _payload(results["serial"]),
              f"{ex} executor payload differs from serial")

    # The spmd batch really spans every device, not just device 0.
    with spmd_scope(make_spmd_mesh()):
        ev = problem.evaluator()
    rng = np.random.default_rng(SEED)
    designs = [random_design(problem.spec, rng) for _ in range(16)]
    objs, _ = ev._spmd_fn(np.stack([d.perm for d in designs]),
                          np.stack([d.adj for d in designs]), ev.f)
    ndev = len(objs.sharding.device_set)
    rec["spmd_batch_devices"] = ndev
    check(ndev == len(jax.devices()) == 4,
          f"spmd batch spans {ndev} device(s), expected 4")
    check(np.array_equal(np.asarray(objs, dtype=np.float64),
                         problem.evaluator().batch(designs)),
          "sharded batch differs from the single-device batch")


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a-d; 4: the four-chip stage_dist phase")
    args = ap.parse_args(argv)

    from repro import compile_cache

    cache_dir = compile_cache.enable()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform is {platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    print(json.dumps({"device_kind": devices[0].device_kind,
                      "count": len(devices), "cache_dir": cache_dir}),
          flush=True)

    phases = ([("dist4", phase_dist4)] if args.chips == 4 else
              [("a_evaluator", phase_evaluator), ("b_paper", phase_paper),
               ("c_system", phase_system), ("d_service", phase_service)])
    monitor = CompileMonitor()
    failed = []
    for name, fn in phases:
        rec = {"phase": name}
        with monitor.phase(rec):
            try:
                fn(rec)
                rec["ok"] = True
            except Exception as e:  # noqa: BLE001 — report, run the rest
                rec["ok"] = False
                rec["error"] = f"{type(e).__name__}: {e}"
                traceback.print_exc()
                failed.append(name)
        print(json.dumps(rec, default=float), flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
