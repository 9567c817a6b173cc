"""Unified ``repro.noc`` API: registry coverage, budget accounting,
serialization round trips, the CLI smoke tier, and the hardened move
validation (real exceptions instead of ``-O``-stripped asserts)."""

import json
import time

import numpy as np
import pytest

from repro.core import (CASES, Design, Evaluator, PhvContext, dominates,
                        spec_tiny, traffic_matrix)
from repro.core.amosa import amosa
from repro.core.problem import sample_neighbor_moves
from repro.noc import (Budget, BudgetExhausted, NocProblem, RunResult,
                       design_from_json, design_to_json, get_optimizer,
                       named_spec, optimizer_names, run)
from repro.noc.api import budget_limit

ALL_OPTIMIZERS = ("amosa", "local", "nsga2", "pcbb", "stage", "stage_batch",
                  "stage_dist")

#: small-budget configs that exercise every optimizer in a few seconds
SMALL_CONFIGS = {
    "stage": dict(iters_max=2, n_swaps=4, n_link_moves=4, max_local_steps=5),
    "stage_batch": dict(n_starts=2, iters_max=2, n_swaps=4, n_link_moves=4,
                        max_local_steps=5),
    "stage_dist": dict(n_workers=2, executor="serial", iters_max=2,
                       n_swaps=4, n_link_moves=4, max_local_steps=5),
    "amosa": dict(t_max=0.5, t_min=0.05, alpha=0.7, iters_per_temp=8),
    "nsga2": dict(pop_size=8, generations=2),
    "local": dict(n_starts=2, n_swaps=4, n_link_moves=4, max_steps=4),
    "pcbb": dict(max_expansions=30, link_descent_steps=2,
                 n_random_rollouts=1),
}


@pytest.fixture(scope="module")
def tiny_problem():
    problem = NocProblem(spec=spec_tiny(), traffic="BFS", case="case3")
    ev = problem.evaluator()
    ctx = problem.context(ev)
    return problem, ev, ctx


def test_registry_contains_every_optimizer():
    assert optimizer_names() == ALL_OPTIMIZERS
    for name in ALL_OPTIMIZERS:
        entry = get_optimizer(name)
        assert entry.name == name and callable(entry.run_fn)
    with pytest.raises(ValueError, match="unknown optimizer"):
        get_optimizer("gradient_descent")


@pytest.mark.parametrize("name", ALL_OPTIMIZERS)
def test_every_optimizer_returns_roundtrippable_runresult(
        tiny_problem, name, tmp_path):
    """Acceptance: every registry optimizer runs under a shared Budget and
    its RunResult JSON round-trips to identical Pareto objectives."""
    problem, ev, ctx = tiny_problem
    if get_optimizer(name).owns_result:
        # Coordinator drivers (stage_dist) run on per-worker evaluators and
        # refuse ev=/ctx= injection — run them standalone.
        res = run(problem, name, budget=Budget(max_evals=400, seed=0),
                  config=SMALL_CONFIGS[name])
    else:
        budget = Budget(max_evals=ev.n_evals + 400, seed=0)
        res = run(problem, name, budget=budget, config=SMALL_CONFIGS[name],
                  ev=ev, ctx=ctx)
    assert isinstance(res, RunResult) and res.optimizer == name
    assert len(res.designs) >= 1 and res.n_evals > 0 and res.n_calls > 0
    assert np.isfinite(res.phv())
    # Pareto set: mutually non-dominated under the active objective subset,
    # structurally valid designs.
    sub = np.asarray(res.objs)[:, list(res.obj_idx)]
    for i in range(sub.shape[0]):
        for j in range(sub.shape[0]):
            if i != j:
                assert not dominates(sub[i], sub[j])
    spec = problem.spec
    for d in res.designs:
        assert sorted(d.perm.tolist()) == list(range(spec.n_tiles))
        assert int(np.triu(d.adj).sum()) == spec.n_planar_links
        assert np.array_equal(d.adj, d.adj.T)
    # Exact JSON round trip (file and in-memory); saved files are strict
    # RFC JSON (no bare NaN/Infinity tokens — history phv is NaN here).
    path = tmp_path / f"{name}.json"
    res.save(path)
    json.loads(path.read_text())  # stdlib accepts lax too; check tokens:
    for token in ("NaN", "Infinity"):
        assert token not in path.read_text()
    back = RunResult.load(path)
    assert np.array_equal(np.asarray(back.objs), np.asarray(res.objs))
    assert back.obj_idx == res.obj_idx
    assert [d.key() for d in back.designs] == [d.key() for d in res.designs]
    # equal_nan: the history's phv column is NaN unless track_phv was on.
    assert np.array_equal(back.history, res.history, equal_nan=True)


def test_runresult_nonfinite_extra_roundtrips(tmp_path):
    """Non-finite diagnostics in ``extra`` survive save/load (NaN -> null
    -> NaN, inf -> "inf" -> inf) and the file stays strict JSON."""
    res = RunResult(
        optimizer="stage", problem={}, budget={},
        config={"iters_max": np.int64(3), "scale": np.float64(1.5)},
        obj_idx=(0, 1), designs=[], objs=np.zeros((0, 5)),
        n_evals=0, n_calls=0, wall_s=0.0, history=np.zeros((0, 4)),
        extra={"phv": float("nan"), "bound": float("inf"),
               "scores": [1.5, float("-inf")]})
    path = tmp_path / "nonfinite.json"
    res.save(path)
    assert "NaN" not in path.read_text()
    back = RunResult.load(path)
    assert np.isnan(back.extra["phv"]) and np.isnan(back.phv())
    assert back.extra["bound"] == float("inf")
    assert back.extra["scores"] == [1.5, float("-inf")]
    assert back.config == {"iters_max": 3, "scale": 1.5}


def _canonical_run_json(res: RunResult) -> str:
    """RunResult JSON with the only nondeterministic fields — wall-clock
    seconds (``wall_s`` and the history rows' wall column) — zeroed; every
    other byte must reproduce for a fixed (problem, budget, seed)."""
    j = res.to_json()
    j["wall_s"] = 0.0
    j["history"] = [[0.0] + row[1:] for row in j["history"]]
    return json.dumps(j, sort_keys=True)


@pytest.mark.parametrize("name", ["stage", "stage_batch"])
@pytest.mark.parametrize("forest_backend", ["numpy", "jnp"])
def test_registry_run_seeded_determinism(name, forest_backend):
    """Two registry runs with the same (NocProblem, Budget, seed) produce
    byte-identical RunResult JSON (wall-clock excluded) for both surrogate
    backends — the reproducibility contract the ROADMAP's distributed
    multi-start item merges workers on."""
    problem = NocProblem(spec=spec_tiny(), traffic="BFS", case="case3",
                         forest_backend=forest_backend)
    budget = Budget(max_evals=150, seed=3)
    first, second = (
        _canonical_run_json(run(problem, name, budget=budget,
                                config=SMALL_CONFIGS[name]))
        for _ in range(2))
    assert problem.forest_backend in first  # knob serialized with the run
    assert first == second


def test_forest_backend_validated_at_construction():
    """A bad forest_backend fails fast — at NocProblem/config construction,
    not at the first surrogate refit after evaluations were spent."""
    from repro.noc import StageBatchConfig, StageConfig

    with pytest.raises(ValueError, match="forest_backend"):
        NocProblem(spec=spec_tiny(), traffic="BFS", forest_backend="bogus")
    with pytest.raises(ValueError, match="forest_backend"):
        StageConfig(forest_backend="bogus")
    with pytest.raises(ValueError, match="forest_backend"):
        StageBatchConfig(forest_backend="bogus")
    with pytest.raises(ValueError, match="forest_backend"):
        NocProblem(spec=spec_tiny(), traffic="BFS", forest_backend="pallas")
    with pytest.raises(ValueError, match="meta_backend"):
        StageBatchConfig(meta_backend="bogus")
    assert StageConfig(forest_backend="jnp").forest_backend == "jnp"
    assert StageConfig().forest_backend is None  # inherit the problem's


def test_stage_dist_config_validated_and_injection_refused(tiny_problem):
    """StageDistConfig fails fast on bad knobs, and the owns-result driver
    refuses the single-process ev=/ctx=/callback= conveniences instead of
    silently mis-accounting them."""
    from repro.noc import StageDistConfig

    with pytest.raises(ValueError, match="executor"):
        StageDistConfig(executor="threads")
    with pytest.raises(ValueError, match="n_workers"):
        StageDistConfig(n_workers=0)
    with pytest.raises(ValueError, match="sync_every"):
        StageDistConfig(sync_every=-1)
    with pytest.raises(ValueError, match="forest_backend"):
        StageDistConfig(forest_backend="bogus")
    problem, ev, ctx = tiny_problem
    with pytest.raises(ValueError, match="owns its RunResult"):
        run(problem, "stage_dist", budget=Budget(max_evals=50),
            config=SMALL_CONFIGS["stage_dist"], ev=ev, ctx=ctx)
    with pytest.raises(ValueError, match="owns its RunResult"):
        run(problem, "stage_dist", budget=Budget(max_evals=50),
            config=SMALL_CONFIGS["stage_dist"], callback=print)


def test_run_with_prespent_budget_reports_exhausted(tiny_problem):
    """A budget already consumed at entry yields an empty result that is
    consistently flagged exhausted=True for every driver (nothing was
    evaluated by this run beyond what the guard allowed)."""
    problem, ev, ctx = tiny_problem
    for name in ("stage", "amosa", "local"):
        before = ev.n_evals
        res = run(problem, name, budget=Budget(max_evals=before, seed=0),
                  config=SMALL_CONFIGS[name], ev=ev, ctx=ctx)
        assert res.exhausted and res.n_evals == 0
        assert len(res.designs) == 0 and res.phv() == 0.0


def test_design_json_roundtrip_exact(tiny_problem):
    problem, ev, ctx = tiny_problem
    rng = np.random.default_rng(7)
    from repro.core import random_design

    for _ in range(3):
        d = random_design(problem.spec, rng)
        back = design_from_json(json.loads(json.dumps(design_to_json(d))))
        assert back.key() == d.key()


def test_problem_json_roundtrip():
    spec = spec_tiny()
    for traffic in ("BFS", ("BFS", "BP"),
                    traffic_matrix(spec, "BFS") * 0.5):
        p = NocProblem(spec=spec, traffic=traffic, case="case2")
        q = NocProblem.from_json(json.loads(json.dumps(p.to_json())))
        assert q.spec == p.spec and q.case == p.case
        assert np.allclose(q.traffic_matrix(), p.traffic_matrix())


def test_named_spec_and_bad_inputs():
    assert named_spec("tiny") == spec_tiny()
    with pytest.raises(ValueError, match="unknown spec"):
        named_spec("128")
    with pytest.raises(ValueError, match="unknown case"):
        NocProblem(spec=spec_tiny(), traffic="BFS", case="case9")


def test_problem_eq_and_hash_with_matrix_traffic():
    """Explicit-matrix problems must compare and hash (the generated
    dataclass __eq__ would crash on ndarrays) — cache/dedup keys for the
    distributed fan-out."""
    spec = spec_tiny()
    f = traffic_matrix(spec, "BFS")
    p1 = NocProblem(spec=spec, traffic=f.copy())
    p2 = NocProblem(spec=spec, traffic=f.copy())
    p3 = NocProblem(spec=spec, traffic=f * 2.0)
    assert p1 == p2 and hash(p1) == hash(p2)
    assert p1 != p3
    assert NocProblem(spec=spec, traffic="BFS") != p1
    assert len({p1, p2, p3}) == 2


# ---------------------------------------------------------------------------
# Evaluation accounting
# ---------------------------------------------------------------------------
def test_evaluator_counts_requested_designs_only():
    """n_evals counts requested designs — padding to the next power of two
    and max_batch chunking are invisible; n_calls counts dispatches."""
    spec = spec_tiny()
    ev = Evaluator(spec, traffic_matrix(spec, "BFS"), max_batch=4)
    mesh = spec.mesh_design()
    ev.batch([mesh] * 3)                  # pads to 4
    assert ev.n_evals == 3 and ev.n_calls == 1
    ev.batch([mesh] * 10)                 # chunks 4 + 4 + 2 (padded to 2)
    assert ev.n_evals == 13 and ev.n_calls == 4
    ev.batch([])                          # empty: no dispatch, no evals
    assert ev.n_evals == 13 and ev.n_calls == 4
    ev(mesh)                              # single-design path
    assert ev.n_evals == 14 and ev.n_calls == 5


def test_registry_budget_agrees_with_legacy_driver_counts():
    """Acceptance: a registry run at Budget(max_evals=B) spends exactly the
    evaluations the legacy driver call spends, and finds the same Pareto
    objectives."""
    spec = spec_tiny()
    f = traffic_matrix(spec, "BFS")
    B = 150
    ev = Evaluator(spec, f)
    ctx = PhvContext(ev(spec.mesh_design()), CASES["case3"])
    legacy = amosa(spec, ev, ctx, spec.mesh_design(), seed=0, t_max=0.5,
                   t_min=0.05, alpha=0.7, iters_per_temp=10, max_evals=B)

    problem = NocProblem(spec=spec, traffic="BFS", case="case3")
    res = run(problem, "amosa", budget=Budget(max_evals=B, seed=0),
              config=dict(t_max=0.5, t_min=0.05, alpha=0.7,
                          iters_per_temp=10))
    assert res.n_evals == ev.n_evals
    assert np.array_equal(np.sort(np.asarray(res.objs), axis=0),
                          np.sort(legacy.objs, axis=0))


def test_budget_guard_backstops_pcbb(tiny_problem):
    """PCBB has no native max_evals — the uniform guard stops it and the
    recorder's best-so-far Pareto set is returned."""
    problem, ev, ctx = tiny_problem
    cap = ev.n_evals + 40
    res = run(problem, "pcbb", budget=Budget(max_evals=cap, seed=0),
              config=dict(max_expansions=500), ev=ev, ctx=ctx)
    assert res.exhausted
    assert len(res.designs) >= 1
    # Overshoot bounded by the single dispatch in flight when the guard fired.
    assert ev.n_evals <= cap + 8


def test_budget_guard_max_calls(tiny_problem):
    problem, ev, ctx = tiny_problem
    res = run(problem, "nsga2",
              budget=Budget(max_calls=ev.n_calls + 2, seed=0),
              config=dict(pop_size=8, generations=10), ev=ev, ctx=ctx)
    assert res.exhausted and res.n_calls <= 3


# ---------------------------------------------------------------------------
# Limits armed on the evaluator (budget, shard deadline)
# ---------------------------------------------------------------------------
def _armed_limit(kind: str, ev):
    """(check, exception) for a limit that ``ev`` has already reached."""
    if kind == "deadline":
        from repro.dist.worker import ShardDeadlineExceeded, deadline_limit

        return deadline_limit(time.monotonic() - 1.0), ShardDeadlineExceeded
    budget = (Budget(max_evals=ev.n_evals) if kind == "max_evals"
              else Budget(max_calls=ev.n_calls))
    return budget_limit(ev, budget), BudgetExhausted


@pytest.mark.parametrize("limit", ["max_evals", "max_calls", "deadline"])
@pytest.mark.parametrize("dispatch", ["batch_aux", "batch_moves-delta_off",
                                      "batch_moves-delta_on", "edp"])
def test_armed_limit_raises_before_dispatch(dispatch, limit):
    """A limit armed with ``Evaluator.limited`` raises its own exception at
    every public dispatch entry — ``batch_aux``, ``batch_moves`` on the
    dense path and on the host-table path, and ``edp`` — before anything
    is dispatched or a routing table is built; once disarmed, the same call
    dispatches."""
    spec = spec_tiny()
    ev = Evaluator(spec, traffic_matrix(spec, "BFS"),
                   delta="on" if dispatch.endswith("delta_on") else "off")
    mesh = spec.mesh_design()
    ev.batch([mesh])  # the counters a budget at them has reached
    moves = sample_neighbor_moves(spec, mesh, np.random.default_rng(0),
                                  n_swaps=2, n_link_moves=2)
    call = {"batch_aux": lambda: ev.batch_aux([mesh]),
            "batch_moves-delta_off": lambda: ev.batch_moves(moves),
            "batch_moves-delta_on": lambda: ev.batch_moves(moves),
            "edp": lambda: ev.edp(mesh)}[dispatch]
    check, exc = _armed_limit(limit, ev)
    n_evals, n_calls = ev.n_evals, ev.n_calls
    stats = dict(ev.delta_stats)
    with ev.limited(check):
        with pytest.raises(exc):
            call()
    assert (ev.n_evals, ev.n_calls) == (n_evals, n_calls)
    assert ev.delta_stats == stats
    call()
    assert ev.n_calls == n_calls + 1


def test_budget_disarmed_when_run_returns(tiny_problem):
    """``run`` arms its budget on the evaluator only while it runs: a run
    that stops on a spent budget leaves the evaluator free for the next
    run with a larger budget, and for plain calls. Nested limits are
    checked newest first."""
    from repro.dist.worker import ShardDeadlineExceeded, deadline_limit

    problem, ev, ctx = tiny_problem
    mesh = problem.spec.mesh_design()
    spent = run(problem, "local", budget=Budget(max_evals=ev.n_evals, seed=0),
                config=SMALL_CONFIGS["local"], ev=ev, ctx=ctx)
    assert spent.exhausted and spent.n_evals == 0
    again = run(problem, "local",
                budget=Budget(max_evals=ev.n_evals + 20, seed=0),
                config=SMALL_CONFIGS["local"], ev=ev, ctx=ctx)
    assert again.n_evals > 0
    n_calls = ev.n_calls
    ev.batch([mesh])
    assert ev.n_calls == n_calls + 1
    with ev.limited(deadline_limit(time.monotonic() - 1.0)):
        with ev.limited(budget_limit(ev, Budget(max_calls=ev.n_calls))):
            with pytest.raises(BudgetExhausted):
                ev.batch([mesh])
        with pytest.raises(ShardDeadlineExceeded):
            ev.batch([mesh])
    ev.batch([mesh])
    assert ev.n_calls == n_calls + 2


def test_run_callback_streams_telemetry(tiny_problem):
    problem, ev, ctx = tiny_problem
    events = []
    run(problem, "local", budget=Budget(seed=1),
        config=dict(n_starts=1, n_swaps=4, n_link_moves=4, max_steps=3),
        callback=events.append, ev=ev, ctx=ctx)
    assert events, "callback never fired"
    evs = [e["n_evals"] for e in events]
    assert evs == sorted(evs)
    assert all({"n_evals", "n_calls", "best_edp", "wall_s"} <= set(e)
               for e in events)


# ---------------------------------------------------------------------------
# AMOSA adaptive speculative block
# ---------------------------------------------------------------------------
def test_amosa_adaptive_block_budget_pinned(tiny_problem):
    """Adaptive blocks clip to the remaining budget: a budget-bound chain
    spends max_evals exactly (no speculative overshoot), and the archive
    stays mutually non-dominated."""
    problem, ev, ctx = tiny_problem
    spec = problem.spec
    start = ev.n_evals
    B = start + 120
    arch = amosa(spec, ev, ctx, spec.mesh_design(), seed=3, t_max=1.0,
                 t_min=1e-6, alpha=0.7, iters_per_temp=10, max_evals=B,
                 adaptive_block=True, block_max=16)
    assert ev.n_evals == B, "adaptive blocks must land exactly on the budget"
    sub = arch.objs[:, list(ctx.obj_idx)]
    for i in range(sub.shape[0]):
        for j in range(sub.shape[0]):
            if i != j:
                assert not dominates(sub[i], sub[j])


def test_amosa_default_block_unchanged(tiny_problem):
    """block_size=1 (the default) keeps exact sequential accounting — the
    adaptive machinery must not perturb the legacy path."""
    problem, ev, ctx = tiny_problem
    spec = problem.spec
    start = ev.n_evals
    B = start + 60
    a1 = amosa(spec, ev, ctx, spec.mesh_design(), seed=11, t_max=0.5,
               t_min=1e-6, alpha=0.7, iters_per_temp=10, max_evals=B)
    assert ev.n_evals == B
    B2 = ev.n_evals + 60
    a2 = amosa(spec, ev, ctx, spec.mesh_design(), seed=11, t_max=0.5,
               t_min=1e-6, alpha=0.7, iters_per_temp=10, max_evals=B2,
               block_size=1, adaptive_block=False)
    assert np.array_equal(np.sort(a1.objs, axis=0), np.sort(a2.objs, axis=0))
    with pytest.raises(ValueError, match="block_size"):
        amosa(spec, ev, ctx, spec.mesh_design(), block_size=0)


# ---------------------------------------------------------------------------
# Move validation — real exceptions, not -O-stripped asserts
# ---------------------------------------------------------------------------
def test_move_link_validation_raises():
    spec = spec_tiny()
    mesh = spec.mesh_design()
    from repro.core.problem import absent_planar_pairs, existing_planar_links

    links = existing_planar_links(spec, mesh.adj)
    holes = absent_planar_pairs(spec, mesh.adj)
    # Valid move works.
    moved = mesh.move_link(links[0], holes[0])
    assert int(np.triu(moved.adj).sum()) == spec.n_planar_links
    # Removing a non-existent link.
    with pytest.raises(ValueError, match="non-existent"):
        mesh.move_link(holes[0], holes[1])
    # Adding an already-present link.
    with pytest.raises(ValueError, match="already-present"):
        mesh.move_link(links[0], links[1])
    # Self-links.
    with pytest.raises(ValueError, match="self-link"):
        mesh.move_link(links[0], (2, 2))
    with pytest.raises(ValueError, match="differ"):
        mesh.swap_tiles(1, 1)
    # The original design is untouched by a failed move.
    assert mesh.key() == spec.mesh_design().key()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def test_cli_run_smoke(capsys):
    """Tier-1 gate: the CLI smoke run (registry dispatch + budget
    enforcement + JSON round trip) must pass."""
    from repro.noc import cli

    assert cli.main(["run", "--smoke", "--quiet"]) == 0
    assert "smoke ok" in capsys.readouterr().out
