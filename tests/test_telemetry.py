"""The program's spans and counters (``repro.telemetry``).

Spans nest per thread, self time is a span's duration minus what its
children cover, the ring reports what it pushed out, JAX's compile events
become ``jit.*`` spans and counters, a seeded search records every phase
of DESIGN.md's span table with counts that match the evaluator's own, and
inside a profiler session the spans land on the trace's host plane.
"""

import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core.evaluate import Evaluator
from repro.core.problem import spec_16, spec_tiny
from repro.noc import Budget, NocProblem, run

#: every span name a seeded stage_batch with host table deltas records
SEARCH_SPANS = {
    "noc.run", "stage.iter", "stage.features", "stage.fit", "stage.meta",
    "meta.step", "local.step", "local.sample", "local.select",
    "local.archive", "eval.dispatch", "eval.pack", "eval.wait",
    "eval.tables", "tables.build", "jit.compile", "jit.lower",
}
SMALL = {"n_starts": 2, "iters_max": 3, "n_swaps": 6, "n_link_moves": 6,
         "max_local_steps": 6}


def _since(t0_ns, name=None):
    return [s for s in telemetry.spans()
            if s.t0_ns >= t0_ns and (name is None or s.name == name)]


def _tiny_search(seed=5):
    """A seeded registry stage_batch at spec_tiny on a fresh evaluator with
    host table deltas on (so ``eval.tables`` runs too)."""
    problem = NocProblem(spec=spec_tiny(), traffic="BFS", case="case5")
    ev = Evaluator(problem.spec, problem.traffic_matrix(), delta="on")
    res = run(problem, "stage_batch", budget=Budget(max_evals=300, seed=seed),
              config=SMALL, ev=ev)
    return ev, res


def test_nesting_parents_and_self_time():
    t0 = time.perf_counter_ns()
    with telemetry.span("test.outer"):
        with telemetry.span("test.inner"):
            time.sleep(0.01)
        time.sleep(0.005)
        with telemetry.span("test.inner"):
            time.sleep(0.01)
    inner = _since(t0, "test.inner")
    (outer,) = _since(t0, "test.outer")
    assert len(inner) == 2
    assert all(s.parent == outer.sid for s in inner)
    assert outer.parent == -1
    assert all(outer.t0_ns <= s.t0_ns and s.t1_ns <= outer.t1_ns
               for s in inner)
    # the ring is in the order spans ended: children before their parent
    names = [s.name for s in _since(t0)]
    assert names == ["test.inner", "test.inner", "test.outer"]
    self_ns = (outer.t1_ns - outer.t0_ns
               - sum(s.t1_ns - s.t0_ns for s in inner))
    assert 0.004 < self_ns * 1e-9 < 0.5
    tot = telemetry.totals()["test.outer"]
    assert tot["self_s"] <= tot["seconds"]


def test_self_time_in_totals_is_duration_minus_children():
    before = telemetry.totals().get("test.self", {"self_s": 0.0,
                                                  "seconds": 0.0})
    t0 = time.perf_counter_ns()
    with telemetry.span("test.self"):
        with telemetry.span("test.child"):
            time.sleep(0.02)
    after = telemetry.totals()["test.self"]
    (outer,) = _since(t0, "test.self")
    (child,) = _since(t0, "test.child")
    want = (outer.t1_ns - outer.t0_ns) - (child.t1_ns - child.t0_ns)
    assert after["self_s"] - before["self_s"] == pytest.approx(
        want * 1e-9, abs=1e-9)
    assert after["seconds"] - before["seconds"] == pytest.approx(
        (outer.t1_ns - outer.t0_ns) * 1e-9, abs=1e-9)


def test_attribute_sums_in_totals():
    before = telemetry.totals().get("test.attrs", {"count": 0, "attrs": {}})
    for rows in (3, 4, 5):
        with telemetry.span("test.attrs", rows=rows, padded=8):
            pass
    with telemetry.span("test.attrs") as sp:
        sp.attrs["rows"] = 10
    after = telemetry.totals()["test.attrs"]
    assert after["count"] - before["count"] == 4
    got = {k: v - before["attrs"].get(k, 0)
           for k, v in after["attrs"].items()}
    assert got == {"rows": 22, "padded": 24}


def test_span_records_when_the_body_raises():
    t0 = time.perf_counter_ns()
    with pytest.raises(KeyError):
        with telemetry.span("test.raises"):
            raise KeyError("x")
    assert len(_since(t0, "test.raises")) == 1
    with telemetry.span("test.after"):
        pass
    (after,) = _since(t0, "test.after")
    assert after.parent == -1           # the stack was unwound


def test_dropped_after_the_ring_overflows():
    d0 = telemetry.dropped()
    n = telemetry.CAPACITY + 7
    for _ in range(n):
        with telemetry.span("test.flood"):
            pass
    ring = telemetry.spans()
    assert len(ring) == telemetry.CAPACITY
    assert telemetry.dropped() - d0 >= 7
    assert ring[-1].name == "test.flood"
    assert telemetry.totals()["test.flood"]["count"] >= n


def test_jit_compile_span_names_the_function():
    def telemetry_probe(x):
        return jnp.sin(x) * 3.0 + 1.0

    fn = jax.jit(telemetry_probe)
    t0 = time.perf_counter_ns()
    with telemetry.span("test.compiling"):
        fn(jnp.arange(7.0)).block_until_ready()
    (outer,) = _since(t0, "test.compiling")
    comp = [s for s in _since(t0, "jit.compile")
            if "telemetry_probe" in s.attrs["fun"]]
    low = [s for s in _since(t0, "jit.lower")
           if "telemetry_probe" in s.attrs["fun"]]
    assert comp and low
    for s in comp + low:
        assert s.parent == outer.sid
        assert outer.t0_ns <= s.t0_ns <= s.t1_ns <= outer.t1_ns
    # the compile is a child: the outer span's self time leaves it out,
    # and never goes negative where nested traces overlap
    tot = telemetry.totals()["test.compiling"]
    assert 0 <= tot["self_s"] < (outer.t1_ns - outer.t0_ns) * 1e-9


def test_seeded_stage_batch_records_every_phase():
    t0 = time.perf_counter_ns()
    ev, res = _tiny_search()
    spans = _since(t0)
    assert SEARCH_SPANS <= {s.name for s in spans}
    disp = [s for s in spans if s.name == "eval.dispatch"]
    assert sum(s.attrs["rows"] for s in disp) == ev.n_evals
    assert len(disp) == ev.n_calls
    assert all(s.attrs["padded"] >= s.attrs["rows"] for s in disp)
    assert int(res.n_evals) == ev.n_evals
    (top,) = [s for s in spans if s.name == "noc.run"]
    by_sid = {s.sid: s for s in spans}
    for s in spans:
        if s.name.startswith(("stage.", "local.", "meta.", "eval.")):
            p = s
            while p.parent in by_sid:
                p = by_sid[p.parent]
            assert p is top, s.name
    for s in spans:
        if s.name in ("eval.pack", "eval.wait"):
            assert by_sid[s.parent].name == "eval.dispatch"
        if s.name == "meta.step":
            assert by_sid[s.parent].name == "stage.meta"
            assert s.attrs["cands"] > 0


def test_delta_path_counters_sum_to_delta_stats():
    """The counts on ``eval.tables`` add up to the evaluator's
    ``delta_stats``, and each full host build is a ``tables.build`` span
    under it, named by why it ran."""
    problem = NocProblem(spec=spec_16(), traffic="BFS", case="case5")
    ev = Evaluator(problem.spec, problem.traffic_matrix(), delta="on")
    t0 = time.perf_counter_ns()
    run(problem, "stage_batch", budget=Budget(max_evals=120, seed=3),
        config=SMALL, ev=ev)
    spans = _since(t0)
    tables = [s for s in spans if s.name == "eval.tables"]
    st = ev.delta_stats
    assert st["swap"] and st["delta"]
    for attr, key in (("swaps", "swap"), ("deltas", "delta"),
                      ("fallbacks", "fallback"), ("misses", "table_misses")):
        assert sum(s.attrs[attr] for s in tables) == st[key], attr
    builds = [s for s in spans if s.name == "tables.build"]
    by_sid = {s.sid: s for s in spans}
    assert all(by_sid[s.parent].name == "eval.tables" for s in builds)
    assert sum(s.attrs["why"] == "miss" for s in builds) == st["table_misses"]
    assert (sum(s.attrs["why"] == "fallback" for s in builds)
            == st["fallback"])


@pytest.mark.parametrize("path", ["batch_aux", "batch_moves"])
def test_dispatch_spans_carry_the_walks_steps_and_cap(path):
    """Both dispatch paths, the dense ``batch_aux`` and the delta path's
    ``batch_moves``, give each ``eval.dispatch`` span ``walk_steps``, the
    longest walk of its batch, and ``walk_cap``, ``max_hops``."""
    from repro.core import routing
    from repro.core.objectives import design_cost_np, make_consts
    from repro.core.problem import random_design, sample_neighbor_moves
    from repro.kernels.ref import walk_accumulate_np

    problem = NocProblem(spec=spec_16(), traffic="BFS", case="case5")
    spec = problem.spec
    ev = Evaluator(spec, problem.traffic_matrix(), delta="on", max_batch=8)
    rng = np.random.default_rng(7)
    mv = sample_neighbor_moves(spec, random_design(spec, rng), rng, 6, 6)
    ds = mv.materialize_all()
    c = make_consts(spec)
    longest = []
    for d in ds:
        nh = routing.host_tables(design_cost_np(spec, d.adj)).nh
        h = walk_accumulate_np(nh, np.ones(nh.shape), c.link_delay,
                               max_hops=c.max_hops)[0]
        longest.append(int(h.max()))
    t0 = time.perf_counter_ns()
    if path == "batch_aux":
        ev.batch_aux(ds)
    else:
        ev.batch_moves(mv)
    disp = _since(t0, "eval.dispatch")
    assert [s.attrs["rows"] for s in disp] == [8, len(ds) - 8]
    assert [s.attrs["walk_steps"] for s in disp] == [max(longest[:8]),
                                                     max(longest[8:])]
    assert all(s.attrs["walk_cap"] == spec.max_hops for s in disp)


def test_spans_leave_the_search_unchanged():
    """The same seeded search twice: same front, same span sequence."""
    out = []
    for _ in range(2):
        t0 = time.perf_counter_ns()
        _, res = _tiny_search(seed=11)
        names = [s.name for s in _since(t0)
                 if not s.name.startswith("jit.")]
        out.append((np.asarray(res.objs).tobytes(),
                    [d.key() for d in res.designs], names))
    assert out[0] == out[1]


def test_profiler_trace_holds_program_spans(tmp_path):
    from jax.profiler import ProfileData

    from repro.core.local_search import local_search_batch
    from repro.core.objectives import CASES
    from repro.core.pareto import PhvContext

    spec = spec_tiny()
    problem = NocProblem(spec=spec, traffic="BFS", case="case5")
    ev = problem.evaluator()
    ctx = PhvContext(ev(spec.mesh_design()), CASES["case5"])
    rng = np.random.default_rng(0)
    local_search_batch(spec, ev, ctx, [spec.mesh_design()], rng,
                       n_swaps=4, n_link_moves=4, max_steps=2)  # compile
    t0 = time.perf_counter_ns()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.window"):
            local_search_batch(spec, ev, ctx, [spec.mesh_design()], rng,
                               n_swaps=4, n_link_moves=4, max_steps=2)
    n_steps = len(_since(t0, "local.step"))
    n_disp = len(_since(t0, "eval.dispatch"))
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    events, window = [], None
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                iv = (e.start_ns, e.start_ns + e.duration_ns)
                if e.name == "test.window":
                    window = iv
                elif e.name.startswith("repro."):
                    events.append((e.name, *iv))
    assert window is not None
    names = [n for n, *_ in events]
    assert names.count("repro.local.step") == n_steps >= 1
    assert names.count("repro.eval.dispatch") == n_disp >= 1
    # one clock: the program's spans sit inside the enclosing annotation
    assert all(window[0] <= s <= e <= window[1] for _, s, e in events)


def test_compile_cache_counters(tmp_path, monkeypatch):
    """With the persistent cache on through ``repro.compile_cache``, a
    first compile is a miss and the same program after the in-memory caches
    are cleared is a hit; both show in ``totals()``."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", tmp_path / "cache")
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}

    def counts():
        t = telemetry.totals()
        return (t.get("jit.cache_hit", {}).get("count", 0),
                t.get("jit.cache_miss", {}).get("count", 0))

    def probe(x):
        return jnp.cos(x) * 5.0 - 2.0

    try:
        assert compile_cache.enable() == str(tmp_path / "cache")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        x = np.arange(5.0, dtype=np.float32)
        h0, m0 = counts()
        jax.jit(probe)(x).block_until_ready()
        h1, m1 = counts()
        assert (h1, m1 - m0) == (h0, 1)
        jax.clear_caches()
        jax.jit(probe)(x).block_until_ready()
        h2, m2 = counts()
        assert (h2 - h1, m2) == (1, m1)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_listener_registered_once():
    """Installing again adds no second listener: one compile, one span."""
    telemetry._install()
    telemetry._install()

    def telemetry_once(x):
        return x * 7.0 - 1.0

    t0 = time.perf_counter_ns()
    jax.jit(telemetry_once)(np.ones(3, np.float32)).block_until_ready()
    comp = [s for s in _since(t0, "jit.compile")
            if "telemetry_once" in s.attrs["fun"]]
    assert len(comp) == 1
