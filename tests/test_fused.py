"""Fused meta-search scoring (core.fused + kernels/stage_fused) and the
device PHV twin (core.phv_jnp).

Conformance contract (DESIGN.md §12): the fused path computes features in
f32, so at large specs a feature can land within f32 rounding of a forest
threshold and flip a branch — both trajectories are valid surrogate
ascents. At spec_tiny the margins are wide and the parity tests here pin
EXACT agreement: same accepted moves, same designs, same training rows.
The Pallas tail is pinned bit-equal to the jnp tail it replaces (same f32
compares, same first-max tie-break as np.argmax)."""

import numpy as np
import pytest

from repro.core import (CASES, Evaluator, PhvContext, random_design,
                        spec_16, spec_tiny, traffic_matrix)
from repro.core.features import design_features_batch
from repro.core.forest import RegressionForest
from repro.core.fused import (META_BACKENDS, MetaScorer, check_meta_backend,
                              _fused_consts)
from repro.core.pareto import hypervolume_with_batch
from repro.core.phv_jnp import hypervolume_with_batch_jnp
from repro.core.problem import sample_neighbor_moves, sample_neighbors
from repro.core.stage import _meta_greedy, _meta_greedy_host, stage_batch


def _fit_forest(spec, n=60, seed=0):
    """Forest fitted on real featurized designs (realistic thresholds)."""
    rng = np.random.default_rng(seed)
    designs = [random_design(spec, rng) for _ in range(n)]
    x = design_features_batch(spec, designs)
    y = rng.normal(size=n) + x[:, 0]
    return RegressionForest(seed=seed, n_trees=8, max_depth=5).fit(x, y)


# ---------------------------------------------------------------- moves rep
def test_neighbor_moves_match_materialized_designs():
    """materialize_all() reproduces the legacy sample_neighbors stream:
    same rng consumption, same designs in the same (swaps-first) order."""
    spec = spec_tiny()
    for seed in range(4):
        d = random_design(spec, np.random.default_rng(seed))
        moves = sample_neighbor_moves(spec, d, np.random.default_rng(seed + 9),
                                      n_swaps=8, n_link_moves=8)
        legacy = sample_neighbors(spec, d, np.random.default_rng(seed + 9),
                                  n_swaps=8, n_link_moves=8)
        assert len(moves) == len(legacy)
        for j, dl in enumerate(legacy):
            dm = moves.materialize(j)
            assert np.array_equal(dm.perm, dl.perm)
            assert np.array_equal(dm.adj, dl.adj)


def test_meta_backend_validation():
    for b in META_BACKENDS:
        check_meta_backend(b)
    check_meta_backend(None, allow_none=True)
    with pytest.raises(ValueError):
        check_meta_backend("nope")
    with pytest.raises(ValueError):
        check_meta_backend(None)
    # MetaScorer is the device arm only.
    spec = spec_tiny()
    with pytest.raises(ValueError):
        MetaScorer(spec, _fit_forest(spec), backend="host")


# ------------------------------------------------------------ feature twin
@pytest.mark.parametrize("spec_fn", [spec_tiny, spec_16])
def test_fused_features_conform_to_host(spec_fn):
    """Fused f32 featurization of base+move candidates matches the host f64
    design_features_batch of the materialized designs to f32 tolerance."""
    import jax.numpy as jnp

    from repro.core.fused import _fused_features

    spec = spec_fn()
    rng = np.random.default_rng(0)
    d = random_design(spec, rng)
    moves = sample_neighbor_moves(spec, d, rng, n_swaps=6, n_link_moves=6)
    sc = MetaScorer(spec, _fit_forest(spec))
    sa, sb, er, ea = sc._encode(moves)
    base_perm, base_lm, scalars = sc._base_state(d)
    got = np.asarray(_fused_features(sc.c, base_perm, base_lm, scalars,
                                     jnp.asarray(sa), jnp.asarray(sb),
                                     jnp.asarray(er), jnp.asarray(ea)))
    want = design_features_batch(spec, moves.materialize_all())
    b = len(moves)
    np.testing.assert_allclose(got[:b], want, rtol=3e-5, atol=3e-6)
    # Identity-padded tail rows reproduce the base design's features.
    base_feats = design_features_batch(spec, [d])[0]
    for row in got[b:]:
        np.testing.assert_allclose(row, base_feats, rtol=3e-5, atol=3e-6)


def test_score_moves_matches_host_predict():
    """score_moves == argmax of predict(features(materialized designs)),
    and score_base == predict on the base design (spec_tiny, f32 exact)."""
    spec = spec_tiny()
    model = _fit_forest(spec)
    sc = MetaScorer(spec, model)
    rng = np.random.default_rng(3)
    for _ in range(5):
        d = random_design(spec, rng)
        moves = sample_neighbor_moves(spec, d, rng, n_swaps=8, n_link_moves=8)
        if not len(moves):
            continue
        j, vj = sc.score_moves(moves)
        want = model.predict(
            design_features_batch(spec, moves.materialize_all()))
        assert j == int(np.argmax(want))
        assert vj == pytest.approx(float(want.max()), rel=1e-6)
        assert sc.score_base(d) == pytest.approx(
            float(model.predict(design_features_batch(spec, [d]))[0]),
            rel=1e-6)


# ------------------------------------------------------------- meta parity
def test_meta_greedy_fused_matches_host_spec_tiny():
    """Full greedy ascent parity at spec_tiny: identical accepted designs
    for host and fused backends across seeds (identical rng streams)."""
    spec = spec_tiny()
    model = _fit_forest(spec)
    for seed in range(5):
        d0 = random_design(spec, np.random.default_rng(seed))
        d_host = _meta_greedy_host(spec, model, d0,
                                   np.random.default_rng(100 + seed),
                                   n_swaps=8, n_link_moves=8, max_steps=10)
        d_fused = _meta_greedy(spec, model, d0,
                               np.random.default_rng(100 + seed),
                               n_swaps=8, n_link_moves=8, max_steps=10,
                               backend="fused")
        assert d_host.key() == d_fused.key()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_stage_batch_meta_backend_parity_tiny(seed):
    """End-to-end stage_batch equality host vs fused at spec_tiny: same
    global Pareto set (hence equal PHV), same surrogate training rows —
    the equal-PHV-at-equal-budget leg of the PR-9 acceptance check."""
    spec = spec_tiny()
    f = traffic_matrix(spec, "BFS")
    outs = {}
    for mb in ("host", "fused"):
        res = stage_batch(spec, f, n_starts=2, seed=seed, iters_max=3,
                          n_swaps=6, n_link_moves=6, max_local_steps=10,
                          meta_backend=mb)
        outs[mb] = res
    h, g = outs["host"], outs["fused"]
    assert sorted(d.key() for d in h.global_set.designs) == \
        sorted(d.key() for d in g.global_set.designs)
    # Equal eval budget: both arms visited the same number of designs.
    # Full row-for-row trajectory equality is NOT asserted here — CART
    # thresholds land exactly on discrete training feature values, so a
    # 1-ulp f32-vs-f64 difference can flip a knife-edge accept mid-run
    # without changing the front (single-call trajectory parity is pinned
    # separately by test_meta_greedy_fused_matches_host_spec_tiny).
    assert h.x_train.shape == g.x_train.shape
    assert h.y_train.shape == g.y_train.shape


# --------------------------------------------------------- jit-cache churn
def test_score_jit_one_compile_per_padded_shape():
    """Neighborhood sizes that pad to the same power of two share one
    compile — the fused scorer cannot retrace per neighborhood (the PR-4
    shape-cache discipline)."""
    from repro.core import fused as fused_mod

    spec = spec_tiny()
    model = _fit_forest(spec)
    sc = MetaScorer(spec, model)
    rng = np.random.default_rng(0)
    d = random_design(spec, rng)

    fn = fused_mod._SCORE_JIT
    before = fn._cache_size()
    sizes = []
    for ns, nl in [(5, 4), (4, 4), (6, 2), (3, 5), (7, 1)]:
        moves = sample_neighbor_moves(spec, d, rng, n_swaps=ns,
                                      n_link_moves=nl)
        sizes.append(len(moves))
        sc.score_moves(moves)
    pads = {1 << max(0, (s - 1).bit_length()) for s in sizes}
    assert fn._cache_size() - before <= len(pads)
    # And repeating the largest neighborhood adds nothing.
    mid = fn._cache_size()
    for _ in range(3):
        sc.score_moves(sample_neighbor_moves(spec, d, rng, n_swaps=7,
                                             n_link_moves=1))
    assert fn._cache_size() == mid


def test_refit_reuses_compiled_scorer_and_traversal():
    """Forests of one configuration fitted on different row counts have
    different node counts but one device shape: the second forest adds no
    compile to the fused scorer or to the standalone traversal at a
    neighborhood pad the first already used (a STAGE refit compiles
    nothing)."""
    from repro.core import forest as forest_mod
    from repro.core import fused as fused_mod

    spec = spec_tiny()
    small = _fit_forest(spec, n=20, seed=3)
    large = _fit_forest(spec, n=200, seed=3)
    assert small._flat["n_nodes"] != large._flat["n_nodes"]
    (ts, key_s), (tl, key_l) = small.jnp_tensors(), large.jnp_tensors()
    assert key_s == key_l == (5, 8, 64)
    assert [a.shape for a in ts] == [a.shape for a in tl]
    assert [a.dtype for a in ts] == [a.dtype for a in tl]

    d = random_design(spec, np.random.default_rng(0))
    xq = design_features_batch(
        spec, [random_design(spec, np.random.default_rng(s))
               for s in range(5)])

    def run(model):
        sc = MetaScorer(spec, model)
        sc.score_moves(sample_neighbor_moves(
            spec, d, np.random.default_rng(1), n_swaps=5, n_link_moves=4))
        sc.score_base(d)
        return model.predict(xq, backend="jnp")

    run(small)
    score, traverse = fused_mod._SCORE_JIT, forest_mod._JITTED_FLAT
    n_score, n_trav = score._cache_size(), traverse._cache_size()
    out = run(large)
    assert score._cache_size() == n_score
    assert traverse._cache_size() == n_trav
    np.testing.assert_allclose(out, large.predict(xq, backend="numpy"),
                               rtol=0, atol=1e-6)


# -------------------------------------------------------------- pallas arm
@pytest.mark.interpret
@pytest.mark.parametrize("nsl", [(1, 0), (3, 2), (8, 8), (24, 24)])
def test_pallas_score_interpret_matches_jnp(nsl):
    """fused-pallas (interpret) returns the same (argmax, value) as the jnp
    tail at odd / padded / multi-block batch sizes."""
    spec = spec_tiny()
    model = _fit_forest(spec)
    sc_j = MetaScorer(spec, model, backend="fused")
    sc_p = MetaScorer(spec, model, backend="fused-pallas", interpret=True)
    assert sc_p.pallas  # interpret mode always resolves to the kernel
    rng_a = np.random.default_rng(7)
    rng_b = np.random.default_rng(7)
    ns, nl = nsl
    for _ in range(3):
        d = random_design(spec, np.random.default_rng(11))
        mv_a = sample_neighbor_moves(spec, d, rng_a, n_swaps=ns,
                                     n_link_moves=nl)
        mv_b = sample_neighbor_moves(spec, d, rng_b, n_swaps=ns,
                                     n_link_moves=nl)
        if not len(mv_a):
            continue
        j_j, v_j = sc_j.score_moves(mv_a)
        j_p, v_p = sc_p.score_moves(mv_b)
        assert j_p == j_j
        assert v_p == pytest.approx(v_j, rel=1e-6, abs=1e-7)
    assert sc_p.pallas  # no silent fallback happened


@pytest.mark.interpret
def test_meta_greedy_pallas_matches_fused():
    """backend='fused-pallas' (interpret) walks the same trajectory as
    'fused' — the kernel argmax semantics match the host prefix argmax."""
    spec = spec_tiny()
    model = _fit_forest(spec)
    d0 = random_design(spec, np.random.default_rng(2))
    d_f = _meta_greedy(spec, model, d0, np.random.default_rng(42),
                       n_swaps=8, n_link_moves=8, max_steps=8,
                       backend="fused")
    sc = MetaScorer(spec, model, backend="fused-pallas", interpret=True)
    d_p = _meta_greedy(spec, model, d0, np.random.default_rng(42),
                       n_swaps=8, n_link_moves=8, max_steps=8,
                       backend="fused-pallas", scorer=sc)
    assert d_f.key() == d_p.key()


def test_pallas_off_tpu_falls_back_to_jnp_tail():
    """Explicit fused-pallas without interpret raises at construction on
    every platform; no scorer silently serves the jnp tail instead."""
    spec = spec_tiny()
    model = _fit_forest(spec)
    with pytest.raises(ValueError, match="interpret=True"):
        MetaScorer(spec, model, backend="fused-pallas")
    assert MetaScorer(spec, model, backend="fused-pallas",
                      interpret=True).pallas
    assert not MetaScorer(spec, model, backend="fused").pallas


# ------------------------------------------------------------ PHV jnp twin
@pytest.mark.parametrize("m", [1, 2, 3, 4])
def test_phv_jnp_twin_conforms(m):
    """Device twin vs host f64 oracle at m=1..4, including dominated rows,
    duplicates, and candidates beyond ref."""
    rng = np.random.default_rng(m)
    ref = np.full(m, 1.6)
    pts = rng.uniform(0.2, 1.5, size=(9, m))
    pts = np.vstack([pts, pts[:2]])           # duplicates
    cands = rng.uniform(0.1, 1.9, size=(13, m))  # some beyond ref
    want = hypervolume_with_batch(pts, cands, ref)
    got = hypervolume_with_batch_jnp(pts, cands, ref)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-6)


def test_phv_jnp_twin_empty_set():
    ref = np.full(3, 1.6)
    cands = np.random.default_rng(0).uniform(0.2, 1.5, size=(5, 3))
    want = hypervolume_with_batch(np.zeros((0, 3)), cands, ref)
    got = hypervolume_with_batch_jnp(np.zeros((0, 3)), cands, ref)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-6)


def test_phv_context_backend_knob():
    """PhvContext(phv_backend='jnp') routes phv_with_batch through the twin
    (f32-close to host) while scalar phv stays host-exact; bad names raise
    at construction."""
    spec = spec_tiny()
    f = traffic_matrix(spec, "BFS")
    ev = Evaluator(spec, f)
    mesh_objs = ev(spec.mesh_design())
    with pytest.raises(ValueError):
        PhvContext(mesh_objs, CASES["case3"], phv_backend="cuda")
    ctx_h = PhvContext(mesh_objs, CASES["case3"])
    ctx_j = PhvContext(mesh_objs, CASES["case3"], phv_backend="jnp")
    rng = np.random.default_rng(1)
    objs = ev.batch([random_design(spec, rng) for _ in range(6)])
    want = ctx_h.phv_with_batch(objs[:4], objs[4:])
    got = ctx_j.phv_with_batch(objs[:4], objs[4:])
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-6)
    assert ctx_j.phv(objs) == ctx_h.phv(objs)  # scalar path is shared


# ------------------------------------------------------------- spmd parity
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_spmd_evaluator_matches_serial(backend):
    """Evaluator built under spmd_scope (1 device here) is bit-equal to the
    plain path — sharding the batch axis reorders no reductions. The
    pallas case runs the APSP kernel (interpreted) inside shard_map."""
    from repro.core.evaluate import make_spmd_mesh, spmd_scope

    spec = spec_tiny()
    f = traffic_matrix(spec, "BFS")
    rng = np.random.default_rng(5)
    designs = [random_design(spec, rng) for _ in range(6)]
    interp = backend == "pallas"
    ev = Evaluator(spec, f)
    with spmd_scope(make_spmd_mesh()):
        ev_s = Evaluator(spec, f, backend=backend, interpret=interp)
    assert ev_s._spmd_fn is not None and ev._spmd_fn is None
    a, aux_a = ev.batch_aux(designs)
    b, aux_b = ev_s.batch_aux(designs)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(aux_a["net_lat"], aux_b["net_lat"])


@pytest.mark.slow
def test_spmd_multi_device_subprocess():
    """4 host devices (XLA_FLAGS) — the spmd evaluator and the 'spmd' dist
    executor both reproduce the serial numbers exactly."""
    import os
    import subprocess
    import sys

    code = """
import numpy as np
from repro.core import Evaluator, random_design, spec_tiny, traffic_matrix
from repro.core.evaluate import make_spmd_mesh, spmd_scope
import jax
assert jax.device_count() == 4, jax.device_count()
spec = spec_tiny()
f = traffic_matrix(spec, "BFS")
rng = np.random.default_rng(0)
designs = [random_design(spec, rng) for _ in range(6)]
want = Evaluator(spec, f).batch(designs)
with spmd_scope(make_spmd_mesh()):
    ev = Evaluator(spec, f)
got = ev.batch(designs)  # pads 6 -> 8, divisible by 4 devices
np.testing.assert_array_equal(want, got)
with spmd_scope(make_spmd_mesh()):
    ev_k = Evaluator(spec, f, backend="pallas", interpret=True)
np.testing.assert_array_equal(want, ev_k.batch(designs))
print("SPMD-OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=".",
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SPMD-OK" in out.stdout


def test_dist_spmd_executor_matches_serial():
    """run_dist(executor='spmd') reproduces executor='serial' exactly on a
    single device (in-order shards, one mesh program per dispatch)."""
    from repro.dist import run_dist
    from repro.noc.api import Budget, NocProblem
    from repro.noc.optimizers import StageDistConfig

    problem = NocProblem(spec_tiny(), traffic="BFS")
    budget = Budget(max_evals=60, seed=0)
    cfg_s = StageDistConfig(n_workers=2, executor="serial", iters_max=2,
                            n_swaps=4, n_link_moves=4, max_local_steps=6)
    cfg_m = StageDistConfig(n_workers=2, executor="spmd", iters_max=2,
                            n_swaps=4, n_link_moves=4, max_local_steps=6)
    r_s = run_dist(problem, budget, cfg_s)
    r_m = run_dist(problem, budget, cfg_m)
    np.testing.assert_array_equal(r_s.objs, r_m.objs)
    assert r_s.n_evals == r_m.n_evals
