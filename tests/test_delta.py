"""Incremental-evaluation (delta) path: bit-equality against the full
rebuild oracle across random move chains, the blocked dense kernels, the
spec_large tier, and the Evaluator integration.

The delta path must be *bit-equal* to a from-scratch recompute — every
finite hop cost is a small integer (exact in f32/f64) and the shadow
tie-breaker perturbations are a pure function of (n, slot pair), so any
correct shortest-path scheme lands on identical tables. These tests pin
that contract; see DESIGN.md §13 and the notes in core/routing.py.

Property tests need ``hypothesis``; without it they are skipped and the
deterministic seeded chains still run (same pattern as test_pareto)."""

from functools import partial

import numpy as np
import pytest

from repro.core import Evaluator, PhvContext, APP_NAMES
from repro.core import routing
from repro.core.local_search import local_search
from repro.core.objectives import CASES, design_cost_np
from repro.core.problem import (random_design, sample_neighbor_moves,
                                spec_16, spec_64, spec_large, spec_tiny)
from repro.core.traffic import avg_traffic

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - property tests skip without it
    st = None


def _iters(n):
    return routing.apsp_iters(n)


def _link_weight(spec, add):
    return float(np.float32(spec.router_stages)
                 + np.float32(spec.link_delay[add[0], add[1]]))


def _chain_check(spec, seed, steps, *, delta_kw=None, require_delta=True):
    """Drive a random link-move chain through delta_link_move and assert
    every HostTables field bit-equal to a scratch host_tables rebuild."""
    rng = np.random.default_rng(seed)
    d = spec.mesh_design()
    t = routing.host_tables(design_cost_np(spec, d.adj))
    n_delta = 0
    for _ in range(steps):
        mv = sample_neighbor_moves(spec, d, rng, 0, 4)
        if mv.rem.shape[0] == 0:
            continue
        rem, add = tuple(mv.rem[0]), tuple(mv.add[0])
        t2 = routing.delta_link_move(t, rem, add, _link_weight(spec, add),
                                     **(delta_kw or {}))
        d = mv.materialize(0)
        ref = routing.host_tables(design_cost_np(spec, d.adj))
        if t2 is None:
            t2 = ref
        else:
            n_delta += 1
            for f in ref._fields:
                assert np.array_equal(getattr(t2, f), getattr(ref, f)), f
        t = t2
    if require_delta:
        assert n_delta > 0  # the chain actually exercised the delta path
    return n_delta


# ------------------------------------------------------- oracle parity
@pytest.mark.parametrize("spec_fn", [spec_tiny, spec_16, spec_64])
def test_host_tables_bit_equal_device_oracle(spec_fn):
    spec = spec_fn()
    cost = design_cost_np(spec, spec.mesh_design().adj)
    t = routing.host_tables(cost)
    dist, nh = routing.routing_tables(cost, _iters(spec.n_tiles))
    assert np.array_equal(t.dist, np.asarray(dist))
    assert np.array_equal(t.nh, np.asarray(nh))
    # The shadow metric floors back onto the true f32 distances exactly.
    assert np.array_equal(np.floor(t.dist_t).astype(np.float32), t.dist)


def _design_cost(spec_fn, seed):
    """The mesh's hop costs (``seed`` None) or a seeded random design's."""
    spec = spec_fn()
    d = (spec.mesh_design() if seed is None
         else random_design(spec, np.random.default_rng(seed)))
    return design_cost_np(spec, d.adj)


def _cut_cost(kind):
    """spec_64 mesh costs with one tile isolated (its row and column INF)
    or with every edge between slots < 32 and slots >= 32 removed."""
    cost = _design_cost(spec_64, None).copy()
    n = cost.shape[0]
    if kind == "isolated":
        cost[5, :] = cost[:, 5] = routing.INF
        cost[5, 5] = 0.0
    else:
        lo = np.arange(n) < n // 2
        cost[np.ix_(lo, ~lo)] = cost[np.ix_(~lo, lo)] = routing.INF
    return cost


ORACLE_CASES = {
    **{f"{fn.__name__}-{'mesh' if seed is None else f'rand{seed}'}":
       partial(_design_cost, fn, seed)
       for fn in (spec_tiny, spec_16, spec_64, spec_large)
       for seed in (None, 0, 1, 2)},
    "isolated": partial(_cut_cost, "isolated"),
    "split": partial(_cut_cost, "split"),
}


@pytest.mark.parametrize("case", list(ORACLE_CASES))
def test_host_tables_bit_equal_squaring_oracle(case):
    """host_tables (one Floyd–Warshall pass, edge-list next hop) equals the
    squaring oracle in every HostTables field, bit for bit: apsp_np on the
    tie-broken f64 costs, its floor, and the dense next_hop_np. The cut
    cases run _nh_cols_sparse's dense fallback (an isolated tile) and its
    INF-tie fix-up (two components, every tile with a neighbor)."""
    cost = np.asarray(ORACLE_CASES[case](), dtype=np.float32)
    n = cost.shape[0]
    edge = (cost < routing.INF / 2) & ~np.eye(n, dtype=bool)
    if case == "isolated":
        assert not edge.any(axis=1).all()
    if case == "split":
        assert edge.any(axis=1).all()
    cost_t = cost.astype(np.float64)
    cost_t[edge] += routing._tie_eps(n).astype(np.float64)[edge]
    dist_t = routing.apsp_np(cost_t, routing.apsp_iters(n))
    dist = np.floor(dist_t).astype(np.float32)
    if case in ("isolated", "split"):
        assert (dist >= routing.INF).any()
    ref = routing.HostTables(cost, dist, routing.next_hop_np(cost, dist),
                             cost_t, dist_t)
    t = routing.host_tables(cost)
    for f in ref._fields:
        got, want = getattr(t, f), getattr(ref, f)
        assert got.dtype == want.dtype, f
        assert np.array_equal(got, want), f


@pytest.mark.parametrize("spec_fn,seed,steps",
                         [(spec_tiny, 0, 40), (spec_16, 1, 40)])
def test_delta_chain_bit_equal_full_rebuild(spec_fn, seed, steps):
    _chain_check(spec_fn(), seed, steps)


def test_delta_chain_bit_equal_spec_large():
    # 256-tile tier: the motivating scale for the delta path.
    _chain_check(spec_large(), 2, 6)


def test_delta_disconnect_then_reconnect():
    """Removing a bridge floods INF into the tables; re-adding it must
    restore them. max_dirty_frac=1.0 forces the delta path through both
    halves instead of falling back."""
    spec = spec_tiny()
    n = spec.n_tiles
    # A sparse planar layer: a single chain 0-1, 1-2, 2-3 on layer 0 (the
    # vertical TSVs connect the two layers, so 0-1 is a bridge for pairs
    # split across {0} x {1,2,3} columns of each layer).
    adj = np.zeros((n, n), dtype=bool)
    for a, b in [(0, 1), (1, 2), (2, 3)]:
        adj[a, b] = adj[b, a] = True
    t = routing.host_tables(design_cost_np(spec, adj))
    assert np.all(t.dist < routing.INF / 2)  # connected to start

    # Move 1: remove (0,1), add (0,2). Mid-move — after the removal phase,
    # before the addition — slot 0 (plus its TSV partner) is cut off from
    # the rest: INF floods those entries, then the added edge pulls them
    # back to finite values.
    t1 = routing.delta_link_move(t, (0, 1), (0, 2),
                                 float(np.float32(spec.router_stages)
                                       + np.float32(spec.link_delay[0, 2])),
                                 max_dirty_frac=1.0)
    adj1 = adj.copy()
    adj1[0, 1] = adj1[1, 0] = False
    adj1[0, 2] = adj1[2, 0] = True
    ref1 = routing.host_tables(design_cost_np(spec, adj1))
    assert t1 is not None
    for f in ref1._fields:
        assert np.array_equal(getattr(t1, f), getattr(ref1, f)), f

    # Move 2: the inverse — the chain must land back on the original
    # tables bit-for-bit (same graph => same shadow metric => same floor).
    w01 = float(np.float32(spec.router_stages)
                + np.float32(spec.link_delay[0, 1]))
    t2 = routing.delta_link_move(t1, (0, 2), (0, 1), w01, max_dirty_frac=1.0)
    assert t2 is not None
    for f in t._fields:
        assert np.array_equal(getattr(t2, f), getattr(t, f)), f


def test_delta_fallback_contract():
    """max_dirty_frac=0.0 rejects any move that dirties an entry — the
    caller must get None, never silently-wrong tables."""
    spec = spec_tiny()
    n_delta = _chain_check(spec, 3, 10, delta_kw={"max_dirty_frac": 0.0},
                           require_delta=False)
    assert n_delta == 0


# ------------------------------------------------------- blocked kernels
def test_min_plus_blocked_bit_equal_broadcast():
    rng = np.random.default_rng(5)
    for n, bk in [(7, 2), (37, 8), (64, 64), (33, 128)]:
        a = rng.integers(0, 30, size=(n, n)).astype(np.float32)
        a[rng.random((n, n)) < 0.3] = routing.INF
        b = rng.integers(0, 30, size=(n, n)).astype(np.float32)
        ref = np.asarray(routing.min_plus(a, b))
        got = np.asarray(routing.min_plus_blocked(a, b, block_k=bk))
        assert np.array_equal(got, ref), (n, bk)


def test_blocked_device_path_matches_host_above_dense_nmax():
    """N=300 > DENSE_NMAX: apsp/next_hop dispatch to the k-/j-blocked scan
    paths; they must be bit-equal to the independent host mirrors."""
    n = 300
    rng = np.random.default_rng(6)
    cost = np.full((n, n), routing.INF, dtype=np.float32)
    np.fill_diagonal(cost, 0.0)
    ring = np.arange(n)
    w_ring = rng.integers(1, 30, size=n).astype(np.float32)
    cost[ring, (ring + 1) % n] = w_ring
    cost[(ring + 1) % n, ring] = w_ring
    ii = rng.integers(0, n, size=400)
    jj = rng.integers(0, n, size=400)
    keep = ii != jj
    w = rng.integers(1, 30, size=400).astype(np.float32)
    cost[ii[keep], jj[keep]] = w[keep]
    cost[jj[keep], ii[keep]] = w[keep]
    it = _iters(n)
    t = routing.host_tables(cost)
    dist = np.asarray(routing.apsp(cost, it))
    assert np.array_equal(dist, t.dist)
    nh = np.asarray(routing.next_hop(cost, dist))
    assert np.array_equal(nh, t.nh)


def test_pow2_block_bounds():
    for n in [8, 64, 256, 1024, 4096]:
        b = routing._pow2_block(n)
        assert b & (b - 1) == 0 and 4 <= b <= 128
        assert 4 * n * n * b <= routing._BLOCK_BUDGET_BYTES or b == 4


@pytest.mark.slow
def test_1024_tile_blocked_apsp_memory_safe():
    """The 1024-tile stretch tier: blocked APSP must run without an
    (N, N, N) intermediate (4 GiB at f32) and agree with the host path
    on sampled rows."""
    from repro.core.problem import spec_1024

    spec = spec_1024()
    cost = design_cost_np(spec, spec.mesh_design().adj)
    it = _iters(spec.n_tiles)
    dist = np.asarray(routing.apsp(cost, it))
    t = routing.host_tables(cost)
    assert np.array_equal(dist, t.dist)


# ------------------------------------------------------- Evaluator wiring
def test_batch_moves_delta_bit_equal_dense():
    spec = spec_16()
    f = avg_traffic(spec, list(APP_NAMES))
    ev_on = Evaluator(spec, f, delta="on")
    ev_off = Evaluator(spec, f, delta="off")
    rng = np.random.default_rng(7)
    d = spec.mesh_design()
    for step in range(4):
        mv = sample_neighbor_moves(spec, d, rng, 5, 5)
        o_on = ev_on.batch_moves(mv)
        o_off = ev_off.batch_moves(mv)
        assert np.array_equal(o_on, o_off), step
        j = int(np.argmin(o_on[:, 2]))
        d = mv.materialize(j)
        ev_on.note_accept(mv, j)
    assert ev_on.delta_stats["delta"] + ev_on.delta_stats["fallback"] > 0
    assert ev_on.delta_stats["swap"] > 0


def test_local_search_trajectory_invariant_to_delta():
    spec = spec_tiny()
    f = avg_traffic(spec, list(APP_NAMES))

    def run(mode):
        ev = Evaluator(spec, f, delta=mode)
        ctx = PhvContext(ev(spec.mesh_design()), CASES["case3"])
        return local_search(spec, ev, ctx, spec.mesh_design(),
                            np.random.default_rng(11),
                            n_swaps=4, n_link_moves=4, max_steps=5)

    r_off, r_on = run("off"), run("on")
    assert np.array_equal(np.asarray(r_off.traj_objs),
                          np.asarray(r_on.traj_objs))
    assert r_off.n_steps == r_on.n_steps


def test_delta_auto_threshold_and_knob_validation():
    spec = spec_16()
    f = avg_traffic(spec, list(APP_NAMES))
    assert not Evaluator(spec, f).delta_on        # 16 < DELTA_AUTO_MIN_TILES
    assert Evaluator(spec, f, delta="on").delta_on
    with pytest.raises(ValueError):
        Evaluator(spec, f, delta="sometimes")
    sl = spec_large()
    ev = Evaluator(sl, avg_traffic(sl, list(APP_NAMES)))
    assert ev.delta_on                            # 256-tile tier: auto-on
    assert ev.max_batch >= 1                      # N-aware batch shrink


def test_spec_large_smoke():
    """The 256-tile tier is a well-formed problem instance."""
    sl = spec_large()
    assert sl.n_tiles == 256
    d = sl.mesh_design()
    # Planar link budget: the mesh seed respects the spec's own budget.
    assert d.adj.sum() // 2 <= sl.n_links
    ev = Evaluator(sl, avg_traffic(sl, list(APP_NAMES)))
    objs = ev(d)
    assert np.all(np.isfinite(objs))


# ------------------------------------------------------- property tests
def _given_chains(max_examples):
    def deco(fn):
        if st is None:
            @pytest.mark.skip(reason="hypothesis not installed")
            def stub():
                pass
            stub.__name__ = fn.__name__
            return stub
        return settings(max_examples=max_examples, deadline=None)(
            given(st.integers(0, 2 ** 31 - 1), st.integers(1, 12))(fn))
    return deco


@_given_chains(max_examples=15)
def test_delta_chain_property(seed, steps):
    _chain_check(spec_tiny(), seed, steps, require_delta=False)
