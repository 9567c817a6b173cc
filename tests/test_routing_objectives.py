"""Routing (APSP/next-hop/walk) vs networkx oracle + objective sanity."""

import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import networkx as nx
import numpy as np
import pytest

from repro import telemetry
from repro.core import (Evaluator, random_design, sample_neighbors, spec_16,
                        spec_64, spec_tiny, traffic_matrix)
from repro.core import routing
from repro.core.objectives import (design_cost_np, make_consts,
                                   peak_temperature_celsius)
from repro.kernels.ref import walk_accumulate_np

#: objective rows and ``net_lat`` of the ``WALK_CASES`` batches, recorded
#: from the evaluator when its walk ran a fixed ``max_hops`` steps
GOLDEN = Path(__file__).parent / "data" / "walk_golden.npz"


def _cost_matrix(spec, d):
    c = make_consts(spec)
    full = jnp.asarray(d.adj) | c.vadj
    n = spec.n_tiles
    cost = jnp.where(full, c.router_stages + c.link_delay, routing.INF)
    return jnp.where(jnp.eye(n, dtype=bool), 0.0, cost), c


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_apsp_matches_networkx(seed):
    spec = spec_16()
    rng = np.random.default_rng(seed)
    d = random_design(spec, rng)
    cost, c = _cost_matrix(spec, d)
    dist = np.asarray(routing.apsp(cost, c.apsp_iters))

    g = nx.Graph()
    cost_np = np.asarray(cost)
    n = spec.n_tiles
    for a in range(n):
        for b in range(a + 1, n):
            if cost_np[a, b] < routing.INF / 2:
                g.add_edge(a, b, weight=float(cost_np[a, b]))
    if not nx.is_connected(g):
        pytest.skip("random design disconnected; covered by validity test")
    ref = dict(nx.all_pairs_dijkstra_path_length(g))
    for a in range(n):
        for b in range(n):
            assert dist[a, b] == pytest.approx(ref[a][b], rel=1e-5)


def test_walk_consistent_with_dist():
    """Along walked paths, total cost r*h + delay must equal the APSP dist."""
    spec = spec_16()
    d = spec.mesh_design()
    cost, c = _cost_matrix(spec, d)
    dist, nh = routing.routing_tables(cost, c.apsp_iters)
    f = jnp.ones((spec.n_tiles, spec.n_tiles), jnp.float32)
    hops, delay, util, visits, all_done, _ = routing.walk_paths(
        nh, c.link_delay, f, c.max_hops
    )
    assert bool(all_done)
    total = spec.router_stages * np.asarray(hops) + np.asarray(delay)
    np.testing.assert_allclose(total, np.asarray(dist), rtol=1e-5)


def test_walk_utilization_conservation():
    """Total f-weighted link traversals == sum over pairs f_ij * hops_ij."""
    spec = spec_tiny()
    d = spec.mesh_design()
    cost, c = _cost_matrix(spec, d)
    dist, nh = routing.routing_tables(cost, c.apsp_iters)
    rng = np.random.default_rng(0)
    f = jnp.asarray(rng.uniform(size=(8, 8)) * (1 - np.eye(8)), jnp.float32)
    hops, delay, util, visits, all_done, _ = routing.walk_paths(
        nh, c.link_delay, f, c.max_hops
    )
    assert float(jnp.sum(util)) == pytest.approx(
        float(jnp.sum(f * hops)), rel=1e-5
    )
    # Router visits = link traversals + one destination visit per unit f.
    assert float(jnp.sum(visits)) == pytest.approx(
        float(jnp.sum(f * hops) + jnp.sum(f)), rel=1e-5
    )


def _walk_batch(spec, seed, n_random):
    """``n_random`` random designs, then 2 swaps and 2 link moves of the
    first."""
    rng = np.random.default_rng(seed)
    ds = [random_design(spec, rng) for _ in range(n_random)]
    return ds + sample_neighbors(spec, ds[0], rng, 2, 2)


def _loop_one_pair(nh):
    """Next hops in which one pair bounces between two routers for ever."""
    nh = nh.copy()
    i, j = 0, int(np.argmax(nh[0] != np.arange(nh.shape[0])))
    a = int(nh[i, j])
    nh[a, j] = i
    return nh


# name: (spec, seed, random designs, row whose walk never arrives)
WALK_CASES = {"spec_16": (spec_16, 16, 3, None),
              "spec_64": (spec_64, 64, 2, None),
              "spec_16_unreachable": (spec_16, 16, 3, 3)}


@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_early_exit_walk(case):
    """The walk stops when the batch's last pair arrives, and gives the
    rows the fixed ``max_hops`` walk gave, bit for bit; the batches pad to
    a power of two with copies of their last design. A pair that never
    arrives keeps the loop running to the cap, ``all_done`` false and its
    design's row +INF, and leaves the other rows as they were."""
    spec_fn, seed, n_random, stuck = WALK_CASES[case]
    spec = spec_fn()
    c = make_consts(spec)
    f = traffic_matrix(spec, "BP")
    ds = _walk_batch(spec, seed, n_random)
    tabs = [routing.host_tables(design_cost_np(spec, d.adj), c.apsp_iters)
            for d in ds]
    nhs = [t.nh for t in tabs]
    if stuck is not None:
        nhs[stuck] = _loop_one_pair(nhs[stuck])
    golden = np.load(GOLDEN)
    gold = golden[case.removesuffix("_unreachable") + ".objs"]
    ok = np.arange(len(ds)) != stuck

    ev = Evaluator(spec, f)
    if stuck is None:
        objs, aux = ev.batch_aux(ds)
        np.testing.assert_array_equal(objs, gold)
        np.testing.assert_array_equal(
            aux["net_lat"], golden[case + ".net_lat"])
    t0 = time.perf_counter_ns()
    rows = ev._eval_from_tables([d.perm for d in ds], [d.adj for d in ds],
                                [t.dist for t in tabs], nhs)
    np.testing.assert_array_equal(rows[ok], gold[ok])
    assert np.all(rows[~ok] == routing.INF)

    # the walk itself, on the padded batch, against the numpy oracle that
    # walks every pair to its destination or to the cap
    pad = 1 << (len(ds) - 1).bit_length()
    perms = [d.perm for d in ds] + [ds[-1].perm] * (pad - len(ds))
    nh_b = np.stack(nhs + [nhs[-1]] * (pad - len(ds)))
    f_b = np.stack([f[p][:, p] * (1 - np.eye(spec.n_tiles)) for p in perms]
                   ).astype(np.float32)
    hops, delay, util, visits, done, steps = jax.vmap(
        partial(routing.walk_paths, max_hops=c.max_hops),
        in_axes=(0, None, 0))(jnp.asarray(nh_b), c.link_delay,
                              jnp.asarray(f_b))
    longest = []
    for k in range(pad):
        h, dl, u, v = walk_accumulate_np(nh_b[k], f_b[k], c.link_delay,
                                         max_hops=c.max_hops)
        np.testing.assert_array_equal(np.asarray(hops[k]), h)
        np.testing.assert_allclose(np.asarray(delay[k]), dl, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(util[k]), u, rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(visits[k]), v, rtol=1e-5)
        assert bool(done[k]) == (k != stuck)
        assert int(steps[k]) == int(h.max())
        longest.append(int(h.max()))
    # the batched loop ran as long as the batch's longest path: the cap
    # where a pair never arrives, far below it where all do
    (disp,) = [s for s in telemetry.spans()
               if s.name == "eval.dispatch" and s.t0_ns >= t0]
    assert disp.attrs["walk_steps"] == max(longest)
    assert disp.attrs["walk_cap"] == c.max_hops
    if stuck is None:
        assert max(longest) < c.max_hops // 2
    else:
        assert max(longest) == c.max_hops


def test_mesh_objectives_valid_and_positive():
    for spec in (spec_tiny(), spec_16(), spec_64()):
        f = traffic_matrix(spec, "BP")
        ev = Evaluator(spec, f)
        objs = ev(spec.mesh_design())
        assert np.all(np.isfinite(objs)) and np.all(objs > 0)


def test_batch_matches_single():
    spec = spec_tiny()
    f = traffic_matrix(spec, "HS")
    ev = Evaluator(spec, f)
    rng = np.random.default_rng(3)
    ds = [spec.mesh_design()] + [random_design(spec, rng) for _ in range(5)]
    batch = ev.batch(ds)
    for d, row in zip(ds, batch):
        np.testing.assert_allclose(ev(d), row, rtol=1e-6)


def test_disconnected_design_marked_invalid():
    spec = spec_tiny()
    d = spec.mesh_design()
    # Remove every planar link touching slot 0 and give them elsewhere; slot 0
    # keeps only its vertical link; then drop links touching slot 4 (its
    # vertical partner) too -> stack {0,4} isolated.
    adj = np.zeros_like(d.adj)
    # Connect only slots {1,2,3} and {5,6,7} planar rings, budget-filling.
    pairs = [(1, 2), (2, 3), (1, 3), (5, 6), (6, 7), (5, 7), (1, 2), (5, 6)]
    cnt = 0
    for a, b in pairs:
        if not adj[a, b] and cnt < spec.n_planar_links:
            adj[a, b] = adj[b, a] = True
            cnt += 1
    d.adj = adj
    f = traffic_matrix(spec, "BP")
    ev = Evaluator(spec, f)
    objs = ev(d)
    assert not np.all(np.isfinite(objs)) or np.all(objs >= 1e8)


def test_thermal_prefers_power_near_sink():
    """Eq. 5: within one vertical stack, hot cores near the sink give a lower
    peak temperature than hot cores far from it (the paper's §6.5 Het-therm
    observation: GPUs move toward the sink)."""
    from repro.core.problem import SystemSpec
    spec = SystemSpec(nx=1, ny=1, n_layers=4, n_cpu=1, n_llc=2, n_gpu=1)
    c = make_consts(spec)
    # core powers: CPU(id 0)=2.0, LLC(ids 1,2)=0.8, GPU(id 3)=3.0.
    hot_at_sink = np.array([3, 0, 1, 2], dtype=np.int32)
    hot_on_top = np.array([1, 2, 0, 3], dtype=np.int32)
    assert peak_temperature_celsius(c, hot_at_sink) < peak_temperature_celsius(
        c, hot_on_top
    )


def test_energy_increases_with_longer_links():
    """Replacing a short link by a long link (same endpoints' layer) must not
    decrease link energy contribution for the same routes."""
    spec = spec_16()
    f = traffic_matrix(spec, "GAU")
    ev = Evaluator(spec, f)
    mesh = spec.mesh_design()
    o = ev(mesh)
    assert o[3] > 0
