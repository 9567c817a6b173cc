"""Per-kernel correctness: pallas_call(interpret=True) vs pure-jnp oracles,
swept over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.link_util import walk_accumulate
from repro.kernels.minplus import minplus
from repro.kernels.ssd import ssd


# ------------------------------------------------------------------ minplus
@pytest.mark.parametrize(
    "bsz,n",
    [(1, 8), (2, 16), (1, 36), (2, 64), (1, 70),
     # odd / prime / above-one-block sizes exercising the +INF padding
     (1, 33), (3, 37), (1, 129)],
)
def test_minplus_matches_ref(bsz, n):
    rng = np.random.default_rng(n)
    a = rng.uniform(0, 10, size=(bsz, n, n)).astype(np.float32)
    b = rng.uniform(0, 10, size=(bsz, n, n)).astype(np.float32)
    got = minplus(jnp.asarray(a), jnp.asarray(b), interpret=True)
    want = ref.minplus_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_minplus_with_inf_edges():
    from repro.kernels.minplus import INF
    rng = np.random.default_rng(0)
    a = rng.uniform(1, 5, size=(1, 12, 12)).astype(np.float32)
    a[0, rng.uniform(size=(12, 12)) < 0.5] = INF
    np.fill_diagonal(a[0], 0.0)
    got = minplus(jnp.asarray(a), jnp.asarray(a), interpret=True)
    want = ref.minplus_ref(jnp.asarray(a), jnp.asarray(a))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_routing_backend_switch_pallas_matches_jnp():
    """routing_tables_batched(backend="pallas") == the jnp oracle on an
    odd-N (36-tile) spec — the evaluator's TPU hot path, interpreted."""
    import numpy as np_
    from repro.core import random_design, spec_36
    from repro.core import routing
    from repro.core.objectives import design_cost, make_consts

    spec = spec_36()
    c = make_consts(spec)
    rng = np_.random.default_rng(2)
    adjs = jnp.asarray(np_.stack(
        [spec.mesh_design().adj, random_design(spec, rng).adj]))
    costs = jax.vmap(lambda a: design_cost(c, a))(adjs)
    dist_j, nh_j = routing.routing_tables_batched(
        costs, c.apsp_iters, backend="jnp")
    dist_p, nh_p = routing.routing_tables_batched(
        costs, c.apsp_iters, backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(dist_p), np.asarray(dist_j),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(nh_p), np.asarray(nh_j))


def test_evaluator_backend_switch_matches_jnp():
    """Evaluator(backend="pallas", interpret=True) reproduces the jnp
    objective rows end-to-end (validity masking included)."""
    from repro.core import Evaluator, random_design, spec_tiny, traffic_matrix

    spec = spec_tiny()
    f = traffic_matrix(spec, "BFS")
    rng = np.random.default_rng(1)
    designs = [spec.mesh_design()] + [random_design(spec, rng)
                                      for _ in range(3)]
    objs_j = Evaluator(spec, f, backend="jnp").batch(designs)
    objs_p = Evaluator(spec, f, backend="pallas", interpret=True).batch(designs)
    np.testing.assert_allclose(objs_p, objs_j, rtol=1e-5, atol=1e-6)


@pytest.mark.interpret
@pytest.mark.parametrize("n", [7, 33, 129])
def test_minplus_apsp_interpret_matches_jnp_oracle(n):
    """Full APSP (repeated blocked min-plus squaring) through the Pallas
    interpreter on CPU vs the vmapped jnp oracle, at odd / padded N — the
    evaluator's whole pallas routing path runs in tier-1, not just on TPU."""
    from repro.core import routing

    rng = np.random.default_rng(n)
    cost = rng.uniform(1, 5, size=(2, n, n)).astype(np.float32)
    cost[rng.uniform(size=cost.shape) < 0.6] = routing.INF  # sparse graphs
    for b in range(cost.shape[0]):
        np.fill_diagonal(cost[b], 0.0)
    n_iters = routing.apsp_iters(n)
    want = routing.apsp_batched(jnp.asarray(cost), n_iters, backend="jnp")
    got = routing.apsp_batched(jnp.asarray(cost), n_iters, backend="pallas",
                               interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_minplus_apsp_converges_to_routing_apsp():
    from repro.core import spec_tiny, traffic_matrix
    from repro.core import routing
    from repro.core.objectives import make_consts
    from repro.kernels.ops import apsp as ops_apsp

    spec = spec_tiny()
    c = make_consts(spec)
    d = spec.mesh_design()
    full = jnp.asarray(d.adj) | c.vadj
    n = spec.n_tiles
    cost = jnp.where(full, c.router_stages + c.link_delay, routing.INF)
    cost = jnp.where(jnp.eye(n, dtype=bool), 0.0, cost)
    want = routing.apsp(cost, c.apsp_iters)
    got = ops_apsp(cost[None], c.apsp_iters, interpret=True)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


# ------------------------------------------------------------- link-util
@pytest.mark.parametrize("spec_name", ["tiny", "s16"])
def test_walk_accumulate_matches_ref(spec_name):
    from repro.core import spec_16, spec_tiny, traffic_matrix
    from repro.core import routing
    from repro.core.objectives import make_consts

    spec = {"tiny": spec_tiny, "s16": spec_16}[spec_name]()
    c = make_consts(spec)
    d = spec.mesh_design()
    full = jnp.asarray(d.adj) | c.vadj
    n = spec.n_tiles
    cost = jnp.where(full, c.router_stages + c.link_delay, routing.INF)
    cost = jnp.where(jnp.eye(n, dtype=bool), 0.0, cost)
    dist, nh = routing.routing_tables(cost, c.apsp_iters)
    f = traffic_matrix(spec, "BFS")
    fs = jnp.asarray(f[d.perm][:, d.perm] * (1 - np.eye(n)), jnp.float32)

    hops_k, dsum_k, util_k, visits_k = walk_accumulate(
        nh, fs, c.link_delay, max_hops=c.max_hops, interpret=True
    )
    hops_r, dsum_r, util_r, visits_r = ref.walk_accumulate_ref(
        nh, fs, c.link_delay, max_hops=c.max_hops
    )
    np.testing.assert_allclose(np.asarray(hops_k), np.asarray(hops_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(dsum_k), np.asarray(dsum_r), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(util_k), np.asarray(util_r), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(visits_k), np.asarray(visits_r), rtol=1e-4, atol=1e-5)

    # third corner of the conformance triangle: the scalar-loop numpy
    # oracle must agree with both the jnp scatter-add port and the kernel
    # (mirrors the minplus numpy-jnp-pallas triangle).
    hops_n, dsum_n, util_n, visits_n = ref.walk_accumulate_np(
        nh, fs, c.link_delay, max_hops=c.max_hops
    )
    np.testing.assert_allclose(hops_n, np.asarray(hops_r), atol=1e-5)
    np.testing.assert_allclose(dsum_n, np.asarray(dsum_r), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(util_n, np.asarray(util_r), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(visits_n, np.asarray(visits_r), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(util_n, np.asarray(util_k), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------- attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,kh,s,dh,causal,window",
    [
        (1, 4, 4, 128, 32, True, None),     # MHA causal
        (2, 4, 2, 128, 16, True, None),     # GQA
        (1, 8, 1, 256, 32, True, None),     # MQA, multi k-block
        (1, 4, 4, 128, 32, False, None),    # bidirectional (encoder)
        (1, 4, 2, 256, 32, True, 64),       # sliding window
    ],
)
def test_flash_attention_matches_ref(b, h, kh, s, dh, causal, window, dtype):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (b, h, s, dh), dtype)
    k = jax.random.normal(keys[1], (b, kh, s, dh), dtype)
    v = jax.random.normal(keys[2], (b, kh, s, dh), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


# ---------------------------------------------------------------------- ssd
@pytest.mark.parametrize(
    "b,s,h,p,n,chunk",
    [(1, 64, 2, 16, 8, 16), (2, 128, 4, 32, 16, 64), (1, 128, 1, 8, 4, 32)],
)
def test_ssd_kernel_matches_sequential_ref(b, s, h, p, n, chunk):
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(keys[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, h))) * 0.1
    a = -jnp.exp(jax.random.normal(keys[2], (h,)) * 0.3)
    bm = jax.random.normal(keys[3], (b, s, n), jnp.float32) * 0.5
    cm = jax.random.normal(keys[4], (b, s, n), jnp.float32) * 0.5
    d = jnp.ones((h,)) * 0.5
    got = ssd(x, dt, a, bm, cm, d, chunk=chunk, interpret=True)
    want = ref.ssd_ref(x, dt, a, bm, cm, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ssd_chunked_ref_matches_sequential():
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    b, s, h, p, n = 2, 128, 2, 16, 8
    x = jax.random.normal(keys[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, h))) * 0.1
    a = -jnp.exp(jax.random.normal(keys[2], (h,)) * 0.3)
    bm = jax.random.normal(keys[3], (b, s, n)) * 0.5
    cm = jax.random.normal(keys[4], (b, s, n)) * 0.5
    d = jnp.full((h,), 0.25)
    got = ref.ssd_chunked_ref(x, dt, a, bm, cm, d, chunk=32)
    want = ref.ssd_ref(x, dt, a, bm, cm, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ssd_gradients_flow_through_chunked_ref():
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    b, s, h, p, n = 1, 64, 2, 8, 4
    x = jax.random.normal(keys[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, h))) * 0.1
    a = -jnp.exp(jax.random.normal(keys[2], (h,)) * 0.3)
    bm = jax.random.normal(keys[3], (b, s, n)) * 0.5
    cm = jax.random.normal(keys[4], (b, s, n)) * 0.5
    d = jnp.full((h,), 0.25)

    def loss(x_):
        return jnp.sum(ref.ssd_chunked_ref(x_, dt, a, bm, cm, d, chunk=16) ** 2)

    g = jax.grad(loss)(x)
    assert np.all(np.isfinite(np.asarray(g)))
    # Check against the sequential formulation's gradient.
    def loss_seq(x_):
        return jnp.sum(ref.ssd_ref(x_, dt, a, bm, cm, d) ** 2)
    g2 = jax.grad(loss_seq)(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g2), rtol=1e-3, atol=1e-3)
