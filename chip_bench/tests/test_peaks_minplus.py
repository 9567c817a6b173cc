"""The peaks table and the min-plus kernel's operation and byte counts."""

import json

import jax
import jax.numpy as jnp
import pytest

import minplus_cost
import trace_reduce


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        trace_reduce.load_peaks("TPU v99 imaginary")


def test_known_device_kind_has_sourced_peaks():
    table = json.load(open(trace_reduce.BENCH_DIR / "peaks.json"))["devices"]
    for kind, peaks in table.items():
        assert peaks == trace_reduce.load_peaks(kind)
        assert peaks["hbm_bytes_per_s"] > 0 and peaks["hbm_source"]
        assert peaks["vpu_f32_addmin_ops_per_s"] > 0 and peaks["vpu_source"]


def _kernel_grid(batch, n):
    """Grid and block shapes of the min-plus pallas_call, read from its
    jaxpr (interpret mode traces the same grid mapping)."""
    from repro.kernels import minplus

    a = jnp.zeros((batch, n, n), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda x: minplus.minplus(x, x, interpret=True))(a)
    eqns = [e for e in jaxpr.jaxpr.eqns]
    while not any(e.primitive.name == "pallas_call" for e in eqns):
        inner = [e for e in eqns if "jaxpr" in e.params]
        eqns = [x for e in inner for x in e.params["jaxpr"].eqns]
    call = next(e for e in eqns if e.primitive.name == "pallas_call")
    gm = call.params["grid_mapping"]
    blocks = [tuple(getattr(d, "block_size", d) for d in bm.block_shape)
              for bm in gm.block_mappings]
    return tuple(gm.grid), blocks


@pytest.mark.parametrize("batch,n", [(1, 64), (8, 64), (1, 256), (8, 256)])
def test_counts_follow_kernel_blocks(batch, n):
    grid, blocks = _kernel_grid(batch, n)
    assert grid == minplus_cost.grid(batch, n)
    bk, npad = minplus_cost.blocks(n)
    assert blocks == [(1, bk, bk)] * 3            # A, B, out
    ops, nbytes = minplus_cost.call_cost(batch, n)
    steps = grid[0] * grid[1] * grid[2] * grid[3]
    assert ops == 2 * batch * npad ** 3
    block_bytes = 4 * bk * bk
    assert nbytes == steps * 2 * block_bytes + \
        grid[0] * grid[1] * grid[2] * block_bytes


def test_counts_by_hand():
    # N=64: one 64^3 block per design; N=256: 2x2x2 blocks of 128.
    assert minplus_cost.call_cost(1, 64) == (2 * 64 ** 3, 4 * 3 * 64 * 64)
    assert minplus_cost.call_cost(1, 256) == (
        2 * 256 ** 3, 4 * (8 * 2 * 128 * 128 + 4 * 128 * 128))


def test_least_time_picks_the_larger_bound():
    peaks = {"vpu_f32_addmin_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    assert minplus_cost.least_time(2e12, 1e10, peaks) == (2.0, "vpu")
    assert minplus_cost.least_time(1e9, 1e12, peaks) == (10.0, "hbm")
