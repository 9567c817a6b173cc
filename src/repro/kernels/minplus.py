"""Batched min-plus matrix product — the APSP inner loop of design evaluation.

Distance squaring D <- D (min,+) D is the optimizer's routing hot spot
(paper-side: every candidate design needs all-pairs shortest paths). On GPU
this is typically written scatter/relaxation style (Bellman-Ford); the
TPU-native formulation is a *blocked dense* min-plus matmul: VMEM tiles of
A-rows and B-columns, with the k-dimension as the innermost sequential grid
axis accumulating ``minimum`` into the output block (the same revisiting
pattern as an MXU matmul k-loop, but on the VPU — min of sums has no MXU
lowering).

Block shapes follow the TPU tiling rule: the last two dims of every block
are multiples of (8, 128) or the whole (padded) array dim. So up to 128
tiles one block spans the matrix (N <= 128: bm = bn = bk = N), and above
it the blocks are 128 x 128 with a 128-wide k step. The (bm, bk, bn)
broadcast intermediate is at most 128^3 x 4 B = 8 MiB of VMEM. The grid is
k-blocked at every tier (DESIGN.md §13), so no (N, N, N) intermediate ever
materializes.

This module is the ``backend="pallas"`` implementation behind
core.routing.apsp_batched / routing_tables_batched at every tile count;
core.evaluate.Evaluator threads its batched candidate APSP through that
switch (``"auto"`` selects this kernel on TPU, the jnp oracle elsewhere;
``interpret=True`` runs it on CPU for tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF = 1.0e9
#: block edge above 128 tiles; a multiple of 128, the lane width
_BLOCK = 128


def _minplus_kernel(a_ref, b_ref, o_ref):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, INF)

    a = a_ref[0]  # (bm, bk)
    b = b_ref[0]  # (bk, bn)
    cand = jnp.min(a[:, :, None] + b[None, :, :], axis=1)
    o_ref[0] = jnp.minimum(o_ref[0], cand)


@functools.partial(jax.jit, static_argnames=("interpret",))
def minplus(
    a: jax.Array,  # (B, N, N)
    b: jax.Array,  # (B, N, N)
    *,
    interpret: bool = False,
) -> jax.Array:
    """out[b, i, j] = min_k a[b, i, k] + b[b, k, j]. Pads N with +INF rows
    (neutral for min-plus) up to a multiple of ``_BLOCK`` when N > ``_BLOCK``."""
    bsz, n, _ = a.shape
    bm = bn = bk = min(_BLOCK, n)
    npad = -(-n // bk) * bk
    if npad != n:
        pad = ((0, 0), (0, npad - n), (0, npad - n))
        a = jnp.pad(a, pad, constant_values=INF)
        b = jnp.pad(b, pad, constant_values=INF)

    grid = (bsz, npad // bm, npad // bn, npad // bk)
    out = pl.pallas_call(
        _minplus_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda b_, i, j, k: (b_, i, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, bn), lambda b_, i, j, k: (b_, k, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda b_, i, j, k: (b_, i, j),
                               memory_space=pltpu.VMEM),
        # Under shard_map the output varies over the same mesh axes as
        # the operands (the batch is sharded, each device runs its shard).
        out_shape=jax.ShapeDtypeStruct(
            (bsz, npad, npad), jnp.float32,
            vma=jax.typeof(a).vma | jax.typeof(b).vma),
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32))
    return out[:, :n, :n]


def apsp(cost: jax.Array, n_iters: int, *, interpret: bool = False) -> jax.Array:
    """Batched APSP by repeated min-plus squaring of (B, N, N) costs."""
    d = cost
    for _ in range(n_iters):
        d = minplus(d, d, interpret=interpret)
    return d
