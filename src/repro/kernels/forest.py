"""Blocked forest-traversal — the MOO-STAGE surrogate's inference hot loop.

The bagged-CART surrogate is queried for whole sampled neighborhoods every
meta-search step (paper §5.2 / Alg. 2 line 9), so forest inference is the
inner loop of every optimizer run. The flat struct-of-arrays forest
(core/forest.py) packs per-tree ``threshold`` / ``feature`` / ``child`` /
``value`` arrays into padded (T, M) tensors with self-looping leaves; a
predict is then ``depth`` rounds of three gathers per (tree, sample) pair.

The TPU-native formulation here mirrors kernels/minplus: the grid runs over
*batch blocks* only, while the node tensors use constant index maps, so
they are resident in VMEM across every grid step and the per-level gathers
for all T trees fuse into one kernel body (no per-level HBM round trips —
the jnp twin re-gathers from device memory each level). ``depth`` is static
and the level loop fully unrolls.

VMEM budget: node tensors are (T, M) f32/int32 x 5 (threshold, feature,
2M-wide child, value) — a 24-tree depth-9 forest is ~0.5 MiB — plus one
(block_b, F) x-block and a (T, block_b) pointer block: far under the
~16 MiB/core limit for every forest the repo trains.

This module is the ``backend="pallas"`` implementation behind
core.forest.RegressionForest.predict, run through the Pallas interpreter
(``interpret=True``; conformance tests). The TPU compiler refuses its
gathers: Mosaic lowers ``take_along_axis`` only where the index shape
equals the operand shape, and here a (T, M) table is indexed by (T, bb)
pointers. So no default path selects it (``resolve_forest_backend``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: default batch-block size; callers that want to bound jit retraces pad
#: their batch to a BLOCK_B multiple *outside* the jitted entry point.
BLOCK_B = 128


def _forest_kernel(thr_ref, feat_ref, child_ref, value_ref, x_ref, o_ref,
                   *, depth: int):
    """One batch block: advance all (tree, sample) node pointers ``depth``
    levels. Leaves self-loop (and their features are clamped to 0), so no
    leaf masking is needed and every pointer advances the same number of
    steps — the same trick as the numpy/jnp twins."""
    thr = thr_ref[...]        # (T, M) f32
    feat = feat_ref[...]      # (T, M) int32, leaf-safe (>= 0)
    child = child_ref[...]    # (T, 2M) int32: [2i] = left, [2i+1] = right
    xb = x_ref[...]           # (block_b, F) f32
    t = thr.shape[0]
    bb = xb.shape[0]
    idx = jnp.zeros((t, bb), jnp.int32)  # all pairs start at the root
    for _ in range(depth):
        node_thr = jnp.take_along_axis(thr, idx, axis=1)     # (T, bb)
        node_feat = jnp.take_along_axis(feat, idx, axis=1)   # (T, bb)
        # x gather: xv[t, b] = xb[b, node_feat[t, b]]
        xv = jnp.take_along_axis(xb, node_feat.T, axis=1).T  # (T, bb)
        go_right = (xv > node_thr).astype(jnp.int32)
        idx = jnp.take_along_axis(child, idx * 2 + go_right, axis=1)
    vals = jnp.take_along_axis(value_ref[...], idx, axis=1)  # (T, bb)
    o_ref[0, :] = jnp.mean(vals, axis=0)


@functools.partial(jax.jit,
                   static_argnames=("depth", "block_b", "interpret"))
def forest_predict(
    threshold: jax.Array,  # (T, M) f32
    feature: jax.Array,    # (T, M) int32, leaf features clamped to 0
    child: jax.Array,      # (T, 2M) int32 interleaved (left, right) pairs
    value: jax.Array,      # (T, M) f32
    x: jax.Array,          # (B, F) f32, already normalized
    *,
    depth: int,
    block_b: int = BLOCK_B,
    interpret: bool = False,
) -> jax.Array:
    """(B,) forest mean over T trees. Pads B up to a ``block_b`` multiple
    (padded rows traverse garbage and are sliced off); child pointers are
    per-tree-local, so padded node tails (self-looping, feature -1 -> 0 in
    ``feature``) are never reached from a real root."""
    b, _ = x.shape
    t, m = threshold.shape
    bp = (b + block_b - 1) // block_b * block_b
    if bp != b:
        x = jnp.pad(x, ((0, bp - b), (0, 0)))

    grid = (bp // block_b,)
    full = lambda i: (0, 0)  # node tensors: one block, VMEM-resident
    out = pl.pallas_call(
        functools.partial(_forest_kernel, depth=depth),
        grid=grid,
        in_specs=[
            pl.BlockSpec((t, m), full, memory_space=pltpu.VMEM),
            pl.BlockSpec((t, m), full, memory_space=pltpu.VMEM),
            pl.BlockSpec((t, 2 * m), full, memory_space=pltpu.VMEM),
            pl.BlockSpec((t, m), full, memory_space=pltpu.VMEM),
            pl.BlockSpec((block_b, x.shape[1]), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_b), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, bp), jnp.float32),
        interpret=interpret,
    )(threshold.astype(jnp.float32), feature.astype(jnp.int32),
      child.astype(jnp.int32), value.astype(jnp.float32),
      x.astype(jnp.float32))
    return out[0, :b]
