"""Fused meta-search scoring tail — normalize → forest traverse → argmax.

The fused meta-greedy step (core/fused.py) featurizes a whole padded
neighborhood on device and then needs only two scalars back: the index of
the best candidate and its surrogate value. The jnp tail materializes the
(B,) value vector in HBM and ships it to the host for the argmax; this
kernel keeps the reduction on-chip — each grid step scores one batch block
against the VMEM-resident forest (same node layout and traversal as
kernels/forest) and folds its block max into a revisited (1, 1) running
best, so the whole neighborhood round-trips exactly eight bytes.

Tie-breaking matches ``np.argmax`` (first max): within a block,
``jnp.argmax`` takes the first; across blocks, the strict ``>`` update
keeps the earlier block's winner. Rows at or beyond ``n_real`` (the
block-multiple padding added outside the jit) are masked to -inf, so a
padding row can never win. ``n_real`` rides in as a (1, 1) array rather
than a static — the real neighborhood size varies per step and must not
key the jit cache (the padded shape does).

This is the ``meta_backend="fused-pallas"`` implementation. Like
kernels/forest it uses gathers the TPU compiler refuses, so it runs only
through the Pallas interpreter (``interpret=True``, conformance tests) and
no default path selects it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: batch-block size; core.fused pads the neighborhood to a multiple of this
#: *outside* the jitted entry point (the PR-4 retrace-bounding trick).
BLOCK_B = 128


def _score_kernel(thr_ref, feat_ref, child_ref, value_ref, xm_ref, xs_ref,
                  nreal_ref, x_ref, oval_ref, oarg_ref, *, depth: int):
    """One batch block: normalize, traverse all (tree, sample) pointers
    ``depth`` levels (self-looping leaves — kernels/forest), reduce the
    block to (max value, argmax) and fold into the running best."""
    i = pl.program_id(0)
    xb = (x_ref[...] - xm_ref[...]) / xs_ref[...]   # (bb, F) f32
    thr = thr_ref[...]                              # (T, M)
    feat = feat_ref[...]
    child = child_ref[...]
    t = thr.shape[0]
    bb = xb.shape[0]
    idx = jnp.zeros((t, bb), jnp.int32)
    for _ in range(depth):
        node_thr = jnp.take_along_axis(thr, idx, axis=1)
        node_feat = jnp.take_along_axis(feat, idx, axis=1)
        xv = jnp.take_along_axis(xb, node_feat.T, axis=1).T
        go_right = (xv > node_thr).astype(jnp.int32)
        idx = jnp.take_along_axis(child, idx * 2 + go_right, axis=1)
    vals = jnp.mean(jnp.take_along_axis(value_ref[...], idx, axis=1),
                    axis=0, keepdims=True)          # (1, bb)
    ridx = jax.lax.broadcasted_iota(jnp.int32, (1, bb), 1) + i * bb
    vals = jnp.where(ridx < nreal_ref[0, 0], vals, -jnp.inf)
    blk_val = jnp.max(vals)
    blk_arg = jnp.argmax(vals[0]).astype(jnp.int32) + i * bb

    @pl.when(i == 0)
    def _():
        oval_ref[0, 0] = -jnp.inf
        oarg_ref[0, 0] = 0

    better = blk_val > oval_ref[0, 0]
    oarg_ref[0, 0] = jnp.where(better, blk_arg, oarg_ref[0, 0])
    oval_ref[0, 0] = jnp.where(better, blk_val, oval_ref[0, 0])


@functools.partial(jax.jit,
                   static_argnames=("depth", "block_b", "interpret"))
def score_block_max(
    threshold: jax.Array,  # (T, M) f32
    feature: jax.Array,    # (T, M) int32, leaf features clamped to 0
    child: jax.Array,      # (T, 2M) int32 interleaved (left, right)
    value: jax.Array,      # (T, M) f32
    xm: jax.Array,         # (1, F) f32 feature means
    xs: jax.Array,         # (1, F) f32 feature stds
    x: jax.Array,          # (B, F) f32 raw features, B a block_b multiple
    n_real: jax.Array,     # (1, 1) int32 — rows >= n_real are padding
    *,
    depth: int,
    block_b: int = BLOCK_B,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(best value, best row index) over the first ``n_real`` rows."""
    b, f = x.shape
    if b % block_b:
        raise ValueError(
            f"batch {b} must be pre-padded to a multiple of {block_b} "
            "outside the jit (core.fused.MetaScorer._encode does this)")
    t, m = threshold.shape
    grid = (b // block_b,)
    full = lambda i: (0, 0)  # constant maps: VMEM-resident across the grid
    oval, oarg = pl.pallas_call(
        functools.partial(_score_kernel, depth=depth),
        grid=grid,
        in_specs=[
            pl.BlockSpec((t, m), full, memory_space=pltpu.VMEM),
            pl.BlockSpec((t, m), full, memory_space=pltpu.VMEM),
            pl.BlockSpec((t, 2 * m), full, memory_space=pltpu.VMEM),
            pl.BlockSpec((t, m), full, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, f), full, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, f), full, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), full, memory_space=pltpu.SMEM),
            pl.BlockSpec((block_b, f), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), full, memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), full, memory_space=pltpu.SMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret,
    )(threshold.astype(jnp.float32), feature.astype(jnp.int32),
      child.astype(jnp.int32), value.astype(jnp.float32),
      xm.astype(jnp.float32), xs.astype(jnp.float32),
      jnp.asarray(n_real, jnp.int32).reshape(1, 1),
      x.astype(jnp.float32))
    return oval[0, 0], oarg[0, 0]
