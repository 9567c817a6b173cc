"""Path-walk accumulation (Eq. 2 / Eq. 1 / Eq. 8 terms) as one-hot MXU matmuls.

The scatter-add formulation (core/routing.walk_paths) is the natural GPU
port; TPUs have no fast scatter, so this kernel re-expresses the walk as
dense one-hot linear algebra — the DESIGN.md §4 hardware adaptation:

  for each destination d (grid axis), with the cursor kept transposed
  (positions x sources) so every operand is a full (N, N) tile or a
  (1, N) row and no matmul needs a transposed left operand:
    CT_0 = I                      (N positions x N sources, one-hot)
    MT[u, v] = [nh[v, d] == u]    (transposed next-hop transition, one-hot)
    per hop t:
      CT_{t+1} = MT @ CT_t                              (MXU)
      util    += (CT_t * w_t) @ CT_{t+1}^T              (MXU; w_t = f masked by done)
      delay_d += colsum((delay^T @ CT_t) * CT_{t+1})    (MXU + VPU)
      hops_d  += 1 - done,   visits += rowsum(CT_t * w_t)   (VPU)

``nh`` must be self-absorbing at the destination (nh[d, d] = d), which
core/routing.next_hop guarantees — finished pairs then accumulate zero
because w_t is masked by done = CT_t[d, :].

Per-destination inputs and outputs are rows of (N, 1, N) views (column d
of nh / f, transposed), so every block's last two dims are either whole
array dims or (1, N) — the TPU tiling rule. All per-destination working
state (CT, MT: N x N f32) lives in VMEM; with N = 64 that is 16 KiB per
buffer. The destination axis is the (sequential) grid; util/visits blocks
are revisited and accumulated across it. Matmuls that carry traffic
weights run at f32 precision (HIGHEST), the one-hot ones are exact either
way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _walk_kernel(nh_ref, f_ref, delay_t_ref, util_ref, hops_ref,
                 dsum_ref, visits_ref, *, max_hops: int, n: int):
    d = pl.program_id(0)

    @pl.when(d == 0)
    def _init():
        util_ref[...] = jnp.zeros_like(util_ref)
        visits_ref[...] = jnp.zeros_like(visits_ref)

    nh_row = nh_ref[0]                 # (1, N) int32: nh[:, d]
    f_row = f_ref[0]                   # (1, N) f32:  f[:, d]
    delay_t = delay_t_ref[...]         # (N, N): delay_t[v, u] = delay[u, v]

    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    m_t = (rows == nh_row).astype(jnp.float32)                # (N, N)
    at_d = rows == d
    hi = jax.lax.Precision.HIGHEST
    nt = (((1,), (1,)), ((), ()))      # a @ b.T

    def body(_, carry):
        c_t, util, hops, dsum, visits = carry
        done = jnp.sum(jnp.where(at_d, c_t, 0.0), axis=0, keepdims=True)
        live = 1.0 - done                                     # (1, N)
        cw = c_t * (f_row * live)
        cn_t = jnp.dot(m_t, c_t, preferred_element_type=jnp.float32)
        util = util + jax.lax.dot_general(
            cw, cn_t, nt, precision=hi, preferred_element_type=jnp.float32)
        step = jnp.sum(
            jnp.dot(delay_t, c_t, precision=hi,
                    preferred_element_type=jnp.float32) * cn_t,
            axis=0, keepdims=True)
        dsum = dsum + live * step
        hops = hops + live
        visits = visits + jnp.sum(cw, axis=1, keepdims=True)  # (N, 1)
        return cn_t, util, hops, dsum, visits

    row0 = jnp.zeros((1, n), jnp.float32)
    _, util_acc, hops, dsum, visits_acc = jax.lax.fori_loop(
        0, max_hops, body,
        ((rows == cols).astype(jnp.float32), jnp.zeros((n, n), jnp.float32),
         row0, row0, jnp.zeros((n, 1), jnp.float32)),
    )
    util_ref[...] += util_acc
    visits_ref[...] += visits_acc
    hops_ref[0] = hops
    dsum_ref[0] = dsum


@functools.partial(jax.jit, static_argnames=("max_hops", "interpret"))
def walk_accumulate(
    nh: jax.Array,      # (N, N) int32 next hops
    f: jax.Array,       # (N, N) f32 slot traffic
    delay: jax.Array,   # (N, N) f32 per-edge wire delay
    *,
    max_hops: int,
    interpret: bool = False,
):
    """Returns (hops, delay_sums, util, visits) matching
    core/routing.walk_paths (visits includes the destination router)."""
    n = nh.shape[0]
    kernel = functools.partial(_walk_kernel, max_hops=max_hops, n=n)
    row_d = pl.BlockSpec((1, 1, n), lambda d: (d, 0, 0),
                         memory_space=pltpu.VMEM)
    whole = pl.BlockSpec((n, n), lambda d: (0, 0), memory_space=pltpu.VMEM)
    util, hops_t, dsum_t, visits = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[row_d, row_d, whole],
        out_specs=[
            whole,
            row_d,
            row_d,
            pl.BlockSpec((n, 1), lambda d: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, n), jnp.float32),     # util (directed)
            jax.ShapeDtypeStruct((n, 1, n), jnp.float32),  # hops, [d, 0, s]
            jax.ShapeDtypeStruct((n, 1, n), jnp.float32),  # delay sums
            jax.ShapeDtypeStruct((n, 1), jnp.float32),     # visits
        ],
        interpret=interpret,
    )(nh.astype(jnp.int32).T.reshape(n, 1, n),
      f.astype(jnp.float32).T.reshape(n, 1, n),
      delay.astype(jnp.float32).T)
    visits = visits[:, 0] + jnp.sum(f, axis=0)  # destination router traversal
    return hops_t[:, 0, :].T, dsum_t[:, 0, :].T, util, visits
