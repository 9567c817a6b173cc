"""jit'd public wrappers around the Pallas kernels with backend dispatch.

``use_kernel=None`` picks the compiled Pallas kernel on TPU and the pure-jnp
reference elsewhere. ``interpret=True`` runs the kernel through the Pallas
interpreter on any host (tests). An explicit ``use_kernel=True`` off the TPU
without ``interpret`` raises: nothing switches to interpret mode on its own.
Training uses custom_vjp wrappers whose backward pass recomputes via the
reference formulation (flash-style recompute — no O(S^2) residuals are
saved)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import link_util as _lu
from . import minplus as _mp
from . import ref as _ref
from . import ssd as _ssd


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _kernel_requested(use_kernel: bool | None, interpret: bool) -> bool:
    """Whether to run the Pallas kernel (compiled, or interpreted when
    ``interpret``); raises for a compiled kernel off the TPU."""
    if interpret:
        return True
    if use_kernel is None:
        return on_tpu()
    if use_kernel and not on_tpu():
        raise ValueError(
            "a compiled Pallas kernel needs a TPU (default backend is "
            f"{jax.default_backend()!r}); pass interpret=True to run it "
            "through the Pallas interpreter")
    return use_kernel


# ------------------------------------------------------------------ minplus
def minplus(a, b, *, use_kernel: bool | None = None, interpret: bool = False):
    if _kernel_requested(use_kernel, interpret):
        return _mp.minplus(a, b, interpret=interpret)
    return _ref.minplus_ref(a, b)


def apsp(cost, n_iters: int, **kw):
    d = cost
    for _ in range(n_iters):
        d = minplus(d, d, **kw)
    return d


# ---------------------------------------------------------------- link util
def walk_accumulate(nh, f, delay, *, max_hops: int,
                    use_kernel: bool | None = None, interpret: bool = False):
    if _kernel_requested(use_kernel, interpret):
        return _lu.walk_accumulate(nh, f, delay, max_hops=max_hops,
                                   interpret=interpret)
    return _ref.walk_accumulate_ref(nh, f, delay, max_hops=max_hops)


# ---------------------------------------------------------------- attention
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attention_kernel_trainable(q, k, v, causal, window):
    return _fa.flash_attention(q, k, v, causal=causal, window=window)


def _attn_fwd(q, k, v, causal, window):
    return _attention_kernel_trainable(q, k, v, causal, window), (q, k, v)


def _attn_bwd(causal, window, res, g):
    q, k, v = res
    # Recompute-based backward through the reference (no saved logits).
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ref.attention_ref(
            q_, k_, v_, causal=causal, window=window
        ),
        q, k, v,
    )
    return vjp(g)


_attention_kernel_trainable.defvjp(_attn_fwd, _attn_bwd)


def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              use_kernel: bool | None = None):
    """Fused attention with GQA: q (B,H,S,D), k/v (B,KH,S,D)."""
    use_kernel = on_tpu() if use_kernel is None else use_kernel
    if use_kernel:
        return _attention_kernel_trainable(q, k, v, causal, window)
    return _ref.attention_ref(q, k, v, causal=causal, window=window)


# ---------------------------------------------------------------------- ssd
@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd_kernel_trainable(x, dt, a, b, c, d, chunk):
    return _ssd.ssd(x, dt, a, b, c, d, chunk=chunk)


def _ssd_fwd(x, dt, a, b, c, d, chunk):
    return _ssd_kernel_trainable(x, dt, a, b, c, d, chunk), (x, dt, a, b, c, d)


def _ssd_bwd(chunk, res, g):
    x, dt, a, b, c, d = res
    _, vjp = jax.vjp(
        lambda *args: _ref.ssd_chunked_ref(*args, chunk=chunk),
        x, dt, a, b, c, d,
    )
    return vjp(g)


_ssd_kernel_trainable.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, a, b, c, d, *, chunk: int = 64,
        use_kernel: bool | None = None, return_state: bool = False):
    """Mamba-2 SSD: x (B,S,H,P), dt (B,S,H), a (H,), b/c (B,S,N), d (H,).
    ``return_state`` also returns the final (B,H,N,P) state (prefill path;
    always served by the chunked reference — state extraction is not part
    of the training-kernel contract)."""
    if return_state:
        if x.shape[1] % chunk == 0 and x.shape[1] > chunk:
            return _ref.ssd_chunked_ref(x, dt, a, b, c, d, chunk=chunk,
                                        return_state=True)
        return _ref.ssd_ref(x, dt, a, b, c, d, return_state=True)
    use_kernel = on_tpu() if use_kernel is None else use_kernel
    if use_kernel:
        return _ssd_kernel_trainable(x, dt, a, b, c, d, chunk)
    if x.shape[1] % chunk == 0 and x.shape[1] > chunk:
        return _ref.ssd_chunked_ref(x, dt, a, b, c, d, chunk=chunk)
    return _ref.ssd_ref(x, dt, a, b, c, d)
