"""Pure-jnp oracles for every Pallas kernel (the correctness references).

Each function is shape-compatible with its kernel counterpart; tests sweep
shapes/dtypes and assert_allclose kernel(interpret=True) against these."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


# ------------------------------------------------------------------ minplus
def minplus_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """(B, N, N) min-plus product, batched."""
    return jnp.min(a[:, :, :, None] + b[:, None, :, :], axis=2)


# ------------------------------------------------------- link-util walk
def walk_accumulate_np(nh, f, delay, *, max_hops: int):
    """Pure-numpy scalar-loop oracle: walk each (src, dst) pair one hop at
    a time exactly as the routing recurrence defines it. Third corner of
    the link-util conformance triangle (numpy / jnp / Pallas-interpret),
    mirroring minplus/forest."""
    import numpy as np

    nh = np.asarray(nh)
    f = np.asarray(f, np.float32)
    delay = np.asarray(delay, np.float32)
    n = nh.shape[0]
    hops = np.zeros((n, n), np.float32)
    dsum = np.zeros((n, n), np.float32)
    util = np.zeros((n, n), np.float32)
    visits = np.zeros((n,), np.float32)
    for i in range(n):
        for j in range(n):
            cur = i
            for _ in range(max_hops):
                if cur == j:
                    break
                nxt = int(nh[cur, j])
                util[cur, nxt] += f[i, j]
                visits[cur] += f[i, j]
                dsum[i, j] += delay[cur, nxt]
                hops[i, j] += 1.0
                cur = nxt
    visits += f.sum(axis=0)  # dst router traversal at completion
    return hops, dsum, util, visits


def walk_accumulate_ref(nh, f, delay, *, max_hops: int):
    """Scatter-add formulation (the GPU-natural port) — reuses the routing
    walk and adapts output dtypes to the kernel contract."""
    from repro.core.routing import walk_paths

    hops, dsum, util, visits, _, _ = walk_paths(
        jnp.asarray(nh, jnp.int32), jnp.asarray(delay, jnp.float32),
        jnp.asarray(f, jnp.float32), max_hops,
    )
    return hops.astype(jnp.float32), dsum, util, visits


# ---------------------------------------------------------------- attention
def attention_ref(
    q: jax.Array,   # (B, H, Sq, D)
    k: jax.Array,   # (B, KH, Sk, D)
    v: jax.Array,   # (B, KH, Sk, D)
    *,
    causal: bool = True,
    window: int | None = None,
    logit_dtype=jnp.float32,
) -> jax.Array:
    b, h, sq, d = q.shape
    kh, sk = k.shape[1], k.shape[2]
    group = h // kh
    kx = jnp.repeat(k, group, axis=1)
    vx = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(logit_dtype),
                   kx.astype(logit_dtype)) * (d ** -0.5)
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vx.astype(logit_dtype)).astype(q.dtype)


# ---------------------------------------------------------------------- ssd
def ssd_ref(x, dt, a, b, c, d, return_state: bool = False):
    """Sequential SSD recurrence — the ground-truth scan.

    x (B,S,H,P), dt (B,S,H), a (H,), b/c (B,S,N), d (H,). Returns (B,S,H,P)
    (plus the final state (B,H,N,P) when ``return_state``).
    """
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    bsz, s, h, p = x.shape
    n = b.shape[-1]

    def step(h_state, inp):
        xt, dtt, bt, ct = inp  # (B,H,P), (B,H), (B,N), (B,N)
        decay = jnp.exp(dtt * a[None, :])                      # (B,H)
        upd = jnp.einsum("bn,bhp->bhnp", bt, xt * dtt[..., None])
        h_state = decay[..., None, None] * h_state + upd       # (B,H,N,P)
        yt = jnp.einsum("bn,bhnp->bhp", ct, h_state)
        return h_state, yt

    h0 = jnp.zeros((bsz, h, n, p), jnp.float32)
    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(bf, 1, 0), jnp.moveaxis(cf, 1, 0))
    h_final, ys = jax.lax.scan(step, h0, xs)
    y = (jnp.moveaxis(ys, 0, 1) + d[None, None, :, None] * xf).astype(x.dtype)
    return (y, h_final) if return_state else y


def ssd_chunked_ref(x, dt, a, b, c, d, *, chunk: int = 64,
                    return_state: bool = False):
    """Chunk-parallel jnp formulation (same math as the kernel, XLA-fused) —
    this is the differentiable path models use when the kernel is off."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    nc = s // chunk
    xf = x.astype(jnp.float32).reshape(bsz, nc, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(bsz, nc, chunk, h)
    bf = b.astype(jnp.float32).reshape(bsz, nc, chunk, n)
    cf = c.astype(jnp.float32).reshape(bsz, nc, chunk, n)

    la = dtf * a[None, None, None, :]                    # (B,C,Q,H)
    sc = jnp.cumsum(la, axis=2)                          # inclusive cumsum
    tril = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))
    g = jnp.einsum("bcqn,bckn->bcqk", cf, bf)
    w = (g[:, :, :, :, None]
         * jnp.exp(sc[:, :, :, None, :] - sc[:, :, None, :, :])
         * dtf[:, :, None, :, :]
         * tril[None, None, :, :, None])                 # (B,C,Q,K,H)
    y_intra = jnp.einsum("bcqkh,bckhp->bcqhp", w, xf)

    # Chunk summary states and their prefix scan.
    to_end = jnp.exp(sc[:, :, -1:, :] - sc) * dtf        # (B,C,Q,H)
    chunk_state = jnp.einsum("bcqn,bcqhp->bchnp", bf, xf * to_end[..., None])
    chunk_decay = jnp.exp(sc[:, :, -1, :])               # (B,C,H)

    def scan_chunks(h_prev, inp):
        st, dec = inp                                     # (B,H,N,P), (B,H)
        h_new = dec[..., None, None] * h_prev + st
        return h_new, h_prev

    h0 = jnp.zeros((bsz, h, n, p), jnp.float32)
    h_final, h_befores = jax.lax.scan(
        scan_chunks, h0,
        (jnp.moveaxis(chunk_state, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    h_befores = jnp.moveaxis(h_befores, 0, 1)             # (B,C,H,N,P)
    cexp = cf[:, :, :, None, :] * jnp.exp(sc)[..., None]  # (B,C,Q,H,N)
    y_inter = jnp.einsum("bcqhn,bchnp->bcqhp", cexp, h_befores)
    y = (y_intra + y_inter).reshape(bsz, s, h, p)
    y = (y + d[None, None, :, None] * x.astype(jnp.float32)).astype(x.dtype)
    return (y, h_final) if return_state else y
