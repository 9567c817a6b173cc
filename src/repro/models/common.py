"""Shared model substrate: config, init, norms, RoPE, losses, and the
logical-axis sharding hook every layer uses.

Models are hand-rolled functional JAX (param pytrees + pure apply fns); all
depth iteration uses lax.scan over stacked layer params so compile time and
HLO size are O(1) in depth (88-layer configs lower in seconds)."""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config describes every assigned architecture (configs/<id>.py)."""

    name: str
    family: str                    # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0              # 0 -> d_model // n_heads
    # Attention pattern.
    sliding_window: int = 0        # 0 -> full attention
    global_every: int = 0          # gemma3: layer l is global iff (l+1) % global_every == 0
    # MoE.
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    n_dense_layers: int = 0        # leading dense layers (MLP width d_ff)
    n_shared_experts: int = 0      # always-on experts of width moe_d_ff
    # Multi-head latent attention (DeepSeek-V2/V3, full-rank queries);
    # kv_lora_rank 0 -> off.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # SSM (Mamba-2 / SSD).
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    conv_width: int = 4
    # Hybrid (zamba2-style): one SHARED attention block every attn_every layers.
    attn_every: int = 0
    # Encoder-decoder (whisper-style).
    encoder_layers: int = 0
    # Frontend stubs ([audio]/[vlm] — the task specifies backbone-only).
    frontend: str = ""             # "" | "audio_stub" | "vq_stub"
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    dtype: Any = jnp.float32       # parameter dtype
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True
    unroll_layers: bool = False    # python-loop depth (roofline per-layer deltas)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def scaled(self, **overrides) -> "ModelConfig":
        return dataclasses.replace(self, **overrides)

    def param_count(self) -> int:
        """Analytic parameter count (roofline MODEL_FLOPS uses this)."""
        d, v = self.d_model, self.vocab
        emb = v * d * (1 if self.family != "encdec" else 1)
        head = d * v
        total = emb + head + d  # + final norm
        def attn_params():
            if self.kv_lora_rank:   # MLA: the layer's two norms are added below
                return self._attn_proj_params()
            return self._attn_proj_params() + 2 * d
        def mlp_params(ff):
            return 3 * d * ff
        if self.family in ("dense", "vlm"):
            total += self.n_layers * (attn_params() + mlp_params(self.d_ff) + 2 * d)
        elif self.family == "moe":
            per = attn_params() + 2 * d + d * self.n_experts \
                + (self.n_experts + self.n_shared_experts) * 3 * d * self.moe_d_ff
            dense = attn_params() + 2 * d + mlp_params(self.d_ff)
            total += (self.n_layers - self.n_dense_layers) * per \
                + self.n_dense_layers * dense
        elif self.family == "ssm":
            total += self.n_layers * (self._mamba_params() + d)
        elif self.family == "hybrid":
            total += self.n_layers * (self._mamba_params() + d)
            total += attn_params() + mlp_params(self.d_ff) + 2 * d  # shared block
        elif self.family == "encdec":
            total += self.encoder_layers * (attn_params() + mlp_params(self.d_ff) + 2 * d)
            # decoder layers add cross attention
            total += self.n_layers * (2 * attn_params() + mlp_params(self.d_ff) + 3 * d)
        return int(total)

    def _attn_proj_params(self) -> int:
        """Attention projections of one layer. MLA: the query projection,
        the joint KV down-projection to the latent plus the shared RoPE key,
        the latent's norm, its up-projection to per-head keys and values,
        and the output projection."""
        d, h = self.d_model, self.n_heads
        if not self.kv_lora_rank:
            hd = self.resolved_head_dim
            return d * hd * h + 2 * d * hd * self.n_kv_heads + hd * h * d
        r = self.kv_lora_rank
        return (d * h * (self.qk_nope_head_dim + self.qk_rope_head_dim)
                + d * (r + self.qk_rope_head_dim) + r
                + r * h * (self.qk_nope_head_dim + self.v_head_dim)
                + h * self.v_head_dim * d)

    def _mamba_params(self) -> int:
        h, p, n = self.ssm_heads, self.ssm_head_dim, self.ssm_state
        d_in = h * p
        d = self.d_model
        # in_proj -> (z, x, B, C, dt) ; out_proj ; conv over (x,B,C) ; A, D, norm
        return d * (2 * d_in + 2 * n + h) + d_in * d + \
            self.conv_width * (d_in + 2 * n) + 2 * h + d_in

    def active_param_count(self) -> int:
        """MoE: parameters touched per token (6*N_active*D flops rule)."""
        if self.family != "moe":
            return self.param_count()
        d = self.d_model
        attn = self._attn_proj_params() + 2 * d
        moe_layer = attn + d * self.n_experts \
            + (self.top_k + self.n_shared_experts) * 3 * d * self.moe_d_ff
        dense_layer = attn + 3 * d * self.d_ff
        return int(
            self.vocab * d * 2 + d
            + (self.n_layers - self.n_dense_layers) * moe_layer
            + self.n_dense_layers * dense_layer
        )


# --------------------------------------------------------------- sharding hook
class _Policy(threading.local):
    fn: Callable[[jax.Array, tuple], jax.Array] | None = None


_POLICY = _Policy()


@contextlib.contextmanager
def activation_sharding(fn: Callable[[jax.Array, tuple], jax.Array]):
    """Install an activation-sharding callback: models call
    ``pshard(x, ('batch', 'seq', 'embed'))`` on layer boundaries and the
    distribution layer (repro.dist.sharding) maps logical axes to the mesh."""
    prev = _POLICY.fn
    _POLICY.fn = fn
    try:
        yield
    finally:
        _POLICY.fn = prev


def pshard(x: jax.Array, logical: tuple) -> jax.Array:
    if _POLICY.fn is None:
        return x
    return _POLICY.fn(x, logical)


def scan_layers(body, init, xs, *, unroll: bool = False):
    """lax.scan over stacked layer params, or a python loop when ``unroll``
    (the roofline analysis needs per-layer HLO deltas — collectives inside a
    while body appear once in the text regardless of trip count)."""
    if not unroll:
        return jax.lax.scan(body, init, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    carry = init
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys


# ------------------------------------------------------------------- layers
def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (..., S, H, D); positions (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def init_dense(key, shape, scale_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[scale_axis]
    return (jax.random.normal(key, shape) * (fan_in ** -0.5)).astype(dtype)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array | None = None) -> jax.Array:
    """Mean token cross-entropy in f32. logits (..., V), labels (...)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
