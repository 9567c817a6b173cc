"""Architecture registry + input_specs() (ShapeDtypeStruct stand-ins).

input_specs() never allocates: every entry is a jax.ShapeDtypeStruct with
weak-type-correct dtypes, shardable along the logical axes the distribution
layer expects. The dry-run lowers against these directly."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models.common import ModelConfig
from . import (chameleon_34b, deepseek_coder_33b, gemma3_1b, mamba2_1_3b,
               mistral_large_123b, moonlight_16b_a3b, qwen3_moe_30b_a3b,
               whisper_base, yi_6b, zamba2_2_7b)
from .shapes import SHAPES, WHISPER_MAX_TARGET, Shape, applicable, cell_status

_MODULES = {
    "mistral-large-123b": mistral_large_123b,
    "gemma3-1b": gemma3_1b,
    "deepseek-coder-33b": deepseek_coder_33b,
    "yi-6b": yi_6b,
    "qwen3-moe-30b-a3b": qwen3_moe_30b_a3b,
    "moonlight-16b-a3b": moonlight_16b_a3b,
    "zamba2-2.7b": zamba2_2_7b,
    "mamba2-1.3b": mamba2_1_3b,
    "whisper-base": whisper_base,
    "chameleon-34b": chameleon_34b,
}

ARCH_NAMES = tuple(_MODULES)

#: other names that resolve to a registered config, outside ARCH_NAMES.
#: "moonshot-v1-16b-a3b" is still named by chip_bench's deferred
#: soc256-moonlight-train configuration and its tests.
ALIASES = {"moonshot-v1-16b-a3b": "moonlight-16b-a3b"}


def get_config(name: str, smoke: bool = False) -> ModelConfig:
    mod = _MODULES[ALIASES.get(name, name)]
    return mod.SMOKE if smoke else mod.CONFIG


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape: Shape) -> dict:
    """ShapeDtypeStruct pytree for the step function of (cfg, shape).

    train   -> {"tokens", "targets"} (+ "frames" for enc-dec)
    prefill -> {"tokens"} (+ "frames")
    decode  -> {"cache": <init_cache specs>, "token"}
    """
    b, s = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        tgt = min(WHISPER_MAX_TARGET, s)
        if shape.kind == "train":
            return {
                "frames": _sds((b, s, cfg.d_model), jnp.bfloat16),
                "tokens": _sds((b, tgt), jnp.int32),
                "targets": _sds((b, tgt), jnp.int32),
            }
        if shape.kind == "prefill":
            return {
                "frames": _sds((b, s, cfg.d_model), jnp.bfloat16),
                "tokens": _sds((b, 8), jnp.int32),
            }
        # decode: self cache of tgt, cross cache of s (audio frames)
        from ..models import encdec
        cache = jax.eval_shape(
            lambda: encdec.init_cache(cfg, b, tgt, s, jnp.bfloat16))
        return {"cache": cache, "token": _sds((b, 1), jnp.int32)}

    if shape.kind == "train":
        return {
            "tokens": _sds((b, s), jnp.int32),
            "targets": _sds((b, s), jnp.int32),
        }
    if shape.kind == "prefill":
        return {"tokens": _sds((b, s), jnp.int32)}
    # decode: one new token against a seq_len cache.
    from ..models import transformer
    cache = jax.eval_shape(
        lambda: transformer.init_cache(cfg, b, s, jnp.bfloat16))
    return {"cache": cache, "token": _sds((b, 1), jnp.int32)}


__all__ = [
    "ALIASES", "ARCH_NAMES", "SHAPES", "applicable", "cell_status",
    "get_config", "input_specs",
]
