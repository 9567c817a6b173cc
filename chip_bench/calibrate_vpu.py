#!/usr/bin/env python3
"""Measure the chip's f32 vector add-then-min rate, the compute ceiling of
the min-plus kernel, for ``peaks.json``.

    python3 chip_bench/calibrate_vpu.py        # on the chip; prints JSON

Two VMEM-resident Pallas kernels with the min-plus op mix (one add and one
min per element step), each at several shapes:

* ``chain``: ``acc = min(acc + x, y)`` repeated ``reps`` times on an
  (S, 128) block, so no operand leaves the core;
* ``block``: the min-plus block product ``o = min(o, min_k a[:, k] + b[k])``
  of one (n, n) x (n, n) block pair, revisited along a grid whose block
  index never changes, so the operands are fetched once.

Each shape is timed over many calls after a warm-up call, wall clock
around ``block_until_ready``; the rate is operations over seconds, and the
best over shapes and repeats is the ceiling. Operations: 2 per element step.
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _chain(s: int, reps: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, y_ref, o_ref):
        x = x_ref[...]
        y = y_ref[...]

        def body(_, acc):
            for _ in range(8):            # unrolled by hand
                acc = jnp.minimum(acc + x, y)
            return acc

        o_ref[...] = jax.lax.fori_loop(0, reps // 8, body, x)

    fn = jax.jit(pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((s, 128), jnp.float32)))
    x = jax.random.uniform(jax.random.key(0), (s, 128), jnp.float32)
    y = jax.random.uniform(jax.random.key(1), (s, 128), jnp.float32) + 4.0
    return (lambda: fn(x, y)), 2 * s * 128 * (reps // 8 * 8)


def _block(n: int, reps: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(a_ref, b_ref, o_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            o_ref[...] = jnp.full_like(o_ref, 1e9)

        a = a_ref[...]
        b = b_ref[...]
        o_ref[...] = jnp.minimum(
            o_ref[...], jnp.min(a[:, :, None] + b[None, :, :], axis=1))

    fn = jax.jit(pl.pallas_call(
        kernel, grid=(reps,),
        in_specs=[pl.BlockSpec((n, n), lambda r: (0, 0)),
                  pl.BlockSpec((n, n), lambda r: (0, 0))],
        out_specs=pl.BlockSpec((n, n), lambda r: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32)))
    a = jax.random.uniform(jax.random.key(2), (n, n), jnp.float32)
    b = jax.random.uniform(jax.random.key(3), (n, n), jnp.float32)
    return (lambda: fn(a, b)), 2 * n * n * n * reps


def measure(make, calls: int = 20, repeats: int = 3) -> float:
    run, ops = make
    run().block_until_ready()
    best = 0.0
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = run()
        out.block_until_ready()
        best = max(best, ops * calls / (time.perf_counter() - t0))
    return best


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate_vpu: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    shapes = [(f"chain_{s}x128", _chain, s, 8192)
              for s in (64, 128, 256, 512, 1024, 2048, 4096)]
    shapes += [(f"block_{n}", _block, n, 256) for n in (64, 128)]
    rates, failed = {}, {}
    for name, make, size, reps in shapes:
        try:
            rates[name] = measure(make(size, reps))
        except Exception as e:   # noqa: BLE001 - a shape the chip refuses
            failed[name] = f"{type(e).__name__}: {str(e)[:200]}"
    best = max(rates, key=rates.get)
    print(json.dumps({"device_kind": dev.device_kind, "rates": rates,
                      "failed": failed, "best": best,
                      "vpu_f32_addmin_ops_per_s": rates[best]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
