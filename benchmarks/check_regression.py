"""CI guard: compare a fresh ``BENCH_netsim.json`` against the committed
baseline (``benchmarks/BENCH_baseline.json``) and exit nonzero when any
tracked kernel slowed down by more than the threshold (default 1.5x).

Usage::

    PYTHONPATH=src python -m benchmarks.check_regression            # compare
    PYTHONPATH=src python -m benchmarks.check_regression --run      # bench first
    PYTHONPATH=src python -m benchmarks.check_regression --threshold 2.0

Keys present in the baseline but missing from the fresh run fail (a kernel
silently dropped out of the bench is itself a regression); keys only in the
fresh run are ignored (new kernels get picked up when the baseline is
re-committed)."""

from __future__ import annotations

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
BASELINE = os.path.join(_HERE, "BENCH_baseline.json")
FRESH = os.path.join(os.path.dirname(_HERE), "BENCH_netsim.json")

#: timing keys guarded against slowdowns (all microseconds, lower = better).
#: The forest rows track each backend separately — the min-of-backends
#: headline key would hide one backend regressing while the other stays fast.
#: The reference paths are tracked too (ROADMAP: extend as kernels land) —
#: they are the oracles every speedup is quoted against, and a silently
#: slowed oracle inflates every reported speedup.
TRACKED = (
    "vectorized_cold_us",
    "vectorized_warm_us",
    "reference_us",
    "batch_us_per_sim",
    "forest_predict_4k_numpy_us",
    "forest_predict_4k_jnp_us",
    "forest_reference_4k_us",
    "stage_meta_search_us_per_step",
    "stage_fused_us_per_step",
    "stage_dist_4w_us",
    "stage_spmd_2w_us",
    "stage_dist_ckpt_4w_us",
    "serve_submit_overhead_us",
    "serve_8req_4w_us",
    "traffic_model_gen_us",
    "agnostic_llm_cross_us",
    "apsp_delta_256_us",
    "pareto_insert_1k_us",
)


def compare(baseline: dict, fresh: dict, threshold: float = 1.5,
            tracked=TRACKED) -> list[str]:
    """List of human-readable regression descriptions (empty = pass)."""
    problems = []
    for key in tracked:
        if key not in baseline:
            continue  # baseline predates this kernel
        if key not in fresh:
            problems.append(f"{key}: missing from fresh run")
            continue
        ratio = fresh[key] / baseline[key]
        if ratio > threshold:
            problems.append(
                f"{key}: {fresh[key]:.0f}us vs baseline {baseline[key]:.0f}us "
                f"({ratio:.2f}x > {threshold:.2f}x)")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default=BASELINE)
    ap.add_argument("--fresh", default=FRESH)
    ap.add_argument("--threshold", type=float, default=1.5)
    ap.add_argument("--run", action="store_true",
                    help="run kernel_bench first to produce the fresh json")
    args = ap.parse_args(argv)

    if args.run:
        from . import kernel_bench
        kernel_bench.main()

    if not os.path.exists(args.fresh):
        print(f"fresh bench json not found at {args.fresh}; "
              "run `python -m benchmarks.check_regression --run` or "
              "`python -m benchmarks.run kernel_bench` first")
        return 2
    with open(args.baseline) as fh:
        baseline = json.load(fh)
    with open(args.fresh) as fh:
        fresh = json.load(fh)

    problems = compare(baseline, fresh, args.threshold)
    if problems:
        print("REGRESSIONS:")
        for p in problems:
            print("  " + p)
        return 1
    print(f"ok: {len([k for k in TRACKED if k in baseline])} tracked kernels "
          f"within {args.threshold:.2f}x of baseline")
    return 0


if __name__ == "__main__":
    sys.exit(main())
