#!/usr/bin/env python3
"""Readings that set the limits of a cell's check: the program's numbers and
the control's, on many seeds, in one process.

    python3 chip_bench/readings.py --workload paper64-avg.stage4 \\
        --seeds 12 --seconds 40

One set-up, then for each seed one window at the cell's own load, as a run
makes it, and its check twice: the program's rows against the float64
reference (the lower reading is the largest over seeds), and the control's,
the reference computed in bfloat16 (one precision step below the
evaluator's float32) put in the program's place on the same designs (the
upper reading is the smallest over seeds). Prints one JSON line per seed
and a summary line. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH_DIR = Path(__file__).resolve().parent
for p in (str(BENCH_DIR), str(BENCH_DIR.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import ml_dtypes
    import numpy as np

    import harness
    from repro import compile_cache

    compile_cache.enable()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("readings: no TPU", file=sys.stderr)
        return 2
    bench = harness.Bench(cell, args.first_seed)
    bench.setup()
    names = [n for n in cell.config["limits"] if n != "first_front"]
    prog = {n: [] for n in names}
    ctrl = {n: [] for n in names}
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        bench.reseed(seed)
        win = bench.window(args.seconds)
        row = {"seed": seed, "evals_per_s": win.evals / win.seconds,
               "first_front": len(win.results[0].designs)
               if win.results and win.results[0] is not None else 0,
               "errors": len(win.errors)}
        for label, dtype, acc in (("program", np.float64, prog),
                                  ("control", ml_dtypes.bfloat16, ctrl)):
            nums = harness.check_window(bench.spec_dict, bench.f,
                                        bench.problem.case, win, cell.config,
                                        seed, dtype)
            row[label] = nums
            for n in names:
                acc[n].append(nums[n])
        print(json.dumps(row), flush=True)
    print(json.dumps({"summary": True, "workload": cell.name,
                      "lower": {n: max(v) for n, v in prog.items()},
                      "upper": {n: min(v) for n, v in ctrl.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
