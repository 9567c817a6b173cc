#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chip_bench/run.py --workload paper64-avg.stage4 --seed 7 \\
        --seconds 40 --trace 0

Set-up (from process start: JAX and TPU start-up, the problem, the
evaluator, every padded batch shape, one warm-up search) is reported as
``setup_s``. Then back-to-back fixed-budget searches run through
``repro.noc.run`` for ``--seconds``. With ``--trace 0`` the cell's
end-to-end metrics are reported; with ``--trace 1`` the window is traced
by the JAX profiler and the cell's per-layer metrics are reported instead.
After the window the evaluated rows and the first search's front are
compared with the plain reference (``reference.py``); every compared number
is printed beside its limit, last on standard error and as the ``checks``
key of the result line. The result is the last line of standard output.

Without an accelerator, or with fewer chips than the cell asks for, the run
exits with code 2 and prints no result.
"""

from __future__ import annotations

import os
import sys
import time


def _process_start() -> float:
    """Wall-clock time at which this process started."""
    try:
        with open("/proc/self/stat") as fh:
            start_ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        return time.time() - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


T_START = _process_start()
# No TPU library log files outside the checkout (set before JAX loads).
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
for p in (str(BENCH_DIR), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)



def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(devices, window=None) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
    if window is not None and window.trace is not None:
        info["busy_s"] = window.trace.busy_s_mean
        info["window_s"] = window.trace.window_s
    return info


def run(argv=None, *, require_chip: bool = True, interpret: bool = False,
        spec_override: dict | None = None, ev_kwargs: dict | None = None,
        bench_dir: Path = BENCH_DIR, out=sys.stdout, err=sys.stderr) -> int:
    """Run one cell; returns the exit code. The keyword arguments exist for
    the CPU rehearsal in ``tests/``: no chip, interpret-mode kernels, a
    smaller tile spec, evaluator options, and the benchmark's data files
    (``BENCHMARK.json`` beside ``bench_dir``) from another directory."""
    args = parse(argv)
    import harness
    import trace_reduce

    cell = harness.load_cell(args.workload, bench_dir=bench_dir)

    from repro import compile_cache

    compile_cache.enable()
    import jax

    # Every program compiled in set-up is written to the persistent cache,
    # however short its compile, so the next run of the cell finds it.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        print(f"chip_bench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX sees {len(devices)} {devices[0].platform} device(s)",
              file=err)
        return 2

    bench = harness.Bench(cell, args.seed, interpret=interpret,
                          spec_override=spec_override, ev_kwargs=ev_kwargs)
    bench.setup()
    setup_s = time.time() - T_START

    trace_dir = tempfile.mkdtemp(prefix="chip_bench_trace_") \
        if args.trace else None
    try:
        win = bench.window(args.seconds, trace_dir)
        device = device_info(bench.devices)
        if trace_dir is not None:
            peaks = trace_reduce.load_peaks(device["kind"]) \
                if require_chip else None
            win.trace = trace_reduce.reduce_dir(trace_dir,
                                                n_devices=cell.chips)
            device = device_info(bench.devices, win)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    spec_dict, f = bench.spec_dict, bench.f
    case, mix = bench.problem.case, cell.mix
    bench.release()
    t_check = time.perf_counter()
    numbers = harness.check_window(spec_dict, f, case, win, cell.config,
                                   args.seed)
    check_s = time.perf_counter() - t_check
    correct, checks = harness.verdict(numbers, cell.config["limits"])
    metrics = {}
    breakdown = None
    if args.trace:
        view = trace_reduce.RunView(window=win, cell=cell, peaks=peaks,
                                    spec=bench.spec_dict)
        for m in cell.per_layer:
            v = harness.load_reader(m["name"], bench_dir)(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = win.trace.breakdown()
    else:
        values = {
            "evals_per_s": win.evals / win.seconds,
            "front_phv": numbers.get("front_phv", 0.0),
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    for e in win.errors:
        print(e, file=err)
    correct = correct and not win.errors

    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=err)
    line = {"correct": bool(correct), "attempted": len(win.results),
            "failed": len(win.errors), "metrics": metrics, "device": device,
            "searches_completed": sum(harness.completed(r)
                                      for r in win.results),
            "evals": win.evals, "dispatches": win.calls,
            "window_s": win.seconds, "driver": mix["driver"],
            "check_s": check_s, "compiles_in_window": win.compiles,
            "compile_s_in_window": win.compile_s,
            "searches": [None if r is None else
                         {"evals": int(r.n_evals), "phv": float(r.phv()),
                          "front": len(r.designs)} for r in win.results]}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    print(json.dumps(line), file=out, flush=True)
    return 0


def main(argv=None) -> int:
    try:
        return run(argv)
    except Exception as e:   # noqa: BLE001 - report and exit nonzero
        import traceback

        traceback.print_exc()
        print(f"chip_bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
