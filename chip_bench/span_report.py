#!/usr/bin/env python3
"""Where a traced window's time goes, read from the program's own spans.

    python3 chip_bench/span_report.py --workload paper64-avg.stage4 \\
        --seed 7 --seconds 50

Runs the cell as ``run.py --trace 1`` does and prints its result line, then
one JSON line with:

* ``split``: the window's seconds by phase, from self times of the
  program's spans (``program_spans.py``): compiles, surrogate fit, meta
  search, local search host work, evaluator host work, the wait for the
  device, and the rest;
* ``idle_by_span``: device idle seconds by the innermost span open at each
  idle interval's midpoint;
* ``compile_s_by_fun``: backend-compile seconds by compiled function;
* ``checks``: the program's counts against the harness's (evaluations and
  dispatches), its evaluator time against the harness's evaluator spans,
  the share of the window covered below ``noc.run``, the share of idle
  time inside named phases, and the ``repro.*`` events of the trace's host
  plane inside the window annotation;
* ``overhead``: the cost of one span, with the profiler off and on.

Exits 2 without an accelerator, like ``run.py``.
"""

from __future__ import annotations

import io
import json
import sys
import time

import run as run_mod   # first: puts the program's src on sys.path

import program_spans as ps
import trace_reduce

SPLIT = {
    "compile": None,                     # union of jit.compile, jit.lower
    "surrogate_fit": ("stage.fit", "stage.features"),
    "meta_search": ("stage.meta", "meta.step"),
    "local_host": ("local.sample", "local.select", "local.archive"),
    "eval_host": ("eval.pack", "eval.tables", "eval.dispatch"),
    "eval_wait": ("eval.wait",),
}


def _host_events(path: str) -> tuple[tuple[int, int] | None, list]:
    """(window annotation, [(name, start, end)] of ``repro.*`` host events)
    of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as fh:
        pd = ProfileData.from_serialized_xspace(fh.read())
    window, events = None, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
                if ev.name == trace_reduce.WINDOW:
                    window = (s, e)
                elif ev.name.startswith("repro."):
                    events.append((ev.name, s, e))
    return window, events


def span_cost_us(n: int = 20000) -> float:
    from repro import telemetry

    t0 = time.perf_counter()
    for _ in range(n):
        with telemetry.span("span_report.probe", rows=1):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def overhead(out_dir: str) -> dict:
    import jax

    off = span_cost_us()
    jax.profiler.start_trace(out_dir)
    try:
        on = span_cost_us()
    finally:
        jax.profiler.stop_trace()
    return {"span_us_profiler_off": off, "span_us_profiler_on": on}


def report(view, host: dict) -> dict:
    w = view.window
    spans = ps.window_spans(view)
    if spans is None:
        return {"error": "no program spans in the window"}
    win_ns = w.seconds * 1e9
    split = {}
    for k, names in SPLIT.items():
        ns = (ps.union_ns([(s.t0_ns, s.t1_ns) for s in spans
                            if s.name in ("jit.compile", "jit.lower")])
              if names is None else ps.self_ns(spans, names))
        split[k] = ns * 1e-9
    split["other"] = w.seconds - sum(split.values())

    disp = [s for s in spans if s.name == "eval.dispatch"]
    # the evaluator's spans do not nest in one another
    in_eval = sum(s.t1_ns - s.t0_ns for s in spans
                  if s.name in ("eval.dispatch", "eval.tables"))
    harness_eval = sum(t1 - t0 for t0, t1, *_ in w.spans) * 1e9
    below = (sum(s.t1_ns - s.t0_ns for s in spans if s.name == "noc.run")
             - ps.self_ns(spans, ("noc.run",)))
    idle = ps.idle_by_span(view) or {}
    idle_tot = sum(idle.values())
    unnamed = sum(v for k, v in idle.items()
                  if k in ("noc.run", "stage.iter", ps.OUTSIDE))
    hw, hev = host.get("window"), host.get("events", [])
    n_prog = sum(not s.name.startswith("jit.") for s in spans)
    n_trace = (sum(hw[0] <= s and e <= hw[1] for _, s, e in hev)
               if hw else 0)
    checks = {
        "rows_sum": sum(s.attrs["rows"] for s in disp), "evals": w.evals,
        "dispatch_spans": len(disp), "dispatches": w.calls,
        "eval_ms_program": in_eval * 1e-6,
        "eval_ms_harness": harness_eval * 1e-6,
        "eval_gap_pct": (100.0 * abs(in_eval - harness_eval) / harness_eval
                         if harness_eval else None),
        "below_noc_run_pct": 100.0 * below / win_ns,
        "idle_named_pct": (100.0 * (1 - unnamed / idle_tot)
                           if idle_tot else None),
        "repro_events_in_window": n_trace, "program_spans_in_window": n_prog,
    }
    by_fun: dict[str, float] = {}
    for s in spans:
        if s.name == "jit.compile":
            f = s.attrs.get("fun", "")
            by_fun[f] = by_fun.get(f, 0.0) + (s.t1_ns - s.t0_ns) * 1e-9
    return {"split_s": split, "idle_by_span_s": idle, "checks": checks,
            "compile_s_by_fun": dict(sorted(by_fun.items(),
                                            key=lambda kv: -kv[1])),
            "spans_in_window": len(spans), "window_s": w.seconds}


def main(argv=None, **run_kwargs) -> int:
    """``run_kwargs`` go to ``run.run`` (the CPU rehearsal's options)."""
    argv = list(sys.argv[1:] if argv is None else argv)
    got: dict = {}
    host: dict = {}
    orig_reduce, orig_view = trace_reduce.reduce_dir, trace_reduce.RunView

    def reduce_dir(trace_dir, n_devices=None):
        import glob
        import os

        files = sorted(glob.glob(os.path.join(trace_dir, "**",
                                              "*.xplane.pb"),
                                 recursive=True))
        if files:
            host["window"], host["events"] = _host_events(files[-1])
        return orig_reduce(trace_dir, n_devices=n_devices)

    def view(**kw):
        got["view"] = orig_view(**kw)
        return got["view"]

    trace_reduce.reduce_dir = reduce_dir
    trace_reduce.RunView = view
    buf = io.StringIO()
    try:
        rc = run_mod.run(argv + ["--trace", "1"], out=buf, **run_kwargs)
    finally:
        trace_reduce.reduce_dir = orig_reduce
        trace_reduce.RunView = orig_view
    sys.stdout.write(buf.getvalue())
    if rc != 0 or "view" not in got:
        return rc
    import tempfile

    rep = report(got["view"], host)
    with tempfile.TemporaryDirectory() as d:
        rep["overhead"] = overhead(d)
    if "checks" in rep:
        # compile spans come from JAX's events, not from span() calls
        n = rep["checks"]["program_spans_in_window"]
        rep["overhead"]["share_off_pct"] = (
            100.0 * n * rep["overhead"]["span_us_profiler_off"] * 1e-6
            / rep["window_s"])
    print(json.dumps(rep), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
