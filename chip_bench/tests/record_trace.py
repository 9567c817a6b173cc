#!/usr/bin/env python3
"""Record the small TPU trace that ``test_trace_reduce.py`` reduces.

    python3 chip_bench/tests/record_trace.py OUT_DIR    # on the chip

A 16-tile evaluator on the chip, a handful of dispatches of a few batch
sizes inside a ``chip_bench.window`` annotation, each wrapped in a
``chip_bench.eval.batch`` span, with host sleeps between them standing for
the search driver. Writes the ``.xplane.pb`` under OUT_DIR and a JSON file
of what the trace must show (dispatch count, batch sizes, sleeps).
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

SIZES = (1, 4, 16, 16)
SLEEP_S = 0.05


def main(out_dir: str) -> int:
    import jax
    import numpy as np

    from repro.core.problem import random_design, spec_16
    from repro.noc import NocProblem

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    problem = NocProblem(spec=spec_16(), traffic="BFS", case="case5")
    ev = problem.evaluator()
    rng = np.random.default_rng(0)
    designs = [random_design(problem.spec, rng) for _ in range(max(SIZES))]
    for b in sorted(set(SIZES)):
        ev.batch(designs[:b])                      # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chip_bench.window"):
        for b in SIZES:
            time.sleep(SLEEP_S)
            with jax.profiler.TraceAnnotation("chip_bench.eval.batch"):
                ev.batch(designs[:b])
        time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    with open(os.path.join(out_dir, "expected.json"), "w") as fh:
        json.dump({"sizes": SIZES, "sleep_s": SLEEP_S,
                   "n_tiles": problem.spec.n_tiles,
                   "apsp_iters": int(ev.consts.apsp_iters),
                   "device_kind": jax.devices()[0].device_kind}, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
