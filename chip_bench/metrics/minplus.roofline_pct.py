"""Share of its roofline that the min-plus kernel (``kernels/minplus.py``)
reaches: the least time its calls could take on this chip (the larger of
operations over the f32 vector add/min rate and bytes over HBM bandwidth,
``minplus_cost.py``, ``peaks.json``) over their device time in the trace.
Each call's (batch, N) is read from its output shape in the trace."""

import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import minplus_cost  # noqa: E402

KERNEL = r"minplus"
SHAPE = re.compile(r"f32\[(\d+),(\d+),(\d+)\]")


def read(run):
    w = run.window
    if w.trace is None or run.peaks is None:
        return None
    least = busy = 0.0
    for name, dur_ns in w.trace.op_events(KERNEL):
        m = SHAPE.search(name)
        if not m or dur_ns <= 0:
            continue
        b, n = int(m.group(1)), int(m.group(2))
        ops, nbytes = minplus_cost.call_cost(b, n)
        least += minplus_cost.least_time(ops, nbytes, run.peaks)[0]
        busy += dur_ns * 1e-9
    if busy <= 0:
        return None
    return 100.0 * least / busy
