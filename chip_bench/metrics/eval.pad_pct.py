"""Share of the device's rows that are padding: 100 * sum(padded - rows) /
sum(padded) over the window's ``eval.dispatch`` spans, whose ``rows`` and
``padded`` attributes the evaluator (``core/evaluate.py``) sets on every
dispatch (batches pad to the next power of two)."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import program_spans  # noqa: E402


def read(run):
    spans = program_spans.window_spans(run)
    if spans is None:
        return None
    disp = [s.attrs for s in spans if s.name == "eval.dispatch"]
    padded = sum(a["padded"] for a in disp)
    if padded <= 0:
        return None
    return 100.0 * (padded - sum(a["rows"] for a in disp)) / padded
