"""Evaluator host time per dispatch (``core/evaluate.py``): self time of
the program's ``eval.pack`` (stack, pad, transfer of the inputs),
``eval.tables`` (host routing tables of the delta path) and
``eval.dispatch`` spans (launching the programs; compiles and the wait for
the device are child spans and not counted), over the number of
``eval.dispatch`` spans in the window."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import program_spans  # noqa: E402

NAMES = ("eval.pack", "eval.tables", "eval.dispatch")


def read(run):
    spans = program_spans.window_spans(run)
    if spans is None:
        return None
    n = sum(s.name == "eval.dispatch" for s in spans)
    if n <= 0:
        return None
    return program_spans.self_ns(spans, NAMES) * 1e-6 / n
