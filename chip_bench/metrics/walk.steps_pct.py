"""Share of the path walk's step cap that the walk ran: 100 *
sum(walk_steps) / sum(walk_cap) over the window's ``eval.dispatch`` spans.
The evaluator (``core/evaluate.py``) sets both attributes on a dispatch
after its readback: ``walk_steps``, the steps the batched walk ran (the
batch's longest path, or the cap), and ``walk_cap``, ``max_hops``. A
program whose dispatches lack them reports nothing."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import program_spans  # noqa: E402


def read(run):
    spans = program_spans.window_spans(run)
    if spans is None:
        return None
    disp = [s.attrs for s in spans
            if s.name == "eval.dispatch" and "walk_cap" in s.attrs]
    cap = sum(a["walk_cap"] for a in disp)
    if cap <= 0:
        return None
    return 100.0 * sum(a["walk_steps"] for a in disp) / cap
