"""Share of the window spent building host routing tables on the delta
path (``core/evaluate.py``, ``core/routing.py``): self time of the
program's ``eval.tables`` (cache look-ups, swaps, ``delta_link_move``
updates, candidate assembly) and ``tables.build`` (each full
``host_tables`` build, on a cache miss or a delta's fallback) spans over
the window's seconds."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import program_spans  # noqa: E402

NAMES = ("eval.tables", "tables.build")


def read(run):
    return program_spans.self_pct(run, NAMES)
