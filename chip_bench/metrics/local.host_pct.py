"""Share of the window spent in the local search's host work
(``core/local_search.py``): self time of the program's ``local.sample``
(neighbour moves), ``local.select`` (PHV scoring, argmax, the winner) and
``local.archive`` (Pareto merge, thinning, history) spans over the window's
seconds."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import program_spans  # noqa: E402

NAMES = ("local.sample", "local.select", "local.archive")


def read(run):
    return program_spans.self_pct(run, NAMES)
