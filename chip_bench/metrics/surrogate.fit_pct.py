"""Share of the window spent fitting the surrogate on the host: self time
of the program's ``stage.fit`` (the forest's CART fit, ``core/forest.py``)
and ``stage.features`` (``design_features_batch`` on trajectories and
restarts) spans, over the window's seconds."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import program_spans  # noqa: E402


def read(run):
    return program_spans.self_pct(run, ("stage.fit", "stage.features"))
