"""Share of the window spent in the meta search (``core/stage.py``
``_meta_greedy`` and ``core/fused.py``'s scorer): self time of the
program's ``stage.meta`` and ``meta.step`` spans over the window's seconds.
Compiles inside it are child spans and are not counted."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import program_spans  # noqa: E402


def read(run):
    return program_spans.self_pct(run, ("stage.meta", "meta.step"))
