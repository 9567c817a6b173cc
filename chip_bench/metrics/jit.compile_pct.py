"""Share of the window spent tracing, lowering and compiling programs (JAX
runtime and entry): the union of the program's ``jit.compile`` and
``jit.lower`` spans, which ``repro.telemetry`` records from JAX's compile
events, over the window's seconds."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import program_spans  # noqa: E402


def read(run):
    return program_spans.union_pct(run, ("jit.compile", "jit.lower"))
