"""The per-layer metrics that read the program's own spans.

A CPU rehearsal of ``paper64-avg.stage4`` with ``--trace 1`` reports all of
them; ``idle_by_span`` puts each idle interval under the innermost span
open at its midpoint; and every reader reports nothing when the ring of
spans lost one from inside the window, or when the program records none.
"""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import harness
import program_spans as ps
import trace_reduce as tr

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
CPU_CELL = Path(__file__).resolve().parent / "cpu_cell.py"
METRICS = ("jit.compile_pct", "surrogate.fit_pct", "meta.search_pct",
           "local.host_pct", "eval.pad_pct", "eval.host_ms_per_dispatch")


@dataclasses.dataclass
class _Window:
    t0: float
    t1: float
    trace: object = None

    @property
    def seconds(self):
        return self.t1 - self.t0


@dataclasses.dataclass
class _View:
    window: _Window


def _span(name, t0, t1, parent, sid, **attrs):
    from repro.telemetry import Span

    return Span(name, t0, t1, parent, attrs, sid)


class _FakeTelemetry:
    def __init__(self, ring, dropped=0):
        self.ring, self.n_dropped = ring, dropped

    def spans(self):
        return list(self.ring)

    def dropped(self):
        return self.n_dropped


# window [1 s, 2 s] on the spans' clock: a search whose iteration holds a
# fit, a meta search with one step, one local step (sample, one dispatch
# with pack and wait, select) and a compile under the dispatch
S = 1_000_000_000
RING = [
    _span("stage.fit", S + 100, S + 300, 1, 2, rows=40),
    _span("meta.step", S + 320, S + 380, 3, 4, cands=48),
    _span("stage.meta", S + 300, S + 400, 1, 3),
    _span("local.sample", S + 400, S + 420, 5, 6, cands=192),
    _span("eval.pack", S + 420, S + 440, 7, 8),
    _span("jit.compile", S + 440, S + 500, 7, 9, fun="jit(forest_traverse)"),
    _span("eval.wait", S + 500, S + 700, 7, 10),
    _span("eval.dispatch", S + 420, S + 710, 5, 7, rows=192, padded=256),
    _span("local.select", S + 710, S + 730, 5, 11),
    _span("local.step", S + 400, S + 750, 1, 5, chains=4),
    _span("stage.iter", S + 50, S + 800, 0, 1),
    _span("noc.run", S + 10, S + 900, -1, 0),
]


def _view(ring, dropped=0, monkeypatch=None, trace=None):
    monkeypatch.setattr(ps, "telemetry", _FakeTelemetry(ring, dropped))
    return _View(_Window(t0=1.0, t1=2.0, trace=trace))


def test_readers_on_known_spans(monkeypatch):
    view = _view(RING, monkeypatch=monkeypatch)
    got = {m: harness.load_reader(m)(view) for m in METRICS}
    w = 1e9                                   # the window, in ns
    assert got["jit.compile_pct"] == pytest.approx(100 * 60 / w)
    assert got["surrogate.fit_pct"] == pytest.approx(100 * 200 / w)
    assert got["meta.search_pct"] == pytest.approx(100 * (40 + 60) / w)
    assert got["local.host_pct"] == pytest.approx(100 * (20 + 20) / w)
    assert got["eval.pad_pct"] == pytest.approx(100 * 64 / 256)
    # dispatch self: 290 - pack 20 - compile 60 - wait 200 = 10; pack 20
    assert got["eval.host_ms_per_dispatch"] == pytest.approx(30e-6)


def test_every_reader_reports_nothing_after_a_drop(monkeypatch):
    # a span that ended inside the window may have been pushed out
    view = _view(RING, dropped=3, monkeypatch=monkeypatch)
    for m in METRICS:
        assert harness.load_reader(m)(view) is None, m
    assert ps.idle_by_span(view) is None
    # spans dropped before the window began leave it readable
    early = [_span("noc.run", 10, 20, -1, 99)] + RING
    view = _view(early, dropped=3, monkeypatch=monkeypatch)
    assert all(harness.load_reader(m)(view) is not None for m in METRICS)


def test_every_reader_reports_nothing_without_telemetry(monkeypatch):
    monkeypatch.setattr(ps, "telemetry", None)
    view = _View(_Window(t0=1.0, t1=2.0))
    for m in METRICS:
        assert harness.load_reader(m)(view) is None, m


def test_innermost_segments():
    segs = ps.innermost_segments(RING)
    assert segs[0] == (S + 10, S + 50, "noc.run")
    assert (S + 440, S + 500, "jit.compile") in segs
    assert (S + 700, S + 710, "eval.dispatch") in segs
    assert (S + 750, S + 800, "stage.iter") in segs
    assert segs[-1] == (S + 800, S + 900, "noc.run")
    # the segments tile the top span without overlap
    assert all(a[1] == b[0] for a, b in zip(segs, segs[1:]))
    assert sum(e - s for s, e, _ in segs) == 890


def test_idle_by_span_on_a_synthetic_trace(monkeypatch):
    # trace clock: window [5000, 6000) ns is [1 s, 1 s + 1000 ns) on the
    # spans' clock; busy [5430, 5690) and [5850, 5950)
    busy = [(5430, 5690), (5850, 5950)]
    dev = tr.DeviceTrace(name="/device:TPU:0", ops=[], modules=[],
                         busy=busy)
    trace = tr.TraceSummary(window=(5000, 6000), devices=[dev],
                            host_spans=[])
    view = _view(RING, monkeypatch=monkeypatch, trace=trace)
    got = ps.idle_by_span(view)
    # idle [5000, 5430): midpoint S + 215 in stage.fit -> 430 ns;
    # [5690, 5850): midpoint S + 770 in stage.iter -> 160 ns;
    # [5950, 6000): midpoint S + 975, after noc.run -> 50 ns
    assert got == {"stage.fit": pytest.approx(430e-9),
                   "stage.iter": pytest.approx(160e-9),
                   ps.OUTSIDE: pytest.approx(50e-9)}
    # two devices: mean over them
    trace2 = tr.TraceSummary(window=(5000, 6000), devices=[dev, dev],
                             host_spans=[])
    assert ps.idle_by_span_of(trace2, S, RING) == got


def test_cpu_rehearsal_reports_the_span_metrics(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run(
        [sys.executable, str(CPU_CELL), "paper64-avg.stage4", "4294967311",
         "20", "--trace", "1"],
        capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"]
    m = line["metrics"]
    for name in METRICS:
        assert name in m, name
    assert 0 < m["eval.pad_pct"]["value"] < 100
    assert 0 < m["local.host_pct"]["value"] < 100
    assert m["eval.host_ms_per_dispatch"]["unit"] == "ms"
    assert m["eval.host_ms_per_dispatch"]["value"] > 0


def test_no_reader_pattern_matches_the_forest_traversals():
    """The surrogate's predict and the meta scorer have names of their own
    (``forest_traverse``, ``meta_score_moves``); no device reader's module
    or op pattern counts them as the walk or the min-plus kernel."""
    import importlib.util
    import re

    names = ("jit_forest_traverse", "jit_meta_score_moves",
             "forest_traverse", "meta_score_moves")
    seen = 0
    for path in sorted((BENCH_DIR / "metrics").glob("*.py")):
        spec = importlib.util.spec_from_file_location(
            "reader_" + path.stem.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        for attr in ("PATTERN", "KERNEL"):
            pat = getattr(mod, attr, None)
            if isinstance(pat, str):
                seen += 1
                assert not any(re.search(pat, n) for n in names), path.name
    assert seen >= 2
