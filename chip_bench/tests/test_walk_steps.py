"""``walk.steps_pct``: the share of the path walk's step cap the walk ran,
read from the ``walk_steps`` and ``walk_cap`` attributes of the window's
``eval.dispatch`` spans. It sums them over the window's dispatches, and
reports nothing where there is no dispatch to read, where the dispatches
carry no such attributes (a program whose walk always runs to its cap), or
where the ring lost a span from inside the window."""

import dataclasses

import pytest

import harness
import program_spans as ps

S = 1_000_000_000


@dataclasses.dataclass
class _Window:
    t0: float
    t1: float


@dataclasses.dataclass
class _View:
    window: _Window


class _FakeTelemetry:
    def __init__(self, ring, dropped=0):
        self.ring, self.n_dropped = ring, dropped

    def spans(self):
        return list(self.ring)

    def dropped(self):
        return self.n_dropped


def _dispatch(sid, t0, **attrs):
    from repro.telemetry import Span

    return Span("eval.dispatch", S + t0, S + t0 + 50, -1, attrs, sid)


def _read(monkeypatch, ring, dropped=0):
    monkeypatch.setattr(ps, "telemetry", _FakeTelemetry(ring, dropped))
    return harness.load_reader("walk.steps_pct")(_View(_Window(1.0, 2.0)))


def test_sums_steps_over_caps_of_the_windows_dispatches(monkeypatch):
    ring = [
        _dispatch(1, 100, rows=192, padded=256, walk_steps=6, walk_cap=24),
        _dispatch(2, 200, rows=7, padded=8, walk_steps=9, walk_cap=48),
        # before the window: not counted
        _dispatch(3, -500, rows=8, padded=8, walk_steps=48, walk_cap=48),
    ]
    assert _read(monkeypatch, ring) == pytest.approx(100 * 15 / 72)


def test_reports_nothing_without_dispatches_or_attributes(monkeypatch):
    assert _read(monkeypatch, []) is None
    parent = [_dispatch(1, 100, rows=192, padded=256)]
    assert _read(monkeypatch, parent) is None
    full = [_dispatch(1, 100, rows=8, padded=8, walk_steps=9, walk_cap=48)]
    assert _read(monkeypatch, full, dropped=2) is None
    monkeypatch.setattr(ps, "telemetry", None)
    reader = harness.load_reader("walk.steps_pct")
    assert reader(_View(_Window(1.0, 2.0))) is None
