"""The program's own spans (``repro.telemetry``) over one measured window.

The program records a span around each phase of a search (``noc.run``,
``stage.iter``, ``stage.fit``, ``meta.step``, ``local.step``,
``eval.dispatch``, ``jit.compile``, ...; PERF.md section 3 lists them) on
``time.perf_counter_ns()``, the clock the harness reads the window's ``t0``
and ``t1`` on. The readers of the per-layer metrics that rest on them keep
the spans that start inside ``[t0, t1]`` and sum self times: a span's
duration minus the part of it its child spans cover.

Every function returns ``None`` where there is nothing to read: a program
without ``repro.telemetry``, or a ring of spans that lost a span from
inside the window.
"""

from __future__ import annotations

import bisect

try:
    from repro import telemetry
except ImportError:          # a program that records no spans
    telemetry = None


def _window_ns(run) -> tuple[int, int]:
    w = run.window
    return int(w.t0 * 1e9), int(w.t1 * 1e9)


def _ring(run):
    """All recorded spans, or ``None`` if a span that ended inside the
    window may have been pushed out of the ring (the ring is in the order
    spans ended, so a dropped span ended before its oldest span)."""
    if telemetry is None:
        return None
    lo, _ = _window_ns(run)
    ring = telemetry.spans()
    if telemetry.dropped() and (not ring or ring[0].t1_ns >= lo):
        return None
    return ring


def window_spans(run):
    """The spans that start inside the window, or ``None``."""
    ring = _ring(run)
    if ring is None:
        return None
    lo, hi = _window_ns(run)
    return [s for s in ring if lo <= s.t0_ns <= hi]


def union_ns(intervals) -> int:
    tot, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            tot += e - s
            end = e
        elif e > end:
            tot += e - end
            end = e
    return tot


def self_ns(spans, names) -> int:
    """Summed self time of the spans named in ``names``."""
    kids: dict[int, list] = {}
    for s in spans:
        kids.setdefault(s.parent, []).append((s.t0_ns, s.t1_ns))
    tot = 0
    for s in spans:
        if s.name in names:
            inner = [(max(a, s.t0_ns), min(b, s.t1_ns))
                     for a, b in kids.get(s.sid, ()) if b > s.t0_ns
                     and a < s.t1_ns]
            tot += s.t1_ns - s.t0_ns - union_ns(inner)
    return tot


def self_pct(run, names) -> float | None:
    """Self time of ``names`` as a share of the window, in %."""
    spans = window_spans(run)
    if spans is None or run.window.seconds <= 0:
        return None
    return 100.0 * self_ns(spans, names) * 1e-9 / run.window.seconds


def union_pct(run, names) -> float | None:
    """The union of the intervals of the spans named in ``names`` as a
    share of the window, in % (nested compile spans overlap)."""
    spans = window_spans(run)
    if spans is None or run.window.seconds <= 0:
        return None
    ns = union_ns([(s.t0_ns, s.t1_ns) for s in spans if s.name in names])
    return 100.0 * ns * 1e-9 / run.window.seconds


def innermost_segments(spans) -> list[tuple[int, int, str]]:
    """Split the time the spans cover into ``(start, end, name)`` segments,
    each named by the innermost span open there (spans of one thread nest;
    a span that outlives its enclosing one is cut at its end)."""
    segs: list[tuple[int, int, str]] = []
    stack: list[tuple[int, str]] = []
    t = None
    for s in sorted(spans, key=lambda s: (s.t0_ns, -s.t1_ns)):
        while stack and stack[-1][0] <= s.t0_ns:
            end, name = stack.pop()
            if end > t:
                segs.append((t, end, name))
                t = end
        if stack and s.t0_ns > t:
            segs.append((t, s.t0_ns, stack[-1][1]))
        t = s.t0_ns if t is None else max(t, s.t0_ns)
        end = min(s.t1_ns, stack[-1][0]) if stack else s.t1_ns
        stack.append((end, s.name))
    while stack:
        end, name = stack.pop()
        if end > t:
            segs.append((t, end, name))
            t = end
    return segs


OUTSIDE = "(no span)"


def idle_by_span(run) -> dict[str, float] | None:
    """Device idle seconds of the traced window by the innermost program
    span open at each idle interval's midpoint, mean over the devices.

    The two clocks are anchored by the window: the trace's
    ``chip_bench.window`` annotation begins right before the harness reads
    ``t0``. Idle time outside every span is under ``OUTSIDE``."""
    trace = run.window.trace
    ring = _ring(run)
    if trace is None or not trace.devices or ring is None:
        return None
    return idle_by_span_of(trace, _window_ns(run)[0], ring)


def idle_by_span_of(trace, t0_ns: int, spans) -> dict[str, float]:
    """``idle_by_span`` of a reduced trace whose window starts at
    ``t0_ns`` on the spans' clock."""
    lo, hi = trace.window
    shift = t0_ns - lo
    segs = innermost_segments(spans)
    starts = [a for a, _, _ in segs]
    out: dict[str, float] = {}
    for d in trace.devices:
        edges = [lo] + [x for iv in d.busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) // 2 + shift
            k = bisect.bisect_right(starts, mid) - 1
            name = segs[k][2] if k >= 0 and segs[k][1] > mid else OUTSIDE
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    n = len(trace.devices)
    return {k: v / n for k, v in sorted(out.items(), key=lambda kv: -kv[1])}
