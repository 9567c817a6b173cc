"""Reduce a JAX profiler trace of one window to what the metrics read.

The trace holds host planes (the benchmark's ``chip_bench.window`` and
``chip_bench.eval.<kind>`` annotations) and one plane per device
(``/device:TPU:<i>``), whose ``XLA Ops`` line has one event per device
operation and whose ``XLA Modules`` line has one event per program run. All
events share one clock. From them:

* device-busy time: the union of the op intervals inside the window, per
  device;
* device time per program (module) and per operation name;
* idle gaps: the stretches inside the window where a device runs nothing,
  each attributed to what the host was doing at its midpoint: inside an
  evaluator call, or in the search driver outside it;
* ``breakdown``: the ten device operations that took most time, and the ten
  longest idle gaps.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from collections import Counter
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
WINDOW = "chip_bench.window"
EVAL_PREFIX = "chip_bench.eval."
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")


def load_peaks(device_kind: str, path: Path | None = None) -> dict:
    """The peaks of ``device_kind``; a kind missing from the table is an
    error, never a default."""
    with open(path or BENCH_DIR / "peaks.json") as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {', '.join(sorted(table))})")
    return table[device_kind]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: list[tuple[str, int, int]]            # (HLO text, start, end)
    modules: list[tuple[str, int, int]]
    busy: list[tuple[int, int]]                # merged, clipped to window

    @property
    def busy_ns(self) -> int:
        return sum(e - s for s, e in self.busy)


@dataclasses.dataclass
class TraceSummary:
    window: tuple[int, int]
    devices: list[DeviceTrace]
    host_spans: list[tuple[int, int, str]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s_mean(self) -> float:
        if not self.devices:
            return 0.0
        return sum(d.busy_ns for d in self.devices) * 1e-9 / len(self.devices)

    def module_ns(self, pattern: str) -> int:
        """Device time of every run of the programs whose name matches
        ``pattern`` (a regular expression), summed over devices."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return sum(min(e, hi) - max(s, lo) for d in self.devices
                   for n, s, e in d.modules if rx.search(n) and e > lo
                   and s < hi)

    def op_events(self, pattern: str) -> list[tuple[str, int]]:
        """(HLO text, duration ns) of the device operations inside the
        window whose HLO text (the event's name) matches ``pattern``."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return [(n, e - s) for d in self.devices for n, s, e in d.ops
                if s >= lo and e <= hi and rx.search(n)]

    def idle_gaps(self) -> list[tuple[str, float]]:
        """(what the host was doing, seconds) of every idle stretch of every
        device inside the window."""
        lo, hi = self.window
        spans = sorted(self.host_spans)
        starts = [s for s, _, _ in spans]
        out = []
        import bisect

        for d in self.devices:
            edges = [lo] + [x for iv in d.busy for x in iv] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b <= a:
                    continue
                mid = (a + b) // 2
                k = bisect.bisect_right(starts, mid) - 1
                what = "search driver"
                if k >= 0 and spans[k][1] >= mid:
                    what = "evaluator " + spans[k][2][len(EVAL_PREFIX):]
                out.append((what, (b - a) * 1e-9))
        return out

    def breakdown(self) -> dict:
        """Top device operations, named ``<program>/<HLO op>`` (the
        program whose run contains the op), and the longest idle gaps."""
        import bisect

        tot: Counter = Counter()
        for d in self.devices:
            mods = sorted(d.modules, key=lambda m: m[1])
            starts = [m[1] for m in mods]
            for n, s, e in d.ops:
                k = bisect.bisect_right(starts, s) - 1
                prog = (mods[k][0].split("(")[0]
                        if k >= 0 and mods[k][2] >= e else "?")
                tot[f"{prog}/{n.split(' = ')[0]}"] += e - s
        ops = [[n, ns * 1e-9] for n, ns in tot.most_common(10)]
        gaps = sorted(self.idle_gaps(), key=lambda g: -g[1])[:10]
        return {"device_ops": ops, "idle_gaps": [[w, s] for w, s in gaps]}


def reduce_file(path: str, n_devices: int | None = None) -> TraceSummary:
    """Reduce one ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    import gzip

    from jax.profiler import ProfileData

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as fh:
        pd = ProfileData.from_serialized_xspace(fh.read())
    host_spans, window = [], None
    devices = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns))
                               for ev in line.events)
                elif line.name == "XLA Modules":
                    modules.extend((ev.name, int(ev.start_ns),
                                    int(ev.start_ns + ev.duration_ns))
                                   for ev in line.events)
            devices.append((int(m.group(2)), plane.name, ops, modules))
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (int(ev.start_ns),
                              int(ev.start_ns + ev.duration_ns))
                elif ev.name.startswith(EVAL_PREFIX):
                    host_spans.append((int(ev.start_ns),
                                       int(ev.start_ns + ev.duration_ns),
                                       ev.name))
    devices.sort()
    if n_devices is not None:
        devices = devices[:n_devices]
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    lo, hi = window
    devs = [DeviceTrace(name=name, ops=ops, modules=modules,
                        busy=_clip(_union([(s, e) for _, s, e in ops]),
                                   lo, hi))
            for _, name, ops, modules in devices]
    return TraceSummary(window=window, devices=devs, host_spans=host_spans)


def reduce_dir(trace_dir: str, n_devices: int | None = None) -> TraceSummary:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb*"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(files[-1], n_devices=n_devices)


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader gets: the window (counters, host
    spans, the reduced trace), the cell, the chip's peaks and the spec."""

    window: object
    cell: object
    peaks: dict | None
    spec: dict
