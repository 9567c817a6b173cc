"""Spans and counters of the program: where a search's time goes.

The program's one tracing system (DESIGN.md §14). A span times one
phase of the work::

    with telemetry.span("eval.dispatch", rows=b, padded=pad):
        ...

and records ``Span(name, t0_ns, t1_ns, parent, attrs, sid)`` on
``time.perf_counter_ns()``, where ``parent`` is the ``sid`` of the span
that was open in the same thread when it began (``-1`` at top level) and
``attrs`` holds small integer counts (or a short label, such as
``tables.build``'s ``why``). Each span also enters
``jax.profiler.TraceAnnotation("repro.<name>", **attrs)``, so inside a
profiler session it lands on the host plane of the trace, on the device
ops' clock.

Recording is always on. Spans sit at phase granularity (a few thousand in a
50 s search window at 64 tiles) and go into a ring of the last
``CAPACITY`` spans; per-name totals since process start (count, seconds,
self seconds, attribute sums) are kept beside it. :func:`spans`,
:func:`totals` and :func:`dropped` read them.

On import one ``jax.monitoring`` listener is registered (once per process;
it starts no JAX backend). It turns JAX's compile events into spans under
whatever span is open in that thread: ``jit.compile`` for the backend
compile, ``jit.lower`` for tracing to a jaxpr and lowering to MLIR, each
over ``[now - duration, now]`` with attribute ``fun`` (the event's function
name). It counts the persistent compilation cache's hits and misses as
``jit.cache_hit`` and ``jit.cache_miss``.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

#: spans the ring keeps; older ones are pushed out (see :func:`dropped`)
CAPACITY = 1 << 16

_DURATION_SPANS = {
    "/jax/core/compile/backend_compile_duration": "jit.compile",
    "/jax/core/compile/jaxpr_trace_duration": "jit.lower",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
}
_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "jit.cache_hit",
    "/jax/compilation_cache/cache_misses": "jit.cache_miss",
}


class Span(NamedTuple):
    name: str
    t0_ns: int
    t1_ns: int
    parent: int          # sid of the enclosing span, -1 at top level
    attrs: dict
    sid: int


class _Frame:
    """An open span: its id, start, and the union of its children's
    intervals so far (children end in time order, so a merge from the back
    suffices; compile spans of nested traces overlap each other)."""

    __slots__ = ("sid", "t0", "cover", "covered")

    def __init__(self, sid: int, t0: int):
        self.sid = sid
        self.t0 = t0
        self.cover: list[list[int]] = []
        self.covered = 0

    def add_child(self, s: int, e: int) -> None:
        s = max(s, self.t0)
        if e <= s:
            return
        new = e - s
        lo, hi = s, e
        cov = self.cover
        while cov and cov[-1][1] > s:
            ps, pe = cov.pop()
            new -= max(0, min(pe, e) - max(ps, s))
            lo, hi = min(lo, ps), max(hi, pe)
        cov.append([lo, hi])
        self.covered += new


_ring: collections.deque = collections.deque(maxlen=CAPACITY)
_totals: dict[str, list] = {}
_new_tuple = tuple.__new__
_lock = threading.Lock()
_ids = itertools.count()
_local = threading.local()
_n_recorded = 0


def _stack() -> list[_Frame]:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def _total(name: str) -> list:
    """[count, ns, self ns, attribute sums] of ``name``; under ``_lock``."""
    tot = _totals.get(name)
    if tot is None:
        tot = _totals[name] = [0, 0, 0, {}]
    return tot


def _record(name: str, t0: int, t1: int, parent: _Frame | None,
            attrs: dict, sid: int, self_ns: int) -> None:
    global _n_recorded
    if parent is not None:
        parent.add_child(t0, t1)
    # tuple.__new__ skips the NamedTuple constructor's argument parsing
    sp = _new_tuple(Span, (name, t0, t1,
                           parent.sid if parent is not None else -1,
                           attrs, sid))
    with _lock:
        _ring.append(sp)
        _n_recorded += 1
        tot = _total(name)
        tot[0] += 1
        tot[1] += t1 - t0
        tot[2] += self_ns
        sums = tot[3]
        for k, v in attrs.items():
            if isinstance(v, int):
                sums[k] = sums.get(k, 0) + v


class span:
    """Context manager timing one phase; see the module docstring.
    ``attrs`` may be added to (``s.attrs["cands"] = n``) before the span
    ends; the profiler annotation carries those given at entry."""

    __slots__ = ("name", "attrs", "_frame", "_parent", "_ann")

    def __init__(self, name: str, **attrs: int):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        st = _stack()
        self._parent = st[-1] if st else None
        self._ann = TraceAnnotation(f"repro.{self.name}", **self.attrs)
        self._ann.__enter__()
        self._frame = _Frame(next(_ids), time.perf_counter_ns())
        st.append(self._frame)
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        fr = self._frame
        st = _stack()
        if st and st[-1] is fr:
            st.pop()
        self._ann.__exit__(None, None, None)
        _record(self.name, fr.t0, t1, self._parent, self.attrs, fr.sid,
                t1 - fr.t0 - fr.covered)


def spans() -> list[Span]:
    """The ring, oldest first (in the order spans ended)."""
    with _lock:
        return list(_ring)


def totals() -> dict[str, dict]:
    """Per-name aggregates since process start: ``{"count", "seconds",
    "self_s", "attrs"}`` (``attrs``: sums of the integer attributes). The
    compile-cache counters have a count only."""
    with _lock:
        return {k: {"count": n, "seconds": ns * 1e-9, "self_s": self_ns * 1e-9,
                    "attrs": dict(sums)}
                for k, (n, ns, self_ns, sums) in _totals.items()}


def dropped() -> int:
    """How many spans the ring has pushed out since process start."""
    with _lock:
        return _n_recorded - len(_ring)


# ---------------------------------------------------- JAX compile events
def _on_duration(event: str, duration: float, **kw) -> None:
    name = _DURATION_SPANS.get(event)
    if name is None:
        return
    t1 = time.perf_counter_ns()
    t0 = t1 - int(duration * 1e9)
    st = _stack()
    parent = st[-1] if st else None
    if parent is not None:
        # JAX times the event on another clock; keep it inside its parent
        t0 = min(max(t0, parent.t0), t1)
    _record(name, t0, t1, parent, {"fun": str(kw.get("fun_name", ""))},
            next(_ids), t1 - t0)


def _on_event(event: str, **_) -> None:
    name = _COUNTERS.get(event)
    if name is None:
        return
    with _lock:
        _total(name)[0] += 1


_installed = False


def _install() -> None:
    global _installed
    if _installed:
        return
    import jax.monitoring as mon

    mon.register_event_duration_secs_listener(_on_duration)
    mon.register_event_listener(_on_event)
    _installed = True


_install()
