"""Share of the window's wall time spent outside evaluator calls: the search
driver and its surrogate (``core/stage.py``, ``core/local_search.py``,
``core/forest.py``, ``core/fused.py``, ``core/pareto.py``). Host clock, from
the benchmark's spans around each evaluator call."""


def read(run):
    w = run.window
    if not w.spans or w.seconds <= 0:
        return None
    inside = sum(t1 - t0 for t0, t1, *_ in w.spans)
    return 100.0 * (1.0 - inside / w.seconds)
