"""Wall time inside evaluator calls per evaluation (``core/evaluate.py``),
host clock: device dispatch and wait, host delta tables, transfers."""


def read(run):
    w = run.window
    if not w.spans or w.evals <= 0:
        return None
    return 1e3 * sum(t1 - t0 for t0, t1, *_ in w.spans) / w.evals
