"""Evaluations per evaluator dispatch over the window: the evaluator's own
``n_evals`` / ``n_calls`` counters (summed over workers for ``stage_dist``).
Padding rows are not counted."""


def read(run):
    w = run.window
    if w.calls <= 0:
        return None
    return w.evals / w.calls
