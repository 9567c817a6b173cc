"""Device time of the program that walks the routing tables and computes
the objectives, per evaluation in the traced window, summed over devices.
One chip: ``jit_evaluate_with_tables`` (the walk after APSP). Four chips
under ``stage_dist``/``spmd``: the one shard_map program
``jit_batch_pipeline``, which holds cost build, APSP and walk together."""

PATTERN = r"evaluate_with_tables|batch_pipeline"


def read(run):
    w = run.window
    if w.trace is None or not w.trace.devices or w.evals <= 0:
        return None
    ns = w.trace.module_ns(PATTERN)
    if ns <= 0:
        return None
    return ns * 1e-6 / w.evals
