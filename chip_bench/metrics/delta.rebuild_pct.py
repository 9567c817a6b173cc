"""Share of link-move candidates whose host routing tables were rebuilt
from scratch (``core/routing.py`` ``host_tables``) instead of updated by
``delta_link_move``: (fallbacks + table-cache misses) / (deltas +
fallbacks), from ``Evaluator.delta_stats`` over the window."""


def read(run):
    d = run.window.delta
    moves = d.get("delta", 0) + d.get("fallback", 0)
    if moves <= 0:
        return None
    return 100.0 * (d["fallback"] + d["table_misses"]) / moves
