"""With the timed path broken underneath, ``correct`` comes out false.

Each fault a cell can have, planted in the program's evaluator by
``cpu_cell.py`` (never in the repository's code), on a CPU run at 16 tiles
that otherwise goes as a benchmark run does."""

import pytest

from test_data_driven import _cell, data_copy

CASES = [
    ("paper64-avg.stage4", 30, 1, "altered"),
    ("paper64-avg.stage4", 30, 1, "half_batch"),
    ("paper64-avg.stage4", 30, 1, "stale"),
    ("soc256-moonlight-train.stage2", 10, 1, "altered"),
    ("soc256-moonlight-train.stage2", 10, 1, "half_batch"),
    ("soc256-moonlight-train.stage2", 10, 1, "stale"),
    ("paper64-avg.dist4", 30, 4, "altered"),
    ("paper64-avg.dist4", 30, 4, "no_exchange"),
]


@pytest.mark.parametrize("workload,seconds,devices,fault", CASES)
def test_fault_makes_run_incorrect(tmp_path, workload, seconds, devices,
                                   fault):
    bench_dir = data_copy(tmp_path / "checkout")
    line, err = _cell(tmp_path, workload, seconds, "--fault", fault,
                      bench_dir=bench_dir, devices=devices)
    assert line["correct"] is False, err[-2000:]
    # it is a compared gap that fails, not a window too short for the search
    gaps = {k: v for k, v in line["checks"].items() if k != "first_front"}
    assert any(v["value"] > v["limit"] for v in gaps.values()), gaps
