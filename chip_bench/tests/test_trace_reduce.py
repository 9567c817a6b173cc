"""The reduction from a profiler trace to what the metrics read."""

import json
from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def _summary():
    # window [0, 100); device busy [10, 30) and [25, 40) and [70, 80);
    # evaluator span [5, 45), the rest is the search driver.
    ops = [("%minplus.1 = f32[4,64,64]{2,1,0} custom-call()", 10, 30),
           ("%fusion.2 = f32[4,64,64]{2,1,0} fusion()", 25, 40),
           ("%while.3 = (s32[]) while()", 70, 80),
           ("%late = f32[1]{0} fusion()", 120, 130)]
    dev = tr.DeviceTrace(
        name="/device:TPU:0", ops=ops,
        modules=[("jit_minplus(1)", 10, 30), ("jit_evaluate_with_tables(2)",
                                              70, 80)],
        busy=tr._clip(tr._union([(s, e) for _, s, e in ops]), 0, 100))
    return tr.TraceSummary(window=(0, 100), devices=[dev],
                           host_spans=[(5, 45, "chip_bench.eval.batch")])


def test_union_and_clip():
    assert tr._union([(5, 9), (0, 3), (2, 4), (9, 12)]) == [(0, 4), (5, 12)]
    assert tr._clip([(0, 4), (5, 12)], 3, 10) == [(3, 4), (5, 10)]


def test_busy_modules_ops_gaps():
    s = _summary()
    assert s.devices[0].busy == [(10, 40), (70, 80)]
    assert s.busy_s_mean == pytest.approx(40e-9)
    assert s.window_s == pytest.approx(100e-9)
    assert s.module_ns(r"evaluate_with_tables") == 10
    assert [n for n, _ in s.op_events("minplus")] == [
        "%minplus.1 = f32[4,64,64]{2,1,0} custom-call()"]
    gaps = sorted(s.idle_gaps(), key=lambda g: -g[1])
    assert gaps == [("search driver", pytest.approx(30e-9)),
                    ("search driver", pytest.approx(20e-9)),
                    ("evaluator batch", pytest.approx(10e-9))]
    b = s.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] == pytest.approx(20e-9)


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5e by ``record_trace.py``."""
    exp = json.load(open(DATA / "small_trace" / "expected.json"))
    s = tr.reduce_dir(str(DATA / "small_trace"), n_devices=1)
    assert len(s.devices) == 1
    assert 0 < s.busy_s_mean < s.window_s
    assert len(s.host_spans) == len(exp["sizes"])
    # one run of the walk program per dispatch, apsp_iters min-plus calls
    runs = [m for m in s.devices[0].modules
            if "evaluate_with_tables" in m[0]
            and s.window[0] <= m[1] < s.window[1]]
    assert len(runs) == len(exp["sizes"])
    mp = s.op_events("minplus")
    assert len(mp) == exp["apsp_iters"] * len(exp["sizes"])
    import re
    shapes = sorted(int(re.search(r"f32\[(\d+),(\d+),", n).group(1))
                    for n, _ in mp)
    pads = sorted(b for b in exp["sizes"] for _ in range(exp["apsp_iters"]))
    assert shapes == pads
    driver = sum(g for w, g in s.idle_gaps() if w == "search driver")
    # the host sleeps between dispatches are device-idle driver time
    assert driver >= 0.8 * exp["sleep_s"] * (len(exp["sizes"]) + 1)
