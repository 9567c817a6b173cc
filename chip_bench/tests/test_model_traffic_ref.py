"""The ``soc256-moonlight16b-train`` configuration: its model is the
published one, the program's traffic matrix equals the plain reference
``model_traffic_ref.py`` at the cell's own 256-tile spec, and the cell runs
end to end on the CPU at 16 tiles with its delta-path metrics read."""

import json

import numpy as np
import pytest

import model_traffic_ref as R
from test_data_driven import BENCH, BENCH_DIR, _cell, data_copy

CONFIG = "soc256-moonlight16b-train"
CELL = "soc256-moonlight16b-train.stage2"
CFG = json.load(open(BENCH_DIR / "configs" / f"{CONFIG}.json"))


def test_file_holds_the_published_config():
    assert {k: CFG[k] for k in R.PUBLISHED} == R.PUBLISHED
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert entry["source"] == R.SOURCE == CFG["source"]
    assert entry["reduced"] == CFG["reduced"] == []


def test_program_matrix_equals_reference_at_the_cell_spec():
    from repro.core.problem import SystemSpec
    from repro.noc import NocProblem

    problem = NocProblem(spec=SystemSpec(**CFG["spec"]),
                         traffic=CFG["traffic"], case=CFG["case"])
    got = problem.traffic_matrix()
    want = R.train_fwd(CFG["spec"])
    assert got.shape == (256, 256)
    assert R.rel_gap(got, want) <= 1e-12
    assert np.isclose(want.sum(), R.INTENSITY)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_cpu_rehearsal_of_the_cell(tmp_path, trace):
    bench_dir = data_copy(tmp_path / "checkout")
    line, err = _cell(tmp_path, CELL, 10, "--trace", trace,
                      bench_dir=bench_dir)
    assert line["correct"] is True, err[-2000:]
    assert line["failed"] == 0 and line["attempted"] >= 1
    if trace == "0":
        assert set(line["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
    else:
        m = line["metrics"]
        assert 0 < m["tables.host_pct"]["value"] < 100
        assert 0 <= m["delta.rebuild_pct"]["value"] <= 100
        assert m["eval.evals_per_dispatch"]["value"] > 0
        assert "jit.compiles_in_window" in m
