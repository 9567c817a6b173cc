"""The check against the plain reference: the program passes it, and the
control, the reference computed one precision step below the evaluator's
float32 (bfloat16), fails it on the same designs."""

import dataclasses

import ml_dtypes
import numpy as np
import pytest

import harness
import reference as R
from cpu_cell import SPEC16
from test_data_driven import data_copy

CELLS = [("paper64-avg.stage4", 30, {}),
         ("soc256-moonlight-train.stage2", 10, {"delta": "on"})]


@pytest.fixture(scope="module", params=CELLS, ids=[c[0] for c in CELLS])
def window(request, tmp_path_factory):
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    name, seconds, kw = request.param
    bench_dir = data_copy(tmp_path_factory.mktemp("checkout"))
    cell = harness.load_cell(name, bench_dir)
    b = harness.Bench(cell, 3_000_000_019, interpret=True,
                      spec_override=SPEC16, ev_kwargs=kw)
    b.setup()
    win = b.window(seconds)
    return b, cell, win


def test_program_passes_control_fails(window):
    b, cell, win = window
    limits = cell.config["limits"]
    args = (b.spec_dict, b.f, b.problem.case, win, cell.config, b.seed)
    prog = harness.check_window(*args)
    ctrl = harness.check_window(*args, dtype=ml_dtypes.bfloat16)
    assert harness.verdict(prog, limits)[0], prog
    assert not harness.verdict(ctrl, limits)[0], ctrl
    for name in ("rows_gap", "front_gap", "phv_gap"):
        assert ctrl[name] > 3 * prog[name]


def test_reference_agrees_with_evaluator_on_random_designs():
    from repro.core.problem import SystemSpec, random_design
    from repro.noc import NocProblem

    spec = SystemSpec(**SPEC16)
    p = NocProblem(spec=spec, traffic="BFS", case="case5")
    rng = np.random.default_rng(5)
    ds = [spec.mesh_design()] + [random_design(spec, rng) for _ in range(8)]
    got = p.evaluator().batch(ds)
    want = R.objectives_many(R.Geometry(dataclasses.asdict(spec)),
                             p.traffic_matrix(),
                             [(d.perm, d.adj) for d in ds])
    assert R.rel_gap(got, want) < 1e-5


def test_hypervolume_by_hand():
    ref = np.array([2.0, 2.0])
    assert R.hypervolume(np.array([[1.0, 1.0]]), ref) == 1.0
    assert R.hypervolume(np.array([[0.0, 1.0], [1.0, 0.0]]), ref) == 3.0
    pts = np.array([[1.0, 1.0, 1.0], [0.5, 1.5, 1.5], [3.0, 0.0, 0.0]])
    assert R.hypervolume(pts, np.full(3, 2.0)) == pytest.approx(
        1.0 + 1.5 * 0.5 * 0.5 - 1.0 * 0.5 * 0.5)
