"""Dry-run machinery: HLO collective parsing units + one real multi-pod cell
lowered in a subprocess (the 512-device env must not leak into this
process's JAX)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.launch import hlo


def test_parse_collectives_shapes_and_kinds():
    text = """
  %ar.1 = f32[1024,16]{1,0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag.2 = bf16[64,512]{1,0} all-gather(%p1), replica_groups=[2,8]<=[16], dimensions={0}
  %rs.3 = f32[128]{0} reduce-scatter(%p2), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %cp.4 = u32[32]{0} collective-permute(%p3), source_target_pairs={{0,1},{1,0}}
  %a2a.5 = s8[256,4]{1,0} all-to-all(%p4), replica_groups=[4,4]<=[16]
"""
    c = hlo.parse_collectives(text)
    assert c["all-reduce"]["count"] == 1
    assert c["all-reduce"]["result_bytes"] == 1024 * 16 * 4
    assert c["all-reduce"]["wire_bytes"] == 2 * 1024 * 16 * 4
    assert c["all-gather"]["result_bytes"] == 64 * 512 * 2
    assert c["all-gather"]["wire_bytes"] == 64 * 512 * 2
    # reduce-scatter: operand = result x group size (8)
    assert c["reduce-scatter"]["wire_bytes"] == 128 * 4 * 8
    assert c["collective-permute"]["count"] == 1
    assert c["all-to-all"]["result_bytes"] == 256 * 4
    assert hlo.wire_bytes(c) > 0


def test_parse_ignores_non_collectives():
    text = "%dot.1 = f32[128,128]{1,0} dot(%a, %b), lhs_contracting_dims={1}"
    c = hlo.parse_collectives(text)
    assert hlo.wire_bytes(c) == 0


@pytest.mark.slow
def test_dryrun_cell_subprocess(tmp_path):
    """One real cell on both production meshes, via `python -m` exactly as
    the deliverable specifies. whisper-base compiles fastest."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "whisper-base", "--shape", "decode_32k",
         "--out", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "OK" in proc.stdout
    # Artifacts recorded for both meshes, outside the checkout's records.
    for mesh in ("pod16x16", "pod2x16x16"):
        path = os.path.join(tmp_path, f"whisper-base__decode_32k__{mesh}.json")
        assert os.path.exists(path)
        rec = json.load(open(path))
        assert rec["status"] == "ok"
        assert rec["memory"]["temp_size_in_bytes"] > 0


def test_dryrun_artifacts_complete_and_green():
    """The full sweep (run via `python -m repro.launch.dryrun --all`) must
    have produced one artifact per (arch x shape x mesh) cell, all ok/skip."""
    base = os.path.join("experiments", "dryrun")
    if not os.path.isdir(base) or len(os.listdir(base)) < 80:
        pytest.skip("full sweep artifacts not present (run dryrun --all)")
    from repro.configs import ARCH_NAMES, SHAPES
    n_ok = n_skip = 0
    for arch in ARCH_NAMES:
        for shape in SHAPES:
            for mesh in ("pod16x16", "pod2x16x16"):
                path = os.path.join(base, f"{arch}__{shape}__{mesh}.json")
                assert os.path.exists(path), f"missing {path}"
                rec = json.load(open(path))
                assert rec["status"] == "ok" or rec["status"].startswith(
                    "skip"), f"{path}: {rec['status']}"
                n_ok += rec["status"] == "ok"
                n_skip += rec["status"].startswith("skip")
    assert n_ok >= 64 and n_ok + n_skip == 80
