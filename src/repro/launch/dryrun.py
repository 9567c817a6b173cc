import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# (The two lines above MUST precede any other import: jax freezes the host
# platform device count at first initialization. Everything below is free.)
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"]
    )

"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For every (architecture x input-shape) cell, lower + compile the step
function (train_step for train shapes, prefill/serve_step for inference
shapes) against ShapeDtypeStruct inputs on BOTH production meshes:

    single-pod  (16, 16)      axes (data, model)          256 chips
    multi-pod   (2, 16, 16)   axes (pod, data, model)     512 chips

and record memory_analysis() (fits/doesn't), cost_analysis() (FLOPs/bytes),
and the parsed collective schedule to experiments/dryrun/<cell>.json.
Failures here (sharding mismatch, OOM at compile, unsupported collective)
are bugs in the framework. Usage:

    python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
    python -m repro.launch.dryrun ... --out DIR   # records elsewhere
"""

import argparse
import json
import time
import traceback

import jax
import numpy as np

from ..configs import (ARCH_NAMES, SHAPES, applicable, cell_status,
                       get_config, input_specs)
from ..dist import sharding as shd
from ..models.model import build
from ..train.optimizer import OptConfig
from ..train.train_step import make_decode_fn, make_prefill_fn, make_train_fns
from . import hlo
from .mesh import make_production_mesh

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def build_lowered(arch: str, shape_name: str, mesh, policy: shd.Policy,
                  cfg_overrides: dict | None = None):
    """Lower the cell's step function against ShapeDtypeStructs (no alloc)."""
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.scaled(**cfg_overrides)
    shape = SHAPES[shape_name]
    model = build(cfg)
    specs = input_specs(cfg, shape)

    if shape.kind == "train":
        init_state, jitted_step, _ = make_train_fns(
            model, mesh, policy, OptConfig())
        state_sds = jax.eval_shape(
            lambda: init_state(jax.random.PRNGKey(0)))
        step = jitted_step(state_sds, specs)
        return step.lower(state_sds, specs), cfg

    params_sds = model.abstract_params()
    if shape.kind == "prefill":
        fn = make_prefill_fn(model, mesh, policy)(params_sds, specs)
        return fn.lower(params_sds, specs), cfg

    # decode
    fn = make_decode_fn(model, mesh, policy)(
        params_sds, specs["cache"], specs["token"])
    return fn.lower(params_sds, specs["cache"], specs["token"]), cfg


def _memory_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # backend without memory analysis
        return {"error": str(e)}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if not out:
        out["repr"] = str(ma)
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             policy: shd.Policy | None = None,
             cfg_overrides: dict | None = None,
             save: bool = True, out_dir: str = OUT_DIR) -> dict:
    policy = policy or shd.default_policy_for(SHAPES[shape_name].kind)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": cell_status(arch, shape_name)}
    if not applicable(arch, shape_name):
        if save:
            _save(rec, out_dir)
        return rec
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        t0 = time.perf_counter()
        lowered, cfg = build_lowered(arch, shape_name, mesh, policy,
                                     cfg_overrides)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()

        cost = compiled.cost_analysis() or {}
        if isinstance(cost, (list, tuple)):  # older jax: list of one dict
            cost = cost[0] if cost else {}
        text = compiled.as_text()
        coll = hlo.parse_collectives(text)
        rec.update(
            status="ok",
            lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            flops=float(cost.get("flops", -1)),
            bytes_accessed=float(cost.get("bytes accessed", -1)),
            memory=_memory_dict(compiled),
            collectives=coll,
            wire_bytes=hlo.wire_bytes(coll),
            n_devices=int(np.prod(list(mesh.shape.values()))),
            hlo_chars=len(text),
        )
    except Exception as e:
        rec.update(status="FAIL", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    if save:
        _save(rec, out_dir)
    return rec


def _save(rec: dict, out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    with open(os.path.join(out_dir, name), "w") as fh:
        json.dump(rec, fh, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--out", default=OUT_DIR,
                    help="directory for the per-cell JSON records")
    args = ap.parse_args()

    meshes = [False, True]
    if args.multi_pod_only:
        meshes = [True]
    if args.single_pod_only:
        meshes = [False]

    cells: list[tuple[str, str]]
    if args.all:
        cells = [(a, s) for a in ARCH_NAMES for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch and --shape, or --all"
        cells = [(args.arch, args.shape)]

    n_fail = 0
    for arch, shape_name in cells:
        for mp in meshes:
            rec = run_cell(arch, shape_name, multi_pod=mp, out_dir=args.out)
            tag = "MULTI " if mp else "single"
            if rec["status"] == "ok":
                mem = rec["memory"].get("temp_size_in_bytes", 0) + \
                    rec["memory"].get("argument_size_in_bytes", 0)
                print(f"[{tag}] {arch:22s} {shape_name:12s} OK   "
                      f"lower {rec['lower_s']:6.1f}s compile {rec['compile_s']:6.1f}s  "
                      f"flops/dev {rec['flops']:.3e}  "
                      f"bytes/dev {mem/1e9:7.2f} GB  "
                      f"wire {rec['wire_bytes']/1e9:8.3f} GB", flush=True)
            elif rec["status"].startswith("skip"):
                print(f"[{tag}] {arch:22s} {shape_name:12s} SKIP ({rec['status']})",
                      flush=True)
            else:
                n_fail += 1
                print(f"[{tag}] {arch:22s} {shape_name:12s} FAIL {rec['error']}",
                      flush=True)
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")
    print("dry-run complete: all cells lowered + compiled")


if __name__ == "__main__":
    main()
