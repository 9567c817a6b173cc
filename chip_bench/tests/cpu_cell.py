#!/usr/bin/env python3
"""Run one benchmark cell on the CPU, at 16 tiles, for the tests.

    python3 cpu_cell.py WORKLOAD SEED SECONDS [--trace 1] [--fault NAME]
                        [--bench-dir DIR]

Skips the harness's look for a chip and drives the rest of a run: set-up,
window, the check against the plain reference, the result line. The
Pallas kernels run in interpret mode (the one-chip cells); the four-chip
cell needs ``XLA_FLAGS=--xla_force_host_platform_device_count=4``. At 16
tiles the 256-tile cell's delta path is switched on by the evaluator's own
``delta="on"``. ``--fault`` breaks the timed path underneath (see
``FAULTS``), so a test can see ``correct`` come out false.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent / "src"))

SPEC16 = {"nx": 2, "ny": 4, "n_layers": 2, "n_cpu": 2, "n_llc": 4,
          "n_gpu": 10, "router_stages": 3, "max_hops": 12}


def _rows_fault(alter):
    """Patch every place the evaluator produces objective rows."""
    from repro.core.evaluate import Evaluator

    orig_aux, orig_tab = Evaluator.batch_aux, Evaluator._eval_from_tables

    def batch_aux(self, designs):
        objs, aux = orig_aux(self, designs)
        return alter(objs), aux

    def eval_from_tables(self, *a):
        return alter(orig_tab(self, *a))

    Evaluator.batch_aux = batch_aux
    Evaluator._eval_from_tables = eval_from_tables


def fault_altered():
    """An answer altered where it is produced: latency off by 0.1 %."""
    def alter(rows):
        rows = np.array(rows)
        rows[:, 2] *= 1.001
        return rows
    _rows_fault(alter)


def fault_half_batch():
    """Half of each batch left out: its rows are the other half's."""
    def alter(rows):
        rows = np.array(rows)
        h = len(rows) // 2
        if h:
            rows[h:2 * h] = rows[:h]
        return rows
    _rows_fault(alter)


def fault_stale():
    """A step that returns its state unchanged: every dispatch hands back
    the rows of the one before (the first returns its own)."""
    last = []

    def alter(rows):
        rows = np.array(rows)
        prev = last[0] if last else rows
        last[:] = [rows.copy()]
        return np.resize(prev, rows.shape)
    _rows_fault(alter)


def fault_no_exchange():
    """The exchange between chips left out: the sharded batch's rows are
    the first device's shard, repeated."""
    from repro.core.evaluate import Evaluator

    orig = Evaluator._build_spmd_fn

    def build(self):
        fn = orig(self)
        ndev = self.mesh.devices.size

        def wrapped(perms, adjs, f):
            objs, aux = fn(perms, adjs, f)
            o = np.asarray(objs)
            shard = o.shape[0] // ndev
            return np.tile(o[:shard], (ndev, 1)), aux
        return wrapped

    Evaluator._build_spmd_fn = build


FAULTS = {"altered": fault_altered, "half_batch": fault_half_batch,
          "stale": fault_stale, "no_exchange": fault_no_exchange}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("seed")
    ap.add_argument("seconds")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("--bench-dir", default=str(BENCH_DIR))
    args = ap.parse_args(argv)
    import run

    if args.fault:
        FAULTS[args.fault]()
    dist = "dist" in args.workload
    kw = {"delta": "on"} if "soc256" in args.workload else {}
    return run.run(["--workload", args.workload, "--seed", args.seed,
                    "--seconds", args.seconds, "--trace", args.trace],
                   require_chip=False, interpret=not dist,
                   spec_override=SPEC16, ev_kwargs=kw,
                   bench_dir=Path(args.bench_dir))


if __name__ == "__main__":
    sys.exit(main())
