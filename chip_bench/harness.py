"""The chip benchmark's harness: one cell, one seed, one measured window.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``configs/<config>.json``  the deployment: tile spec, traffic source,
  objective case, how many rows the check compares, and the limits of the
  numbers it compares;
* ``traffic/<traffic>.json`` the search work the window drives: the
  registry optimizer, its config, the evaluations per search, the warm-up;
* ``metrics/<metric>.py``    a reader with ``read(run) -> float | None``
  (``None``: nothing to read in this run, the metric is left out).

A run builds the problem, warms every shape the window reaches, then runs
back-to-back fixed-budget searches through ``repro.noc.run`` until the
window closes, and checks what the timed path produced against the plain
reference in ``reference.py``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import time
import traceback
from pathlib import Path

import numpy as np

import reference as ref_mod

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class BenchError(RuntimeError):
    """A cell cannot be run as specified (missing file, unknown name)."""


# ------------------------------------------------------------- the data
def load_json(path: Path) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError as e:
        raise BenchError(f"missing file {path}") from e


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its configuration and mix."""

    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _applies(metric: dict, cell_name: str, e2e_names: set[str]) -> bool:
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, bench_dir: Path = BENCH_DIR) -> Cell:
    bench = load_json(bench_dir.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: "
                         f"{', '.join(sorted(cells))}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"workload {name!r} names unknown config "
                         f"{w['config']!r}")
    config = load_json(bench_dir.parent / configs[w["config"]]["file"])
    mix = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=e2e, per_layer=per_layer)


def load_reader(metric: str, bench_dir: Path = BENCH_DIR):
    path = bench_dir / "metrics" / f"{metric}.py"
    if not path.exists():
        raise BenchError(f"no reader for per-layer metric {metric!r} "
                         f"({path})")
    spec = importlib.util.spec_from_file_location(
        f"chip_bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def search_seed(seed: int, i: int, mix: dict) -> int:
    """Seed of the window's ``i``-th search (``i = -1``: the warm-up).

    The first search takes the mix's fixed ``first_search_seed``: its front
    is ``front_phv``, which then reads the same in every run of unchanged
    code (across seeds the first search's hypervolume differs by tens of
    percent). All other searches take their seeds from the run's
    ``--seed``."""
    if i == 0:
        return int(mix["first_search_seed"])
    return int(np.random.SeedSequence([int(seed), i + 1]).generate_state(1)[0])


# --------------------------------------------------------- compile events
class CompileMonitor:
    """Backend compiles and their seconds, from JAX's monitoring events."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **_):
        if event == self._COMPILE:
            self.compiles += 1
            self.compile_s += duration


# ----------------------------------------------------- the timed wrapper
class TimedEvaluator:
    """The evaluator as the window's searches see it.

    Covers the public methods the search drivers call. Each call gets a host
    span (and a profiler annotation in traced runs); a dispatch asked for
    after the deadline raises ``BudgetExhausted``, so the search in flight
    ends at the window's edge with its best-so-far. A seeded sample of the
    rows each dispatch returned is kept for the check.
    """

    def __init__(self, ev, rng: np.random.Generator, exhausted_exc):
        self._ev = ev
        self._rng = rng
        self._exc = exhausted_exc
        self.deadline: float | None = None
        self.annotate = False
        self.spans: list[tuple[float, float, int, int, str]] = []
        self.kept: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []

    def reset(self) -> None:
        self.spans.clear()
        self.kept.clear()

    @contextlib.contextmanager
    def _span(self, kind: str, dispatch: bool):
        if (dispatch and self.deadline is not None
                and time.perf_counter() >= self.deadline):
            raise self._exc("the measured window has closed")
        ev = self._ev
        n0, c0 = ev.n_evals, ev.n_calls
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(f"chip_bench.eval.{kind}")
        t0 = time.perf_counter()
        with ann:
            yield
        self.spans.append((t0, time.perf_counter(), ev.n_evals - n0,
                           ev.n_calls - c0, kind))

    def _keep(self, designs_fn, rows: np.ndarray) -> None:
        if len(rows) == 0:
            return
        j = int(self._rng.integers(len(rows)))   # one row per dispatch
        d = designs_fn(j)
        self.kept.append((np.array(d.perm), np.array(d.adj),
                          np.array(rows[j], dtype=np.float64)))

    def batch_aux(self, designs):
        with self._span("batch", bool(designs)):
            out = self._ev.batch_aux(designs)
        self._keep(lambda j: designs[j], out[0])
        return out

    def batch(self, designs):
        return self.batch_aux(designs)[0]

    def __call__(self, d):
        return self.batch([d])[0]

    def batch_moves(self, moves):
        ms = [moves] if not isinstance(moves, (list, tuple)) else list(moves)
        with self._span("moves", any(len(m) for m in ms)):
            rows = self._ev.batch_moves(moves)
        ms = [m for m in ms if len(m)]
        if ms:
            offs = np.cumsum([0] + [len(m) for m in ms])

            def design(j):
                k = int(np.searchsorted(offs, j, side="right") - 1)
                return ms[k].materialize(j - int(offs[k]))

            self._keep(design, rows)
        return rows

    def edp(self, d):
        with self._span("edp", True):
            return self._ev.edp(d)

    def note_accept(self, mv, j):
        with self._span("accept", False):
            return self._ev.note_accept(mv, j)

    def __getattr__(self, name):
        return getattr(self._ev, name)


# ------------------------------------------------------------- the run
@dataclasses.dataclass
class Window:
    """What one measured window did, for the metrics and the check."""

    t0: float
    t1: float
    evals: int
    calls: int
    results: list                      # RunResult per search, in order
    errors: list[str]
    spans: list                        # TimedEvaluator spans (one chip)
    kept: list                         # sampled (perm, adj, row)
    delta: dict
    compiles: int
    compile_s: float
    probe: list = dataclasses.field(default_factory=list)
    trace: object = None               # trace_reduce.TraceSummary

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Bench:
    """Set-up, window and check of one cell on the devices JAX sees."""

    def __init__(self, cell: Cell, seed: int, *, interpret: bool = False,
                 spec_override: dict | None = None,
                 ev_kwargs: dict | None = None):
        import jax

        from repro.noc.api import BudgetExhausted, NocProblem
        from repro.core.problem import SystemSpec

        self.cell = cell
        self.seed = int(seed)
        self.interpret = interpret
        cfg = cell.config
        spec = dict(spec_override or cfg["spec"])
        self.spec_dict = spec
        traffic = cfg["traffic"]
        if isinstance(traffic, list):
            traffic = tuple(traffic)
        self.problem = NocProblem(
            spec=SystemSpec(**spec), traffic=traffic, case=cfg["case"],
            backend=("pallas" if interpret and cell.mix["driver"]
                     == "stage_batch" else "auto"))
        self.f = self.problem.traffic_matrix()
        self.BudgetExhausted = BudgetExhausted
        self.monitor = CompileMonitor()
        self.devices = jax.devices()[:cell.chips]
        self.ev_kwargs = dict(ev_kwargs or {})
        self.ev = None
        self.timed = None

    def reseed(self, seed: int) -> None:
        """Draw the next window's later searches and checked rows from
        ``seed`` (the readings of many seeds share one set-up)."""
        self.seed = int(seed)
        if self.timed is not None:
            self.timed._rng = np.random.default_rng([self.seed, 2])

    # ---------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.core.problem import random_design, sample_neighbor_moves

        mix = self.cell.mix
        rng = np.random.default_rng([self.seed, 1])
        spec = self.problem.spec
        if mix["driver"] == "stage_batch":
            self.ev = self.problem.evaluator(interpret=self.interpret,
                                             **self.ev_kwargs)
            self.timed = TimedEvaluator(self.ev, np.random.default_rng(
                [self.seed, 2]), self.BudgetExhausted)
            warm_ev = self.timed
        else:
            from repro.core.evaluate import make_spmd_mesh, spmd_scope

            with spmd_scope(make_spmd_mesh()):
                warm_ev = self.problem.evaluator()
        top = warm_ev.max_batch or 256
        sizes = [1 << k for k in range(int(math.log2(top)) + 1)]
        designs = [random_design(spec, rng) for _ in range(top)]
        for b in sizes:
            warm_ev.batch(designs[:b])
        if getattr(warm_ev, "delta_on", False):
            for b in sizes:
                mv = sample_neighbor_moves(spec, spec.mesh_design(), rng,
                                           b // 2, b - b // 2)
                warm_ev.batch_moves(mv)
        self._search(-1, mix["warmup_evals"])
        if self.timed is not None:
            self.timed.reset()

    def _search(self, i: int, evals: int):
        from repro.noc import Budget, run

        mix = self.cell.mix
        seed = search_seed(self.seed, i, mix)
        if mix["driver"] == "stage_batch":
            budget = Budget(max_evals=self.ev.n_evals + evals, seed=seed)
            return run(self.problem, "stage_batch", budget=budget,
                       config=mix["config"], ev=self.timed)
        return run(self.problem, mix["driver"],
                   budget=Budget(max_evals=evals, seed=seed),
                   config=mix["config"])

    # ---------------------------------------------------------- window
    def window(self, seconds: float, trace_dir: str | None = None) -> Window:
        import jax

        mix = self.cell.mix
        ev = self.ev
        if self.timed is not None:
            self.timed.reset()
        n0 = (ev.n_evals, ev.n_calls) if ev is not None else (0, 0)
        delta0 = dict(ev.delta_stats) if ev is not None else {}
        comp0 = self.monitor.compiles
        comp_s0 = self.monitor.compile_s
        # Programs first compiled inside the window are not written to the
        # persistent cache, so every run of a cell pays for them alike.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            if self.timed is not None:
                self.timed.annotate = True
        results, errors = [], []
        with contextlib.ExitStack() as stack:
            if trace_dir is not None:
                stack.enter_context(
                    jax.profiler.TraceAnnotation("chip_bench.window"))
            t0 = time.perf_counter()
            deadline = t0 + seconds
            if self.timed is not None:
                self.timed.deadline = deadline
            i = 0
            while time.perf_counter() < deadline:
                try:
                    results.append(self._search(i, mix["evals_per_search"]))
                except Exception:   # noqa: BLE001 - counted, reported
                    errors.append(traceback.format_exc())
                    results.append(None)
                i += 1
            t1 = time.perf_counter()
        if trace_dir is not None:
            jax.profiler.stop_trace()
        if self.timed is not None:
            self.timed.deadline = None
            self.timed.annotate = False
        if ev is not None:
            evals, calls = ev.n_evals - n0[0], ev.n_calls - n0[1]
            delta = {k: ev.delta_stats[k] - delta0[k] for k in delta0}
            spans, kept = list(self.timed.spans), list(self.timed.kept)
        else:
            done = [r for r in results if r is not None]
            evals = sum(int(r.n_evals) for r in done)
            calls = sum(int(r.n_calls) for r in done)
            delta, spans, kept = {}, [], []
        win = Window(t0=t0, t1=t1, evals=evals, calls=calls,
                     results=results, errors=errors, spans=spans, kept=kept,
                     delta=delta, compiles=self.monitor.compiles - comp0,
                     compile_s=self.monitor.compile_s - comp_s0)
        if ev is None:
            self.probe_rows(win)
        return win

    def probe_rows(self, win: Window) -> None:
        """Coordinator cells own their evaluators, so the window's rows
        outside the returned fronts cannot be seen. Evaluate one batch of
        the size each worker dispatches (the fronts' designs and seeded
        neighbours of them) through the same sharded program the workers
        ran, and keep its rows for the check."""
        from repro.core.evaluate import make_spmd_mesh, spmd_scope
        from repro.core.problem import sample_neighbors

        cfg = self.cell.mix["config"]
        n = cfg["n_starts"] * (cfg["n_swaps"] + cfg["n_link_moves"])
        rng = np.random.default_rng([self.seed, 4])
        base = [d for r in win.results if completed(r) for d in r.designs]
        if not base:
            return
        designs = base[:n // 3]
        while len(designs) < n:
            d = base[int(rng.integers(len(base)))]
            nb = sample_neighbors(self.problem.spec, d, rng, 1, 1)
            designs.append(nb[int(rng.integers(len(nb)))] if nb else d)
        with spmd_scope(make_spmd_mesh()):
            ev = self.problem.evaluator()
        rows = ev.batch(designs)
        win.probe = [(np.array(d.perm), np.array(d.adj), r)
                     for d, r in zip(designs, rows)]

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self.ev = self.timed = None
        gc.collect()


# ------------------------------------------------------------ the check
def completed(res) -> bool:
    """The search ran to its own end (budget or convergence), not cut at
    the window's edge: only then does the driver report its diagnostics."""
    return res is not None and ("n_local_searches" in res.extra
                                or res.optimizer == "stage_dist")


def check_window(bench_spec: dict, f: np.ndarray, case: str, win: Window,
                 config: dict, seed: int, dtype=np.float64) -> dict:
    """Compare what the window produced with the plain reference.

    Returns ``{number: value}``, and under ``front_phv`` the reference's
    normalized hypervolume of the first search's front. With ``dtype``
    other than float64 the reference at that precision stands in for the
    program (the control): its rows are compared with the float64
    reference on the same designs.
    """
    geo = ref_mod.Geometry(bench_spec)
    chk = config["check"]
    rng = np.random.default_rng([int(seed), 3])
    mesh_perm, mesh_adj = geo.mesh()
    mesh_ref = ref_mod.objectives(geo, f, mesh_perm, mesh_adj)
    control = np.dtype(dtype) != np.float64

    def program_rows(designs, rows):
        if control:
            return ref_mod.objectives_many(geo, f, designs, dtype)
        return np.asarray(rows, dtype=np.float64)

    out: dict[str, float] = {}
    done = [r for r in win.results if r is not None]

    # 1. rows the timed path evaluated in the window: a seeded sample of
    #    the dispatches' rows (one chip) or of the fronts' rows, and the
    #    whole batch a coordinator cell probes its workers' program with
    pool = list(win.kept)
    if win.probe:
        pool += [(np.array(d.perm), np.array(d.adj), np.array(o))
                 for r in done for d, o in zip(r.designs, r.objs)]
    pick = rng.choice(len(pool), size=min(chk["window_rows"], len(pool)),
                      replace=False) if pool else []
    rows = list(win.probe) + [pool[j] for j in pick]
    designs = [(p, a) for p, a, _ in rows]
    want = ref_mod.objectives_many(geo, f, designs)
    out["rows_gap"] = (ref_mod.rel_gap(
        program_rows(designs, [o for _, _, o in rows]), want)
        if rows else float("inf"))

    # 2. the front of the window's first search
    first = win.results[0] if win.results else None
    out["first_front"] = (float(len(first.designs)) if completed(first)
                          else 0.0)
    if out["first_front"] > 0:
        fd = [(d.perm, d.adj) for d in first.designs]
        want = ref_mod.objectives_many(geo, f, fd)
        got = program_rows(fd, first.objs)
        out["front_gap"] = ref_mod.rel_gap(got, want)
        phv_ref = ref_mod.front_phv(want, mesh_ref, case)
        out["front_phv"] = phv_ref
        phv_got = (ref_mod.front_phv(got, ref_mod.objectives(
            geo, f, mesh_perm, mesh_adj, dtype), case)
            if control else float(first.phv()))
        out["phv_gap"] = (float(abs(phv_got - phv_ref) / phv_ref) if phv_ref > 0
                          else float("inf"))
    else:
        out["front_gap"] = out["phv_gap"] = float("inf")
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}). ``first_front`` must reach its
    limit from above; every other number must stay at or under its limit."""
    checks, ok = {}, True
    for name, lim in limits.items():
        v = numbers.get(name, float("inf"))
        good = v >= lim if name == "first_front" else v <= lim
        ok &= bool(good) and math.isfinite(v)
        checks[name] = {"value": v, "limit": lim}
    return ok, checks
