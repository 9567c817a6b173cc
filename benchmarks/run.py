"""Benchmark aggregator — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV. Reduced budgets keep the full
suite tractable on the CPU container; each module's main() accepts
reduced=False for the full-budget variants reported in EXPERIMENTS.md."""

from __future__ import annotations

import sys
import time


def main() -> int:
    reduced = "--full" not in sys.argv
    # Routing backend for the search benchmarks (fig4/fig8/table2):
    # --backend=jnp|pallas|auto. Validated up front so a typo fails fast
    # instead of surfacing as per-module ERROR rows.
    # --only=<module>[,<module>...] restricts the run (e.g. --only=kernel_bench).
    backend = "auto"
    only: set[str] | None = None
    for arg in sys.argv[1:]:
        if arg.startswith("--backend="):
            backend = arg.split("=", 1)[1]
        if arg.startswith("--only="):
            only = set(arg.split("=", 1)[1].split(","))
    from repro import compile_cache
    from repro.core import routing
    routing.resolve_backend(backend)  # raises ValueError on typos
    compile_cache.enable()
    print(f"# repro benchmarks (reduced={reduced}, backend={backend})")
    print("name,us_per_call,derived")
    t0 = time.perf_counter()

    from . import (fig4_throughput_model, fig6_convergence, fig8_eval_error,
                   fig9_agnostic, fig10_thermal, kernel_bench, noc_cli,
                   roofline_bench, table2_speedup)

    takes_backend = (fig4_throughput_model, fig8_eval_error, table2_speedup)
    mods = [kernel_bench, noc_cli, fig4_throughput_model, fig6_convergence,
            table2_speedup, fig8_eval_error, fig9_agnostic,
            fig10_thermal, roofline_bench]
    names = {m.__name__.rsplit(".", 1)[-1] for m in mods}
    if only is not None and (unknown := only - names):
        raise SystemExit(f"--only names unknown modules: {sorted(unknown)}; "
                         f"available: {sorted(names)}")
    failed = []
    for mod in mods:
        name = mod.__name__.rsplit(".", 1)[-1]
        if only is not None and name not in only:
            continue
        t = time.perf_counter()
        kwargs = {"backend": backend} if mod in takes_backend else {}
        try:
            mod.main(reduced=reduced, **kwargs)
        except Exception as e:  # keep the suite running; fail at the end
            print(f"{name},0,ERROR:{type(e).__name__}:{e}")
            failed.append(name)
        print(f"# {name} took {time.perf_counter()-t:.1f}s", flush=True)

    print(f"# total {time.perf_counter()-t0:.1f}s")
    if failed:
        print(f"# FAILED modules: {','.join(failed)}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
