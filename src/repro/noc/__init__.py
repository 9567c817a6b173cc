"""``repro.noc`` — the unified NoC optimization API (DESIGN.md §7).

Every optimizer in the repo (MOO-STAGE single/multi-start, AMOSA, NSGA-II,
PHV-greedy local search, PCBB) runs through one serializable boundary::

    from repro.noc import Budget, NocProblem, run, named_spec

    problem = NocProblem(spec=named_spec("16"), traffic="BFS", case="case3")
    result = run(problem, "stage", budget=Budget(max_evals=2000, seed=0))
    result.save("run.json")           # JSON round trip, resume/compare later

CLI: ``python -m repro.noc run|compare|agnostic`` (see repro.noc.cli).
"""

from .api import (Budget, BudgetExhausted, NocProblem, RunRecorder,
                  RunResult, design_from_json, design_to_json, named_spec,
                  run)
from .optimizers import (OPTIMIZERS, AmosaConfig, LocalConfig, Nsga2Config,
                         OptimizerEntry, PcbbConfig, StageBatchConfig,
                         StageConfig, StageDistConfig, get_optimizer,
                         make_config, optimizer_names, register)
# Re-exported so the agnostic study is reachable from the unified surface
# (repro.core.agnostic imports repro.noc lazily inside functions — no cycle).
from repro.core.agnostic import (OptimizeBudget, optimize_for_traffic,
                                 run_agnostic_study, summarize, thermal_study)

__all__ = [
    "AmosaConfig", "Budget", "BudgetExhausted",
    "LocalConfig", "NocProblem", "Nsga2Config", "OPTIMIZERS",
    "OptimizeBudget", "OptimizerEntry", "PcbbConfig", "RunRecorder",
    "RunResult", "StageBatchConfig", "StageConfig", "StageDistConfig",
    "design_from_json",
    "design_to_json", "get_optimizer", "make_config", "named_spec",
    "optimize_for_traffic", "optimizer_names", "register",
    "run", "run_agnostic_study", "summarize", "thermal_study",
]
