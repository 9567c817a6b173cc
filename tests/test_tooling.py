"""Benchmark-regression gate: the pure comparison logic of
benchmarks/check_regression.py (the CLI wraps this)."""

import importlib

check_regression = importlib.import_module("benchmarks.check_regression")
compare = check_regression.compare


def test_compare_passes_within_threshold():
    base = {"a_us": 100.0, "b_us": 50.0}
    fresh = {"a_us": 120.0, "b_us": 74.0}
    assert compare(base, fresh, 1.5, tracked=("a_us", "b_us")) == []


def test_compare_flags_slowdown():
    base = {"a_us": 100.0}
    fresh = {"a_us": 151.0}
    problems = compare(base, fresh, 1.5, tracked=("a_us",))
    assert len(problems) == 1 and "a_us" in problems[0]


def test_compare_missing_fresh_key_fails_and_new_baseline_key_skips():
    base = {"a_us": 100.0}
    fresh = {}
    assert len(compare(base, fresh, 1.5, tracked=("a_us",))) == 1
    # tracked key absent from the baseline (older baseline) is skipped
    assert compare({}, {"a_us": 1e9}, 1.5, tracked=("a_us",)) == []


def test_tracked_keys_exist_in_committed_baseline():
    import json
    with open(check_regression.BASELINE) as fh:
        baseline = json.load(fh)
    for key in check_regression.TRACKED:
        assert key in baseline, key


def test_benchmark_run_exits_nonzero_when_a_module_errors(monkeypatch, capsys):
    """A module that raises still gets its ERROR row, and the aggregate run
    then exits nonzero instead of reporting success."""
    from repro import compile_cache

    run = importlib.import_module("benchmarks.run")
    noc_cli = importlib.import_module("benchmarks.noc_cli")

    def boom(**_):
        raise RuntimeError("kernel refused")

    monkeypatch.setattr(noc_cli, "main", boom)
    monkeypatch.setattr(compile_cache, "enable", lambda: "")
    monkeypatch.setattr("sys.argv", ["run", "--only=noc_cli"])
    assert run.main() == 1
    out = capsys.readouterr().out
    assert "noc_cli,0,ERROR:RuntimeError:kernel refused" in out
    assert "FAILED modules: noc_cli" in out


def test_compile_cache_placement(monkeypatch):
    """The environment variable wins untouched; without it the cache sits
    at the fixed in-checkout path."""
    import os

    import jax

    from repro import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
