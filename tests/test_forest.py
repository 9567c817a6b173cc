"""Flat struct-of-arrays forest: golden equivalence against the recursive
traversal (``predict_reference``), degenerate fits, backends, and the
batched feature extractor.

Property tests need ``hypothesis``; without it they are skipped and the
unit tests still run (same pattern as test_pareto)."""

import numpy as np
import pytest

from repro.core import random_design, spec_tiny
from repro.core.features import (FEATURE_NAMES, design_features,
                                 design_features_batch)
from repro.core.forest import RegressionForest, resolve_forest_backend

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - property tests skip without it
    st = None


def _fit(n=200, f=5, seed=0, **kw):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-1, 1, size=(n, f))
    y = x[:, 0] * 2 + np.sin(3 * x[:, 1]) + 0.1 * rng.normal(size=n)
    return RegressionForest(seed=seed, **kw).fit(x, y), rng


# ------------------------------------------------------------------ golden
def test_flat_predict_bit_equal_reference():
    model, rng = _fit(n=400, f=7, n_trees=12, max_depth=7)
    xq = rng.uniform(-1.5, 1.5, size=(513, 7))  # odd batch, extrapolation
    ref = model.predict_reference(xq)
    assert np.array_equal(model.predict(xq, backend="numpy"), ref)


def test_flat_predict_bit_equal_both_batch_layouts():
    # The numpy path switches layout at 1024 samples — check both sides.
    model, rng = _fit(n=300, f=4, n_trees=8)
    xq = rng.uniform(-1, 1, size=(1500, 4))
    ref = model.predict_reference(xq)
    assert np.array_equal(model.predict(xq[:64], backend="numpy"), ref[:64])
    assert np.array_equal(model.predict(xq, backend="numpy"), ref)


def test_jnp_predict_close_to_reference():
    model, rng = _fit(n=300, f=6, n_trees=10)
    xq = rng.uniform(-1, 1, size=(200, 6))
    ref = model.predict_reference(xq)
    out = model.predict(xq, backend="jnp")
    # f32 traversal: tiny numeric drift; a threshold-rounding branch flip
    # would show up as an O(leaf-gap) outlier.
    assert np.allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_single_node_trees():
    model, rng = _fit(n=100, f=3, n_trees=5, max_depth=0)
    xq = rng.uniform(-1, 1, size=(17, 3))
    ref = model.predict_reference(xq)
    assert np.array_equal(model.predict(xq, backend="numpy"), ref)
    assert model._flat["depth"] == 0
    assert np.allclose(model.predict(xq, backend="jnp"), ref, rtol=1e-6)


def test_constant_y_degenerate_fit():
    rng = np.random.default_rng(0)
    x = rng.uniform(size=(50, 4))
    model = RegressionForest(n_trees=6, seed=1).fit(x, np.full(50, 3.25))
    xq = rng.uniform(size=(9, 4))
    assert np.array_equal(model.predict(xq), np.full(9, 3.25))
    assert np.array_equal(model.predict_reference(xq), np.full(9, 3.25))


def test_backend_validation_and_resolution():
    with pytest.raises(ValueError):
        RegressionForest(backend="bogus")
    with pytest.raises(ValueError):
        resolve_forest_backend("bogus")
    assert resolve_forest_backend("numpy") == "numpy"
    assert resolve_forest_backend("jnp") == "jnp"
    assert resolve_forest_backend("auto", batch=4096) in ("numpy", "jnp")
    assert RegressionForest(backend="jnp").backend == "jnp"


def test_single_sample_and_1d_input():
    model, rng = _fit()
    xq = rng.uniform(-1, 1, size=5)
    a = model.predict(xq)           # 1-D input is promoted like before
    b = model.predict_reference(xq)
    assert a.shape == (1,) and np.array_equal(a, b)


# -------------------------------------------------------------- properties
def given_forest_cases(max_examples):
    """Property decorator when hypothesis is available, skip otherwise."""
    def deco(fn):
        if st is None:
            @pytest.mark.skip(reason="hypothesis not installed")
            def stub():
                pass
            stub.__name__ = fn.__name__
            return stub
        cases = st.tuples(
            st.integers(0, 2**31 - 1),           # seed
            st.integers(2, 60),                  # n_train
            st.integers(1, 6),                   # n_features
            st.integers(1, 8),                   # n_trees
            st.integers(0, 6),                   # max_depth
            st.booleans(),                       # constant labels
        )
        return settings(max_examples=max_examples, deadline=None)(
            given(cases)(fn))
    return deco


@given_forest_cases(max_examples=30)
def test_property_flat_equals_reference(case):
    seed, n, f, trees, depth, const = case
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f))
    y = np.zeros(n) if const else rng.normal(size=n)
    model = RegressionForest(n_trees=trees, max_depth=depth,
                             seed=seed % 1000).fit(x, y)
    xq = rng.normal(size=(33, f))
    assert np.array_equal(model.predict(xq, backend="numpy"),
                          model.predict_reference(xq))


# ------------------------------------------------------- batched features
def test_design_features_batch_matches_scalar():
    spec = spec_tiny()
    rng = np.random.default_rng(3)
    designs = [spec.mesh_design()] + [random_design(spec, rng) for _ in range(12)]
    batch = design_features_batch(spec, designs)
    assert batch.shape == (13, len(FEATURE_NAMES))
    scalar = np.stack([design_features(spec, d) for d in designs])
    assert np.allclose(batch, scalar, rtol=1e-9, atol=1e-12)


def test_design_features_batch_empty():
    spec = spec_tiny()
    assert design_features_batch(spec, []).shape == (0, len(FEATURE_NAMES))
