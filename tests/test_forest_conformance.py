"""Cross-backend forest conformance: recursive oracle
(``predict_reference``) <-> flat numpy (f64) <-> jnp (jitted f32 twin).

The flat numpy path is bit-equal to the recursive reference; the jnp path
agrees with both to f32 threshold rounding (<= 1e-6 here). Edge shapes:
1-row batches, odd and power-of-two batch sizes, single-node (leaf-only)
trees, max-depth trees, and padded node tails.

Property tests need ``hypothesis``; without it they are skipped and the
unit tests still run (same pattern as test_forest.py)."""

import numpy as np
import pytest

from repro.core import forest as forest_mod
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


def _assert_triangle(model, xq, atol_oracle=1e-6):
    """The three traversals agree on ``xq``: flat numpy bit-equal to the
    recursive oracle, and jnp within ``atol_oracle`` of both."""
    ref = model.predict_reference(xq)
    np_out = model.predict(xq, backend="numpy")
    jnp_out = model.predict(xq, backend="jnp")
    assert np_out.shape == ref.shape == jnp_out.shape
    np.testing.assert_array_equal(np_out, ref)
    np.testing.assert_allclose(jnp_out, ref, rtol=0, atol=atol_oracle)


# ------------------------------------------------------------- batch shapes
@pytest.mark.parametrize(
    "batch",
    [1,            # single row
     5,            # tiny odd
     127, 129,     # one off a power of two on each side
     128,          # exactly a power of two
     500,          # padded to 512
     1025],        # above the numpy path's 1024 layout switch
)
def test_conformance_over_batch_shapes(batch):
    model, rng = _fit(n=300, f=6, n_trees=10, max_depth=7)
    xq = rng.uniform(-1.5, 1.5, size=(batch, 6))  # extrapolation included
    _assert_triangle(model, xq)


def test_conformance_1d_input_promotes_like_other_backends():
    model, rng = _fit()
    xq = rng.uniform(-1, 1, size=5)
    out = model.predict(xq, backend="jnp")
    assert out.shape == (1,)
    np.testing.assert_allclose(out, model.predict(xq, backend="numpy"),
                               rtol=0, atol=1e-6)


# ------------------------------------------------------------- tree shapes
def test_single_node_trees():
    """max_depth=0: every tree is one leaf, the level loop unrolls to
    nothing and the traversal reduces the root values."""
    model, rng = _fit(n=100, f=3, n_trees=5, max_depth=0)
    assert model._flat["depth"] == 0
    _assert_triangle(model, rng.uniform(-1, 1, size=(17, 3)))


def test_max_depth_trees():
    """min_leaf=1 on dense data grows trees to the depth cap — the deepest
    unrolled traversal the repo's configs can produce."""
    model, rng = _fit(n=256, f=4, n_trees=6, max_depth=16, min_leaf=1)
    assert model._flat["depth"] >= 10
    _assert_triangle(model, rng.uniform(-1, 1, size=(77, 4)))


def test_mixed_size_trees_pad_node_tails():
    """Bootstrap variation gives per-tree node counts below the padded M;
    the short trees' tails are self-looping filler the traversal must never
    enter from a real root."""
    model, rng = _fit(n=60, f=5, n_trees=12, max_depth=6, min_leaf=1)
    feature = model._flat["feature"]
    sizes = [(row != -1).sum() for row in feature]  # split-node counts
    assert len(set(sizes)) > 1  # genuinely ragged before padding
    _assert_triangle(model, rng.uniform(-1, 1, size=(33, 5)))


def _unpadded_jnp_predict(model, xq):
    """The jnp traversal over the unpadded flat layout: each tree's block
    as wide as the largest tree, the loop unrolled to the fitted depth."""
    import jax
    import jax.numpy as jnp

    fl = model._flat
    thrfeat = (fl["threshold_flat"].astype(np.float32)
               + 1j * fl["feat_safe_flat"].astype(np.float32))
    xn = model._normalize(xq)
    b = xn.shape[0]
    xp = np.zeros((1 << max(0, (b - 1).bit_length()), xn.shape[1]),
                  np.float32)
    xp[:b] = xn
    fn = jax.jit(forest_mod.flat_forest_eval,
                 static_argnames=("depth", "n_trees", "n_nodes"))
    out = fn(jnp.asarray(thrfeat.astype(np.complex64)),
             jnp.asarray(fl["child_flat"].astype(np.int32)),
             jnp.asarray(fl["value_flat"].astype(np.float32)),
             jnp.asarray(xp), depth=fl["depth"],
             n_trees=len(model.trees), n_nodes=fl["n_nodes"])
    return np.asarray(out[:b], np.float64)


@pytest.mark.parametrize("case", ["shallow", "mixed", "deep"])
def test_padded_device_layout_matches_unpadded(case):
    """The fixed-capacity device packing returns exactly what the unpadded
    layout returns, within f32 rounding of the numpy oracle and with the
    same argmax: trees shallower than ``max_depth`` (extra unrolled levels
    spin on leaves), trees of mixed sizes (each padded to the capacity),
    and a depth past the full-tree capacity (blocks bucketed to a power of
    two)."""
    if case == "shallow":
        model, rng = _fit(n=40, f=5, n_trees=8, max_depth=9, min_leaf=4)
        assert model._flat["depth"] < model.max_depth
    elif case == "mixed":
        model, rng = _fit(n=60, f=5, n_trees=12, max_depth=6, min_leaf=1)
        sizes = {int((row != -1).sum()) for row in model._flat["feature"]}
        assert len(sizes) > 1
    else:
        model, rng = _fit(n=256, f=5, n_trees=6, max_depth=16, min_leaf=1)
        assert model.max_depth > forest_mod.FULL_TREE_MAX_DEPTH
    depth, n_trees, cap = model.device_shape()
    m = model._flat["n_nodes"]
    if case == "deep":
        assert (depth, cap) == (model._flat["depth"],
                                1 << (m - 1).bit_length())
    else:
        assert (depth, cap) == (model.max_depth, 1 << (model.max_depth + 1))
    assert cap >= m and n_trees == len(model.trees)
    assert model.layout_attrs() == {"nodes": m, "cap": cap}
    assert [a.shape for a in model.jnp_tensors()[0]] == [
        (n_trees * cap,), (2 * n_trees * cap,), (n_trees * cap,)]

    xq = rng.uniform(-1.5, 1.5, size=(70, 5))
    got = model.predict(xq, backend="jnp")
    np.testing.assert_array_equal(got, _unpadded_jnp_predict(model, xq))
    ref = model.predict(xq, backend="numpy")
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)
    assert int(np.argmax(got)) == int(np.argmax(ref))


def test_constant_labels_degenerate_fit():
    rng = np.random.default_rng(0)
    x = rng.uniform(size=(50, 4))
    model = RegressionForest(n_trees=6, seed=1).fit(x, np.full(50, 3.25))
    xq = rng.uniform(size=(9, 4))
    out = model.predict(xq, backend="jnp")
    np.testing.assert_allclose(out, np.full(9, 3.25), rtol=0, atol=1e-6)


# ----------------------------------------------------------- resolution
def test_pallas_resolves_off_tpu_with_one_time_warning():
    """"pallas" is refused like any unknown backend (its kernel is gone),
    and "auto" resolves to numpy or jnp at every batch size."""
    with pytest.raises(ValueError, match="forest_backend must be one of"):
        resolve_forest_backend("pallas")
    with pytest.raises(ValueError, match="forest_backend must be one of"):
        RegressionForest(backend="pallas")
    for batch in (None, 8, 4096):
        assert resolve_forest_backend("auto", batch=batch) in ("numpy", "jnp")


# -------------------------------------------------------------- properties
def given_forest_cases(max_examples):
    """Property decorator when hypothesis is available, skip otherwise
    (mirrors tests/test_forest.py)."""
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
            st.integers(1, 140),                 # query batch
        )
        return settings(max_examples=max_examples, deadline=None)(
            given(cases)(fn))
    return deco


@given_forest_cases(max_examples=20)
def test_property_pallas_equals_jnp_twin(case):
    """jnp agrees with the f64 numpy oracle to f32 threshold rounding on
    arbitrary forests/batches."""
    seed, n, f, trees, depth, batch = case
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f))
    y = rng.normal(size=n)
    model = RegressionForest(n_trees=trees, max_depth=depth,
                             seed=seed % 1000).fit(x, y)
    xq = rng.normal(size=(batch, f))
    np.testing.assert_allclose(
        model.predict(xq, backend="jnp"),
        model.predict(xq, backend="numpy"), rtol=0, atol=1e-6)
