"""Regression forest (bagged CART) — the paper's base learner for Eval.

sklearn is unavailable offline; this is a compact numpy implementation. The
paper notes any quick, sufficiently expressive regressor works (§5.2).
Trees use variance-reduction splits, bootstrap bagging, and per-split
feature subsampling.

Inference is the MOO-STAGE hot path (the surrogate is queried for whole
sampled neighborhoods every meta-search step), so after fitting, the forest
is flattened into struct-of-arrays form: per-tree ``feature`` / ``threshold``
/ ``left`` / ``right`` / ``value`` arrays packed into one padded (T, M)
tensor. ``predict`` traverses all trees for all samples in one vectorized
pass — a (T, B) node-pointer array advanced ``depth`` times with flat
gathers — with a backend switch mirroring core.routing:

  * ``"numpy"``  — the oracle; bit-equal to the recursive traversal
    (``predict_reference``), pinned by golden tests.
  * ``"jnp"``    — jit-compiled float32 traversal (unrolled over depth),
    batch-padded to a power of two so meta-search can fuse scoring; its
    device packing has a fixed per-tree capacity (``device_shape``), so
    refits of one forest configuration share one compile. Agrees with
    numpy up to f32 threshold rounding.
  * ``"auto"``   — jnp on an accelerator (TPU or GPU), numpy/jnp by batch
    size on CPU (DESIGN.md §4.4).
"""

from __future__ import annotations

from functools import partial

import numpy as np

FOREST_BACKENDS = ("auto", "numpy", "jnp")


def check_forest_backend(backend: str | None, *,
                         allow_none: bool = False) -> None:
    """Shared membership check for every forest_backend knob (the forest
    itself, resolution, NocProblem, the stage configs) — one error
    message, one maintenance site. ``allow_none`` admits the configs'
    "inherit the problem's knob" sentinel."""
    if backend is None and allow_none:
        return
    if backend not in FOREST_BACKENDS:
        raise ValueError(
            f"forest_backend must be one of {FOREST_BACKENDS}, "
            f"got {backend!r}")


def resolve_forest_backend(backend: str | None = None,
                           batch: int | None = None) -> str:
    """Resolve ``backend`` (default ``"auto"``) to a concrete one.

    On a TPU, as on a GPU, ``auto`` is the jitted jnp traversal. On CPU it
    picks numpy for small (neighborhood-sized) batches, where per-call
    dispatch dominates, and jnp for large ones."""
    b = backend if backend is not None else "auto"
    check_forest_backend(b)
    if b == "auto":
        import jax

        if jax.default_backend() != "cpu":
            b = "jnp"
        else:
            b = "numpy" if batch is not None and batch < 512 else "jnp"
    return b


class _Tree:
    __slots__ = ("feature", "threshold", "left", "right", "value")

    def __init__(self):
        self.feature = -1
        self.threshold = 0.0
        self.left = None
        self.right = None
        self.value = 0.0


def _build(x, y, rng, depth, max_depth, min_leaf, n_feat_try):
    node = _Tree()
    node.value = float(y.mean())
    if depth >= max_depth or y.shape[0] < 2 * min_leaf or np.ptp(y) < 1e-12:
        return node
    n, f = x.shape
    best = (None, None, np.inf)
    for feat in rng.choice(f, size=min(n_feat_try, f), replace=False):
        xs = x[:, feat]
        order = np.argsort(xs, kind="stable")
        xs_s, y_s = xs[order], y[order]
        # candidate split points between distinct neighbor values
        csum = np.cumsum(y_s)
        csq = np.cumsum(y_s**2)
        tot, tot2 = csum[-1], csq[-1]
        idx = np.arange(min_leaf, n - min_leaf)
        if idx.size == 0:
            continue
        valid = xs_s[idx] < xs_s[idx + 1] - 1e-15
        idx = idx[valid]
        if idx.size == 0:
            continue
        nl = idx + 1.0
        nr = n - nl
        sse = (csq[idx] - csum[idx] ** 2 / nl) + (
            (tot2 - csq[idx]) - (tot - csum[idx]) ** 2 / nr
        )
        j = int(np.argmin(sse))
        if sse[j] < best[2]:
            thr = 0.5 * (xs_s[idx[j]] + xs_s[idx[j] + 1])
            best = (int(feat), float(thr), float(sse[j]))
    if best[0] is None:
        return node
    node.feature, node.threshold = best[0], best[1]
    mask = x[:, node.feature] <= node.threshold
    node.left = _build(x[mask], y[mask], rng, depth + 1, max_depth, min_leaf, n_feat_try)
    node.right = _build(x[~mask], y[~mask], rng, depth + 1, max_depth, min_leaf, n_feat_try)
    return node


def _predict_tree(node: _Tree, x: np.ndarray) -> np.ndarray:
    out = np.empty(x.shape[0])
    stack = [(node, np.arange(x.shape[0]))]
    while stack:
        nd, idx = stack.pop()
        if nd.left is None:
            out[idx] = nd.value
            continue
        mask = x[idx, nd.feature] <= nd.threshold
        stack.append((nd.left, idx[mask]))
        stack.append((nd.right, idx[~mask]))
    return out


def _flatten_tree(root: _Tree):
    """Preorder struct-of-arrays form of one tree.

    Leaves get ``feature = -1`` and self-loop children, so traversal past a
    leaf is the identity and every sample can be advanced the same (max)
    number of steps."""
    feature: list[int] = []
    threshold: list[float] = []
    left: list[int] = []
    right: list[int] = []
    value: list[float] = []
    depth = 0

    def rec(node: _Tree, d: int) -> int:
        nonlocal depth
        depth = max(depth, d)
        i = len(feature)
        feature.append(-1 if node.left is None else node.feature)
        threshold.append(node.threshold)
        value.append(node.value)
        left.append(i)
        right.append(i)
        if node.left is not None:
            left[i] = rec(node.left, d + 1)
            right[i] = rec(node.right, d + 1)
        return i

    rec(root, 0)
    return (np.asarray(feature, np.int32), np.asarray(threshold, np.float64),
            np.asarray(left, np.int32), np.asarray(right, np.int32),
            np.asarray(value, np.float64), depth)


def flat_forest_eval(thrfeat, child, value, xn, depth, n_trees, n_nodes):
    """Traceable flat traversal body — (B,) forest mean from the packed
    ``jnp_tensors()`` layout and an already-normalized f32 batch.

    Works on the (T*B,)-flattened node-pointer layout: every (tree, sample)
    pair advances one int32 pointer per level via three 1-D gathers. Leaves
    self-loop, so no leaf masking is needed and the loop fully unrolls
    (``depth`` must be a Python int). Shared by the standalone jitted
    predict below and the fused meta-search pipeline (core.fused), which
    inlines it after its on-device featurization."""
    import jax.numpy as jnp

    def g(a, idx):
        # All pointers are in bounds by construction (children stay inside
        # their tree, leaf features are clamped to 0) — skipping the default
        # index clamping roughly halves the gather cost on CPU.
        return a.at[idx].get(mode="promise_in_bounds")

    # thrfeat packs (threshold, feature) as one complex64 per node, so a
    # level costs 3 gathers instead of 4 (features are tiny ints — exact
    # as f32 imag parts).
    b, f = xn.shape
    xnf = xn.reshape(-1)
    idx = jnp.repeat(jnp.arange(n_trees, dtype=jnp.int32) * n_nodes, b)
    cols = jnp.tile(jnp.arange(b, dtype=jnp.int32) * f, n_trees)
    for _ in range(depth):
        tf = g(thrfeat, idx)
        fi = jnp.imag(tf).astype(jnp.int32)
        xv = g(xnf, fi + cols)
        go_right = (xv > jnp.real(tf)).astype(jnp.int32)
        idx = g(child, (idx * 2) + go_right)
    return g(value, idx).reshape(n_trees, b).mean(axis=0)


def _predict_flat_jnp_fn():
    """Build the jitted flat traversal lazily so importing the forest never
    forces a jax initialization."""
    import jax

    @partial(jax.jit, static_argnames=("depth", "n_trees", "n_nodes"))
    def forest_traverse(thrfeat, child, value, xn, depth, n_trees,
                        n_nodes):
        return flat_forest_eval(thrfeat, child, value, xn,
                                depth, n_trees, n_nodes)

    return forest_traverse


_JITTED_FLAT = None

#: deepest ``max_depth`` whose full-tree node capacity the device packing
#: reserves per tree (2^13 nodes; see ``RegressionForest.device_shape``)
FULL_TREE_MAX_DEPTH = 12


class RegressionForest:
    def __init__(self, n_trees: int = 24, max_depth: int = 9,
                 min_leaf: int = 3, seed: int = 0, backend: str = "auto"):
        self.n_trees = n_trees
        self.max_depth = max_depth
        self.min_leaf = min_leaf
        self.backend = backend
        check_forest_backend(backend)  # fail fast, but don't touch jax
        self.rng = np.random.default_rng(seed)
        self.trees: list[_Tree] = []
        self._xm = self._xs = None
        self._flat = None        # packed (T, M) numpy tensors
        self._flat_jnp = None    # f32 device copies, built on first jnp call

    def fit(self, x: np.ndarray, y: np.ndarray) -> "RegressionForest":
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        self._xm = x.mean(0)
        self._xs = x.std(0) + 1e-9
        xn = (x - self._xm) / self._xs
        n = x.shape[0]
        n_feat_try = max(1, int(np.ceil(np.sqrt(x.shape[1]))) + 1)
        self.trees = []
        for _ in range(self.n_trees):
            idx = self.rng.integers(0, n, size=n)
            self.trees.append(
                _build(xn[idx], y[idx], self.rng, 0, self.max_depth,
                       self.min_leaf, n_feat_try)
            )
        self._pack()
        return self

    # ------------------------------------------------------------ flattening
    def _pack(self):
        flats = [_flatten_tree(t) for t in self.trees]
        t = len(flats)
        m = max(f[0].shape[0] for f in flats)
        feature = np.full((t, m), -1, np.int32)
        threshold = np.zeros((t, m), np.float64)
        left = np.tile(np.arange(m, dtype=np.int32), (t, 1))
        right = left.copy()
        value = np.zeros((t, m), np.float64)
        depth = 0
        for i, (fe, th, le, ri, va, de) in enumerate(flats):
            k = fe.shape[0]
            feature[i, :k] = fe
            threshold[i, :k] = th
            left[i, :k] = le
            right[i, :k] = ri
            value[i, :k] = va
            depth = max(depth, de)
        # Flat-absolute children (child[2i] = left, child[2i+1] = right) let
        # the traversal do one gather per step; leaves self-loop, so samples
        # that arrive early just spin in place — no leaf masking needed, and
        # leaf features are clamped to 0 so the x-gather stays in bounds.
        offs = (np.arange(t, dtype=np.int64) * m)[:, None]
        child = np.empty((t, m, 2), np.int64)
        child[:, :, 0] = left + offs
        child[:, :, 1] = right + offs
        self._flat = {
            "feature": feature, "threshold": threshold,
            "left": left, "right": right, "value": value,
            "child_flat": child.reshape(-1),
            "feat_safe_flat": np.maximum(feature, 0).astype(np.int64).reshape(-1),
            "threshold_flat": threshold.reshape(-1),
            "value_flat": value.reshape(-1),
            "depth": depth, "n_nodes": m,
        }
        self._flat_jnp = None

    # -------------------------------------------------------------- predict
    def _normalize(self, x: np.ndarray) -> np.ndarray:
        x = np.atleast_2d(np.asarray(x, np.float64))
        return (x - self._xm) / self._xs

    def predict(self, x: np.ndarray, backend: str | None = None) -> np.ndarray:
        """(B,) forest mean via the flat vectorized traversal."""
        xn = self._normalize(x)
        b = resolve_forest_backend(backend if backend is not None else self.backend,
                                   batch=xn.shape[0])
        if b == "jnp":
            return self._predict_jnp(xn)
        return self._predict_numpy(xn)

    def predict_reference(self, x: np.ndarray) -> np.ndarray:
        """Recursive per-tree traversal — the original implementation, kept
        as the golden oracle for the flat paths."""
        xn = self._normalize(x)
        return np.mean([_predict_tree(t, xn) for t in self.trees], axis=0)

    def _predict_numpy(self, xn: np.ndarray) -> np.ndarray:
        """Flat vectorized traversal: node pointers advanced ``depth`` times
        with 1-D ``np.take`` gathers. Bit-equal to the recursive reference
        (same f64 compares, same ``np.mean`` over the tree axis).

        Small batches (the meta-search neighborhood path) use one (T, B)
        pointer block — 4 gathers per level total; big batches iterate per
        tree so the gather working set stays cache-resident."""
        fl = self._flat
        t, m, depth = len(self.trees), fl["n_nodes"], fl["depth"]
        b = xn.shape[0]
        feat = fl["feat_safe_flat"]
        thr = fl["threshold_flat"]
        child = fl["child_flat"]
        xnf = np.ascontiguousarray(xn).ravel()
        cols = np.arange(b, dtype=np.int64) * xn.shape[1]
        if b <= 1024:
            idx = (np.arange(t, dtype=np.int64) * m)[:, None] + np.zeros(
                (1, b), np.int64)
            for _ in range(depth):
                fi = np.take(feat, idx)
                xv = np.take(xnf, fi + cols[None, :])
                go_right = np.take(thr, idx) < xv
                idx = np.take(child, (idx << 1) + go_right)
            return np.take(fl["value_flat"], idx).mean(axis=0)
        vals = np.empty((t, b))
        for ti in range(t):
            idx = np.full(b, ti * m, np.int64)
            for _ in range(depth):
                fi = np.take(feat, idx)
                xv = np.take(xnf, fi + cols)
                go_right = np.take(thr, idx) < xv
                idx = np.take(child, (idx << 1) + go_right)
            vals[ti] = np.take(fl["value_flat"], idx)
        return np.mean(vals, axis=0)

    def device_shape(self) -> tuple[int, int, int]:
        """Static shape key ``(depth, n_trees, n_nodes)`` of the device
        packing (:meth:`jnp_tensors`).

        It depends on the forest's configuration alone wherever that is
        affordable, so every refit of a search reuses one compiled
        traversal: each tree's node block is padded to the capacity of a
        full tree of ``max_depth`` (2^(max_depth+1) nodes: 1024 at the
        default 9) and the loop unrolls ``max_depth`` levels (leaves
        self-loop, so levels past a tree's depth are the identity). Past
        ``FULL_TREE_MAX_DEPTH`` a full tree is too large to reserve; the
        block is then the largest tree's node count rounded up to a power
        of two and the loop unrolls the fitted depth."""
        fl = self._flat
        m, t = fl["n_nodes"], len(self.trees)
        if self.max_depth <= FULL_TREE_MAX_DEPTH:
            return self.max_depth, t, 1 << (self.max_depth + 1)
        return fl["depth"], t, 1 << max(0, (m - 1).bit_length())

    def layout_attrs(self) -> dict[str, int]:
        """``nodes`` (the largest tree's node count) and ``cap`` (its
        padded block on the device): how full the device packing is."""
        return {"nodes": self._flat["n_nodes"], "cap": self.device_shape()[2]}

    def jnp_tensors(self):
        """Cached f32 device tensors of the flat forest, plus its static
        shape key: ``(thrfeat, child, value), (depth, n_trees, n_nodes)``.

        This is the packing `_predict_jnp` traverses; it is public so the
        fused meta-search (core.fused) can inline the same traversal inside
        its own jitted featurize→score pipeline without round-tripping
        features through the host. Each tree owns a block of ``n_nodes``
        slots (:meth:`device_shape`); the slots past its own nodes are
        self-looping leaves no pointer reaches, so a traversal returns what
        the unpadded layout returns. Every dtype is converted in NumPy: the
        one ``jnp.asarray`` per tensor is a plain transfer, with no device
        conversion to compile."""
        import jax.numpy as jnp

        key = self.device_shape()
        if self._flat_jnp is None:
            fl = self._flat
            t, m = fl["feature"].shape
            cap = key[2]
            thrfeat = np.zeros((t, cap), np.complex64)
            thrfeat[:, :m] = (fl["threshold"].astype(np.float32) + 1j *
                              np.maximum(fl["feature"], 0).astype(np.float32))
            child = np.empty((t, cap, 2), np.int32)
            child[:] = np.arange(cap, dtype=np.int32)[None, :, None]
            child[:, :m, 0] = fl["left"]
            child[:, :m, 1] = fl["right"]
            child += (np.arange(t, dtype=np.int32) * cap)[:, None, None]
            value = np.zeros((t, cap), np.float32)
            value[:, :m] = fl["value"]
            self._flat_jnp = (jnp.asarray(thrfeat.reshape(-1)),
                              jnp.asarray(child.reshape(-1)),
                              jnp.asarray(value.reshape(-1)))
        return self._flat_jnp, key

    def _predict_jnp(self, xn: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        global _JITTED_FLAT
        if _JITTED_FLAT is None:
            _JITTED_FLAT = _predict_flat_jnp_fn()
        tensors, (depth, n_trees, n_nodes) = self.jnp_tensors()
        b = xn.shape[0]
        pad = 1 << max(0, (b - 1).bit_length())  # bound recompiles
        xp = np.zeros((pad, xn.shape[1]), np.float32)
        xp[:b] = xn
        out = _JITTED_FLAT(*tensors, jnp.asarray(xp), depth=depth,
                           n_trees=n_trees, n_nodes=n_nodes)
        # slice on the host: an eager device slice compiles per new b
        return np.asarray(out, np.float64)[:b]
