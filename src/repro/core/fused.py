"""Device-resident meta-search scoring — one dispatch per greedy step.

The legacy meta-search step (stage._meta_greedy) materializes every
neighborhood candidate as a ``Design`` (a (N, N) adjacency copy each),
featurizes the batch on the host (features.design_features_batch), and only
then reaches the device for the forest traversal. On spec-sized problems
the host featurization dominates the step (~2 ms of the ~2.9 ms step at
N=64) and the per-candidate ``Design`` construction is pure overhead: the
argmax discards all but one candidate.

This module restructures the step around *moves* (problem.NeighborMoves):
the jitted :func:`_score_moves` takes the base design as a permutation plus
a planar-link-mask vector and the neighborhood as (B,) move-index arrays,
and applies move → featurize → normalize → flat-forest traversal entirely
on device — one XLA dispatch per greedy step. Only the winning move is ever
materialized, on the host, after the accept test.

Shape discipline (the PR-4 retrace-bounding trick): batches are padded to a
power of two OUTSIDE the jit with identity moves (swap slot 0 with itself;
remove+add the scratch link column E), so the jit cache keys on the padded
shape. Identity rows reproduce the base design bit-exactly, so they score
exactly the base value and can never win an accept test (strict ``>``);
the host argmax additionally only looks at the real prefix.

Feature math mirrors features.design_features_batch exactly, in f32 (the
same precision the forest's jnp twin traverses at). Per-slot type
masks are float 0/1, so gathers and the class-proximity terms become
matmuls; the link mask uses a scratch column so swap rows and link rows
share one fixed-shape scatter.
"""

from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

from .features import _batch_consts
from .forest import RegressionForest
from .problem import Design, NeighborMoves, SystemSpec

META_BACKENDS = ("host", "fused")


def check_meta_backend(backend: str | None, *, allow_none: bool = False) -> None:
    if backend is None and allow_none:
        return
    if backend not in META_BACKENDS:
        raise ValueError(
            f"meta_backend must be one of {META_BACKENDS}, got {backend!r}")


@lru_cache(maxsize=8)
def _fused_consts(spec: SystemSpec):
    """Spec-static device tensors for the fused featurizer (one per spec),
    plus the host-side (N, N) → edge-index map used to encode link moves."""
    import jax.numpy as jnp

    c = _batch_consts(spec)
    n = spec.n_tiles
    e = c["iu0"].shape[0]
    eid = np.full((n, n), -1, np.int32)
    eid[c["iu0"], c["iu1"]] = np.arange(e, dtype=np.int32)
    eid[c["iu1"], c["iu0"]] = np.arange(e, dtype=np.int32)
    # Per-slot incident-edge table: inc_edges[x] lists the n-1 triu edge
    # ids touching slot x, other_slot[x] the opposite endpoint of each —
    # the swap-delta features walk these O(N) rows instead of all E edges.
    inc_edges = np.empty((n, n - 1), np.int32)
    other_slot = np.empty((n, n - 1), np.int32)
    for x in range(n):
        mask = (c["iu0"] == x) | (c["iu1"] == x)
        ids = np.flatnonzero(mask)
        inc_edges[x] = ids
        other_slot[x] = np.where(c["iu0"][ids] == x,
                                 c["iu1"][ids], c["iu0"][ids])
    # _ext arrays carry a scratch tail entry (edge E -> zero weight, node
    # n) so identity-padded rows produce exact-zero deltas.
    f32 = jnp.float32
    lens = np.asarray(c["lens"], np.float32)
    loh = np.asarray(c["layer_onehot"], np.float32)
    # Host-side twins for the per-step base-design scalars: every one is an
    # exact small integer in f32 (lens are integer Manhattan distances, the
    # link mask is 0/1), so numpy and XLA produce bitwise-equal values and
    # the ~0.2 ms the base-scalar block cost as device ops becomes ~30 us
    # of host arithmetic per step.
    host = {
        "lens": lens,
        "lens2": (lens * lens).astype(np.float32),
        "loh": loh,
        "is_llc": np.asarray(c["is_llc"], np.float32),
        "iu0": np.asarray(c["iu0"]),
        "iu1": np.asarray(c["iu1"]),
        "n": n,
    }
    dev = {
        "layer": jnp.asarray(c["layer"], f32),
        "col_onehot": jnp.asarray(c["col_onehot"], f32),
        "layer_onehot": jnp.asarray(loh),
        "lens": jnp.asarray(lens),
        "lens_ext": jnp.asarray(np.append(lens, 0.0).astype(np.float32)),
        "loh_ext": jnp.asarray(
            np.vstack([loh, np.zeros((1, loh.shape[1]), np.float32)])),
        "man2": jnp.asarray(c["man2"], f32),
        "vert_deg": jnp.asarray(c["vert_deg"], f32),
        "iu0": jnp.asarray(c["iu0"], jnp.int32),
        "iu1": jnp.asarray(c["iu1"], jnp.int32),
        "iu0_ext": jnp.asarray(
            np.append(c["iu0"], n).astype(np.int32)),
        "iu1_ext": jnp.asarray(
            np.append(c["iu1"], n).astype(np.int32)),
        "inc_edges": jnp.asarray(inc_edges),
        "other_slot": jnp.asarray(other_slot),
        "eid_safe": jnp.asarray(np.maximum(eid, 0)),
        "is_cpu": jnp.asarray(c["is_cpu"], f32),
        "is_llc": jnp.asarray(c["is_llc"], f32),
        "is_gpu": jnp.asarray(c["is_gpu"], f32),
        "power": jnp.asarray(spec.core_power, f32),
    }
    return dev, host, eid, e


def _fused_features(c: dict, base_perm, base_lm, base_scalars,
                    sa, sb, er, ea):
    """(B, F) f32 features for base+move candidates — traceable body.

    ``sa``/``sb`` are swap slot pairs (identity when equal); ``er``/``ea``
    are removed/added edge indices in triu order, with the scratch sentinel
    ``E`` for non-link rows. The formulas transliterate
    features.design_features_batch (FEATURE_NAMES order).

    Every link-mask feature is computed INCREMENTALLY: the caller supplies
    the base-design scalars (``base_scalars``, built by
    ``MetaScorer._base_state`` in host numpy — every entry is an exact
    small integer in f32, so host and device agree bitwise), and this body
    only computes per-candidate deltas in O(B*N) — a swap touches no
    links, a link move touches exactly one removed and one added edge, so
    no (B, E) array is ever materialized (the full-mask variants of
    ``deg`` and the LLC link fraction dominated the whole program at
    E ~ N^2/2). Identity-padded rows hit the scratch edge/node and produce
    exact-zero deltas, keeping the padding contract bitwise."""
    import jax.numpy as jnp

    counts0, sums0, llc_slot0, ends0_ext, deg0 = base_scalars
    s1_0, s2_0, lm_cnt, s_llc0 = sums0[0], sums0[1], sums0[2], sums0[3]
    bsz = sa.shape[0]
    n = base_perm.shape[0]
    rows = jnp.arange(bsz)
    layer = c["layer"]
    k = float(c["layer_onehot"].shape[1])

    # ---------------------------------------------- perm-side (O(B*N))
    perms = jnp.broadcast_to(base_perm, (bsz, n))
    pa, pb = base_perm[sa], base_perm[sb]
    perms = perms.at[rows, sa].set(pb).at[rows, sb].set(pa)

    is_cpu = c["is_cpu"][perms]
    is_llc = c["is_llc"][perms]
    is_gpu = c["is_gpu"][perms]
    power = c["power"][perms]

    def mstats_masked(x_row, mask):
        cnt = mask.sum(1)
        m1 = (mask * x_row).sum(1) / cnt
        m2 = (mask * x_row * x_row).sum(1) / cnt
        return m1, jnp.sqrt(jnp.maximum(m2 - m1 * m1, 0.0))

    llc_mean, llc_std = mstats_masked(layer, is_llc)
    cpu_mean = (layer * is_cpu).sum(1) / is_cpu.sum(1)
    gpu_mean = (layer * is_gpu).sum(1) / is_gpu.sum(1)
    power_depth = (power * layer).sum(1) / (power.sum(1) * k)
    col_power = power @ c["col_onehot"]
    col_power_std = col_power.std(1) / (col_power.mean(1) + 1e-9)

    # ------------------------------------------- link-move deltas (O(B*K))
    counts = counts0[None, :] - c["loh_ext"][er] + c["loh_ext"][ea]
    p = counts / counts.sum(1, keepdims=True)
    entropy = -(p * jnp.log(p + 1e-12)).sum(1) / np.log(k)
    s1 = s1_0 - c["lens_ext"][er] + c["lens_ext"][ea]
    s2 = (s2_0 - c["lens_ext"][er] ** 2 + c["lens_ext"][ea] ** 2)
    len_mean = s1 / lm_cnt
    len_std = jnp.sqrt(jnp.maximum(s2 / lm_cnt - len_mean * len_mean, 0.0))

    # deg: one (B, 4) scatter per dispatch onto a scratch-node column
    # (both endpoints of the removed edge -1, of the added edge +1).
    didx = jnp.stack([c["iu0_ext"][er], c["iu1_ext"][er],
                      c["iu0_ext"][ea], c["iu1_ext"][ea]], axis=1)
    dupd = jnp.broadcast_to(
        jnp.asarray([-1.0, -1.0, 1.0, 1.0], deg0.dtype), (bsz, 4))
    deg = (jnp.broadcast_to(deg0, (bsz, n + 1))
           .at[rows[:, None], didx].add(dupd))[:, :n] + c["vert_deg"]
    llc_deg_mean = (deg * is_llc).sum(1) / is_llc.sum(1)

    # LLC link fraction: link rows move one edge's base end-flag out/in;
    # swap rows re-flag the <= 2(N-1) edges incident to the swapped slots.
    # The (sa, sb) edge appears in both incident walks with a spurious
    # -|la - lb| total (its true delta is zero: max is symmetric), which
    # the last term cancels; identity rows zero out termwise.
    la, lb = llc_slot0[sa], llc_slot0[sb]

    def swap_end_delta(x, v_old, v_new):
        eids = c["inc_edges"][x]                               # (B, N-1)
        lo = llc_slot0[c["other_slot"][x]]
        w = base_lm[eids]
        return ((jnp.maximum(v_new[:, None], lo)
                 - jnp.maximum(v_old[:, None], lo)) * w).sum(1)

    s_llc = (s_llc0
             - ends0_ext[er] + ends0_ext[ea]
             + swap_end_delta(sa, la, lb) + swap_end_delta(sb, lb, la)
             + jnp.abs(la - lb) * base_lm[c["eid_safe"][sa, sb]])
    llc_link_frac = s_llc / jnp.maximum(lm_cnt, 1.0)

    n_llc = is_llc.sum(1)
    cpu_llc = ((is_cpu @ c["man2"]) * is_llc).sum(1) / (is_cpu.sum(1) * n_llc)
    gpu_llc = ((is_gpu @ c["man2"]) * is_llc).sum(1) / (is_gpu.sum(1) * n_llc)

    return jnp.stack([
        llc_mean / k, llc_std / k, cpu_mean / k, gpu_mean / k,
        power_depth, col_power_std,
        entropy, len_mean, len_std,
        deg.mean(1), deg.std(1), deg.max(1),
        llc_deg_mean, cpu_llc, gpu_llc, llc_link_frac,
    ], axis=1)


_SCORE_JIT = None


def _score_moves_fn():
    """Build the jitted move→featurize→normalize→traverse pipeline lazily
    (importing core.fused must not initialize jax)."""
    import jax

    from .forest import flat_forest_eval

    @partial(jax.jit, static_argnames=("depth", "n_trees", "n_nodes"))
    def meta_score_moves(c, thrfeat, child, value, xm, xs,
                         base_perm, base_lm, base_scalars, sa, sb, er, ea,
                         *, depth, n_trees, n_nodes):
        feats = _fused_features(c, base_perm, base_lm, base_scalars,
                                sa, sb, er, ea)
        xn = (feats - xm) / xs
        return flat_forest_eval(thrfeat, child, value, xn,
                                depth, n_trees, n_nodes)

    return meta_score_moves


class MetaScorer:
    """Per-(spec, fitted forest) scorer for the fused meta-greedy step.

    Holds the device-resident spec constants and forest tensors; each
    :meth:`score_moves` call is one XLA dispatch over the whole padded
    neighborhood (the ``meta_backend="fused"`` path; ``"host"`` is
    ``stage._meta_greedy_host``)."""

    def __init__(self, spec: SystemSpec, model: RegressionForest):
        import jax.numpy as jnp

        global _SCORE_JIT
        if _SCORE_JIT is None:
            _SCORE_JIT = _score_moves_fn()
        self.spec = spec
        self.c, self._h, self._eid, self._e = _fused_consts(spec)
        self._iu0, self._iu1 = self._h["iu0"], self._h["iu1"]
        (self.thrfeat, self.child, self.value), \
            (self.depth, self.n_trees, self.n_nodes) = model.jnp_tensors()
        self.xm = jnp.asarray(model._xm.astype(np.float32))
        self.xs = jnp.asarray(model._xs.astype(np.float32))

    # ------------------------------------------------------------- encoding
    def _encode(self, moves: NeighborMoves) -> tuple:
        """Pad the neighborhood to a fixed shape and encode it as move-index
        arrays (identity rows fill the tail)."""
        s = moves.swaps.shape[0]
        b = len(moves)
        pad = 1 << max(0, (b - 1).bit_length())
        sa = np.zeros(pad, np.int32)
        sb = np.zeros(pad, np.int32)
        er = np.full(pad, self._e, np.int32)
        ea = np.full(pad, self._e, np.int32)
        sa[:s] = moves.swaps[:, 0]
        sb[:s] = moves.swaps[:, 1]
        er[s:b] = self._eid[moves.rem[:, 0], moves.rem[:, 1]]
        ea[s:b] = self._eid[moves.add[:, 0], moves.add[:, 1]]
        return sa, sb, er, ea

    def _base_state(self, d: Design) -> tuple:
        """(base_perm, base_lm, base_scalars) — all plain numpy: the jit's
        C++ argument path converts host arrays far cheaper than an eager
        jnp.asarray per array per step, and the base-design link scalars
        are exact small integers in f32 (integer Manhattan lens, 0/1 mask)
        so host numpy reproduces the device values bitwise while skipping
        ~0.2 ms of tiny XLA ops per step."""
        h = self._h
        n = h["n"]
        lm = d.adj[self._iu0, self._iu1].astype(np.float32)
        counts0 = lm @ h["loh"]                                  # (K,)
        llc_slot0 = h["is_llc"][d.perm]                          # (N,)
        ends0 = np.maximum(llc_slot0[self._iu0], llc_slot0[self._iu1])
        sums0 = np.array([h["lens"] @ lm, h["lens2"] @ lm,
                          lm.sum(), ends0 @ lm], np.float32)
        ends0_ext = np.append(ends0, np.float32(0.0))
        deg0 = (np.bincount(self._iu0, weights=lm, minlength=n + 1)
                + np.bincount(self._iu1, weights=lm, minlength=n + 1)
                ).astype(np.float32)
        scalars = (counts0, sums0, llc_slot0, ends0_ext, deg0)
        return d.perm.astype(np.int32, copy=False), lm, scalars

    # -------------------------------------------------------------- scoring
    def score_base(self, d: Design) -> float:
        """Eval(d) — the fused twin of predict(features([d]))[0]."""
        base_perm, base_lm, scalars = self._base_state(d)
        one = np.zeros(1, np.int32)
        vals = _SCORE_JIT(self.c, self.thrfeat, self.child, self.value,
                          self.xm, self.xs, base_perm, base_lm, scalars,
                          one, one, np.full(1, self._e, np.int32),
                          np.full(1, self._e, np.int32),
                          depth=self.depth, n_trees=self.n_trees,
                          n_nodes=self.n_nodes)
        return float(np.asarray(vals)[0])

    def score_moves(self, moves: NeighborMoves) -> tuple[int, float]:
        """(argmax j, Eval of candidate j) over the neighborhood — one
        device dispatch. Tie-break matches np.argmax (first max)."""
        b = len(moves)
        base_perm, base_lm, scalars = self._base_state(moves.base)
        sa, sb, er, ea = self._encode(moves)
        vals = _SCORE_JIT(self.c, self.thrfeat, self.child, self.value,
                          self.xm, self.xs, base_perm, base_lm, scalars,
                          sa, sb, er, ea, depth=self.depth,
                          n_trees=self.n_trees, n_nodes=self.n_nodes)
        # transfer the whole padded vector and slice on the host — an eager
        # device-side vals[:b] would dispatch a second XLA op per step.
        vals = np.asarray(vals)[:b]
        j = int(np.argmax(vals))
        return j, float(vals[j])
