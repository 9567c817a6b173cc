"""Routing model: all-pairs shortest paths + deterministic path walking (JAX).

The paper routes on irregular topologies with ALASH (layered shortest-path)
[40]. For the *analytical* objectives only a deterministic single path per
(src, dst) is needed (Eq. 1 note: "the path ... is determined by the routing
algorithm"), so we model routing as minimum-latency shortest path with
lexicographic tie-breaking:

    cost(hop over link (a, b)) = r + d_ab     (router stages + wire delay)

APSP is computed by min-plus matrix squaring — O(log N) dense min-plus
matmuls, which is the TPU-friendly formulation (see kernels/minplus for the
Pallas version; the jnp path here is the oracle). Next hops follow the
Bellman condition nh[i,j] = argmin_m cost[i,m] + dist[m,j], and a vectorized
walk over all N^2 pairs accumulates, in one pass:

  * per-pair hop count h_ij and wire delay d_ij     (Eq. 1),
  * f-weighted directed link utilization U          (Eq. 2),
  * f-weighted router visit counts                  (Eq. 8).

Routing backends
----------------
The batched entry points (:func:`apsp_batched`,
:func:`routing_tables_batched`) accept ``backend``:

  * ``"jnp"``    — vmapped jnp min-plus squaring; the oracle and the CPU
                   execution path. Materializes an (N, N, N) broadcast per
                   design.
  * ``"pallas"`` — the blocked VMEM-tiled kernel in kernels/minplus; the
                   TPU hot path of core.evaluate.Evaluator. ``interpret=True``
                   runs it through the Pallas interpreter on CPU (tests).
  * ``"auto"``   — ``"pallas"`` on TPU, ``"jnp"`` elsewhere (the default).
"""

from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

INF = 1.0e9

BACKENDS = ("auto", "jnp", "pallas")

#: Largest N served by the one-shot (N, N, N) broadcast formulations of
#: min-plus and next-hop extraction (256³ f32 = 64 MiB transient). Above it
#: the k-/j-blocked paths run instead — bit-equal (min/argmin over exactly
#: the same f32 sums; every finite path cost is a small integer, exactly
#: representable), but never materializing an (N, N, N) intermediate.
DENSE_NMAX = 256

#: Transient budget for one blocked (N, block, N) broadcast slab.
_BLOCK_BUDGET_BYTES = 128 << 20


def _pow2_block(n: int, budget_bytes: int = _BLOCK_BUDGET_BYTES,
                lo: int = 4, hi: int = 128) -> int:
    """Largest power-of-two block b with n·b·n f32 <= ``budget_bytes``
    (clamped to [lo, hi]) — the k/j block width of the memory-safe paths."""
    b = max(1, budget_bytes // (4 * n * n))
    b = 1 << (b.bit_length() - 1)
    return int(min(hi, max(lo, b)))


def apsp_iters(n_tiles: int) -> int:
    """Min-plus squaring iterations guaranteeing APSP convergence for an
    N-node graph (single source of truth for the analytical evaluator and
    the flit simulator's host-side tables)."""
    return math.ceil(math.log2(n_tiles)) + 1


def resolve_backend(backend: str | None = None) -> str:
    """Resolve ``backend`` (or the default ``"auto"``) to a concrete one."""
    b = backend if backend is not None else "auto"
    if b not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {b!r}")
    if b == "auto":
        b = "pallas" if jax.default_backend() == "tpu" else "jnp"
    return b


def min_plus(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(N,N) min-plus product: out[i,j] = min_k a[i,k] + b[k,j].

    One-shot broadcast — materializes an (N, N, N) intermediate, so it is
    only dispatched for N <= DENSE_NMAX (see :func:`min_plus_blocked`)."""
    return jnp.min(a[:, :, None] + b[None, :, :], axis=1)


def min_plus_blocked(a: jnp.ndarray, b: jnp.ndarray,
                     block_k: int | None = None) -> jnp.ndarray:
    """k-blocked min-plus product: bit-equal to :func:`min_plus` (minimum is
    exact and associative; blocking only reorders the reduction) with an
    (N, block_k, N) transient instead of (N, N, N)."""
    n = a.shape[-1]
    bk = min(n, block_k if block_k is not None else _pow2_block(n))
    nb = -(-n // bk)
    pad = nb * bk - n
    # INF-padded phantom k's can't win the min: INF + x >= INF in f32
    # round-to-nearest, and every real entry is bounded by the diagonal-zero
    # term at INF = 1e9.
    a_p = jnp.pad(a, ((0, 0), (0, pad)), constant_values=INF)
    b_p = jnp.pad(b, ((0, pad), (0, 0)), constant_values=INF)

    def body(acc, k0):
        ab = jax.lax.dynamic_slice_in_dim(a_p, k0, bk, axis=1)   # (N, bk)
        bb = jax.lax.dynamic_slice_in_dim(b_p, k0, bk, axis=0)   # (bk, N)
        acc = jnp.minimum(acc, jnp.min(ab[:, :, None] + bb[None, :, :],
                                       axis=1))
        return acc, None

    init = jnp.full((n, n), INF, dtype=a.dtype)
    out, _ = jax.lax.scan(body, init, jnp.arange(nb, dtype=jnp.int32) * bk)
    return out


def apsp(cost: jnp.ndarray, n_iters: int) -> jnp.ndarray:
    """All-pairs shortest path distances by repeated min-plus squaring.

    ``cost`` must have 0 on the diagonal and INF for absent edges.
    ``n_iters >= ceil(log2(N))`` guarantees convergence. Above DENSE_NMAX
    tiles the k-blocked product runs instead of the one-shot broadcast —
    identical results, memory-safe at 1024+ tiles."""
    n = cost.shape[-1]
    mp = min_plus if n <= DENSE_NMAX else min_plus_blocked

    def body(_, d):
        return mp(d, d)

    return jax.lax.fori_loop(0, n_iters, body, cost)


def next_hop(cost: jnp.ndarray, dist: jnp.ndarray) -> jnp.ndarray:
    """nh[i, j] = first-index argmin_m (cost[i, m] + dist[m, j]).

    Deterministic single-path routing: ties break toward the lowest slot
    index (stands in for ALASH's layered escape-path determinism)."""
    n = cost.shape[0]
    # scores[i, m, j]: go from i to neighbor m then shortest to j. Staying
    # put (m == i, cost 0) must not be a candidate hop.
    step_cost = jnp.where(jnp.eye(n, dtype=bool), INF, cost)
    if n <= DENSE_NMAX:
        scores = step_cost[:, :, None] + dist[None, :, :]
        nh = jnp.argmin(scores, axis=1).astype(jnp.int32)  # (N, N)
    else:
        # j-blocked: per destination block an (N, N, bj) score slab. argmin
        # over axis 1 is independent per j-column, so blocking over j is
        # bit-equal to the one-shot form (same first-index tie-breaking).
        bj = min(n, _pow2_block(n))
        nb = -(-n // bj)
        pad = nb * bj - n
        dist_p = jnp.pad(dist, ((0, 0), (0, pad)), constant_values=INF)

        def body(_, j0):
            db = jax.lax.dynamic_slice_in_dim(dist_p, j0, bj, axis=1)
            sc = step_cost[:, :, None] + db[None, :, :]      # (N, N, bj)
            return None, jnp.argmin(sc, axis=1).astype(jnp.int32)

        _, cols = jax.lax.scan(body, None,
                               jnp.arange(nb, dtype=jnp.int32) * bj)
        nh = jnp.moveaxis(cols, 0, 1).reshape(n, nb * bj)[:, :n]
    eye = jnp.arange(n, dtype=jnp.int32)
    # For i == j route nowhere (stay).
    return jnp.where(jnp.eye(n, dtype=bool), eye[:, None], nh)


@partial(jax.jit, static_argnames=("max_hops",))
def walk_paths(
    nh: jnp.ndarray,          # (N, N) int32 next hops
    link_delay: jnp.ndarray,  # (N, N) wire delay per directed edge
    f: jnp.ndarray,           # (N, N) traffic between SLOTS (already permuted)
    max_hops: int,
):
    """Walk every (src, dst) pair simultaneously, one hop per step, until
    the last pair has arrived or ``max_hops`` steps have run.

    ``max_hops`` is the validity cap: a pair still on its way then (a
    routing loop, or a path longer than the cap) leaves ``all_done`` false.
    A step after a pair has arrived adds exact zeros and leaves the pair
    where it is, so stopping at the last arrival gives the same outputs,
    bit for bit, as walking all ``max_hops`` steps. Under ``vmap`` the
    batch runs until its last pair has arrived, each design keeping its
    own outputs and ``steps``.

    Returns (hops, delay, util, visits, all_done, steps):
      hops   (N, N) — links on the path i->j
      delay  (N, N) — sum of wire delays along the path
      util   (N, N) — f-weighted directed link usage  (Eq. 2 accumulation)
      visits (N,)   — f-weighted router traversals (src router included at
                      each step; dst router added at completion)  (Eq. 8)
      all_done ()   — bool: every pair reached its destination
      steps  ()     — int32: steps walked, the longest path or ``max_hops``
    """
    n = nh.shape[0]
    src = jnp.arange(n, dtype=jnp.int32)[:, None] * jnp.ones((1, n), jnp.int32)
    dst = jnp.arange(n, dtype=jnp.int32)[None, :] * jnp.ones((n, 1), jnp.int32)

    def moving(state):
        t, cur = state[:2]
        return (t < max_hops) & jnp.any(cur != dst)

    def body(state):
        t, cur, hops, delay, util, visits = state
        done = cur == dst
        nxt = nh[cur, dst]
        w = jnp.where(done, 0.0, f)
        util = util.at[cur, nxt].add(w)
        visits = visits.at[cur].add(w)
        delay = delay + jnp.where(done, 0.0, link_delay[cur, nxt])
        hops = hops + jnp.where(done, 0, 1)
        cur = jnp.where(done, cur, nxt)
        return t + 1, cur, hops, delay, util, visits

    # The initial carry, the step counter included, is derived from ``nh``
    # so that it has nh's type, varying manual axes included: under
    # shard_map the body's outputs and the loop's predicate vary over the
    # device axis, and a carry of plain constants would not.
    zero = nh * 0
    zero_f = zero.astype(jnp.float32)
    steps, cur, hops, delay, util, visits = jax.lax.while_loop(
        moving, body, (zero[0, 0], src + zero, zero, zero_f, zero_f,
                       zero_f[0]))
    all_done = jnp.all(cur == dst)
    # Destination router traversal (h hops -> h+1 routers).
    visits = visits + f.sum(axis=0)
    return hops, delay, util, visits, all_done, steps


def routing_tables(cost: jnp.ndarray, n_iters: int):
    """Convenience: (dist, next_hop) from a hop-cost matrix."""
    dist = apsp(cost, n_iters)
    return dist, next_hop(cost, dist)


# ----------------------------------------------------------------- batched
@partial(jax.jit, static_argnames=("n_iters",))
def _apsp_batched_jnp(cost: jnp.ndarray, n_iters: int) -> jnp.ndarray:
    return jax.vmap(lambda c: apsp(c, n_iters))(cost)


_next_hop_batched = jax.jit(jax.vmap(next_hop))


def apsp_batched(
    cost: jnp.ndarray,  # (B, N, N)
    n_iters: int,
    *,
    backend: str | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched APSP over a stack of cost matrices on the selected backend."""
    if resolve_backend(backend) == "pallas":
        from ..kernels import minplus as _minplus  # deferred: keeps core importable sans kernels

        return _minplus.apsp(cost, n_iters, interpret=interpret)
    return _apsp_batched_jnp(cost, n_iters)


def routing_tables_batched(
    cost: jnp.ndarray,  # (B, N, N)
    n_iters: int,
    *,
    backend: str | None = None,
    interpret: bool = False,
):
    """Batched (dist, next_hop). APSP runs on ``backend``; the argmin-based
    next-hop extraction is cheap and always runs on the jnp path."""
    dist = apsp_batched(cost, n_iters, backend=backend, interpret=interpret)
    return dist, _next_hop_batched(cost, dist)


# ----------------------------------------------------- host mirrors + deltas
# Exact numpy twins of the device tables, the substrate of incremental
# per-move evaluation (Evaluator.batch_moves). Bit-parity with the jnp path
# rests on integer exactness: every edge cost is a small integer held in f32
# (router stages + integer wire/TSV delay), so every finite path cost is an
# integer far below 2^24 and every f32 sum/min is exact; unreachable entries
# are exactly INF = 1e9 (itself f32-exact, and 1e9 + small rounds to >= 1e9),
# so *any* correct shortest-path scheme — device min-plus squaring, host
# blocked squaring, Floyd–Warshall, or bounded Bellman relaxation — lands on
# the same bits.


def min_plus_np(a: np.ndarray, b: np.ndarray,
                block_k: int | None = None) -> np.ndarray:
    """(M,N)x(N,N) min-plus product on host, k-blocked, dtype-preserving
    (f32 in -> f32 out, bit-equal to the device formulations; the delta
    path also runs it on the f64 tie-broken tables)."""
    a = np.asarray(a)
    b = np.asarray(b)
    dt = np.result_type(a, b, np.float32)
    m, n = a.shape
    bk = min(n, block_k if block_k is not None else
             _pow2_block(max(int(math.isqrt(m * n)), 1)))
    out = np.full((m, b.shape[1]), INF, dtype=dt)
    for k0 in range(0, n, bk):
        ab = a[:, k0:k0 + bk]                       # (M, bk)
        bb = b[k0:k0 + bk, :]                       # (bk, N)
        np.minimum(out, (ab[:, :, None] + bb[None, :, :]).min(axis=1),
                   out=out)
    return out


def apsp_np(cost: np.ndarray, n_iters: int) -> np.ndarray:
    """Host APSP by blocked min-plus squaring; on f32 input bit-equal to
    :func:`apsp` (dtype-preserving like :func:`min_plus_np`)."""
    d = np.asarray(cost)
    if d.dtype != np.float64:
        d = d.astype(np.float32)
    for _ in range(n_iters):
        d = min_plus_np(d, d)
    return d


def apsp_fw_np(cost: np.ndarray) -> np.ndarray:
    """Host APSP by one in-place Floyd–Warshall pass (N³ once, not the
    squarings' ~N³·log N), dtype-preserving like :func:`apsp_np`. In-place
    is exact: with a zero diagonal and non-negative weights, row and column
    ``k`` do not change during step ``k``. On exact inputs (the note
    above) it lands on :func:`apsp_np`'s bits."""
    d = np.asarray(cost)
    d = d.copy() if d.dtype == np.float64 else d.astype(np.float32)
    for k in range(d.shape[0]):
        np.minimum(d, d[:, k:k + 1] + d[k:k + 1, :], out=d)
    return d


def next_hop_np(cost: np.ndarray, dist: np.ndarray,
                rows: np.ndarray | None = None) -> np.ndarray:
    """Host next-hop extraction, j-blocked; bit-equal to :func:`next_hop`
    (numpy argmin and jnp argmin share first-index tie-breaking).

    ``rows`` restricts the computation to a subset of source rows (the
    delta path rebuilds only touched rows); the diagonal rule nh[i,i] = i
    is applied for whatever rows are produced."""
    cost = np.asarray(cost, dtype=np.float32)
    dist = np.asarray(dist, dtype=np.float32)
    n = cost.shape[0]
    step = np.where(np.eye(n, dtype=bool), np.float32(INF), cost)
    if rows is not None:
        step = step[rows]
    m = step.shape[0]
    nh = np.empty((m, n), dtype=np.int32)
    bj = min(n, _pow2_block(max(int(math.isqrt(m * n)), 1)))
    for j0 in range(0, n, bj):
        sc = step[:, :, None] + dist[None, :, j0:j0 + bj]  # (m, N, bj)
        nh[:, j0:j0 + bj] = sc.argmin(axis=1).astype(np.int32)
    ridx = np.arange(n, dtype=np.int32) if rows is None \
        else np.asarray(rows, dtype=np.int32)
    nh[np.arange(m), ridx] = ridx   # i == j: stay
    return nh


# Tie-breaking perturbations. Shortest paths on NoC meshes are massively
# degenerate (every monotone route ties), which makes "does some shortest
# path use this edge?" a uselessly large dirty test for incremental updates.
# The delta path therefore carries a SHADOW metric with a deterministic
# per-edge perturbation eps in (0, 2^-12): perturbed shortest paths are
# (almost surely) unique, so the dirty set shrinks to pairs whose UNIQUE
# perturbed path uses the edge — a near-minimal superset of the truly
# changed pairs. The shadow is exact integer arithmetic in disguise: edge
# weights are integers < 2^21 plus eps = r·2^-30 (r < 2^18), so any simple
# path's value needs <= 51 mantissa bits — exact in f64 — and its eps-sum
# stays < 1, so floor(perturbed distance) IS the true f32 distance (a path
# with smaller integer weight wins by >= 1 > any eps-sum).
_EPS_SCALE = 2.0 ** -30
_EPS_BITS = 18


@lru_cache(maxsize=8)
def _tie_eps(n: int) -> np.ndarray:
    """(N, N) f32 symmetric per-edge tie-breakers, a fixed deterministic
    function of the slot-pair (NOT of any design), so delta-updated shadow
    tables stay consistent across arbitrary move chains."""
    rng = np.random.default_rng(0x3D0C ^ n)
    r = rng.integers(1, 1 << _EPS_BITS, size=(n, n)).astype(np.float64)
    eps = np.triu(r * _EPS_SCALE, 1)
    return (eps + eps.T).astype(np.float32)


def _nh_cols_sparse(cost: np.ndarray, dist: np.ndarray,
                    cols: np.ndarray) -> np.ndarray:
    """(N, |cols|) next hops for destination columns ``cols``, computed
    from the directed edge list (O(E·C) instead of the dense O(N²·C)).

    Exact full-argmin semantics: for reachable entries only neighbors can
    win (non-neighbor scores are >= INF after f32 rounding, and INF never
    rounds down), and within-group edge order is (i, m) row-major — the
    same first-index tie-break. Entries whose best neighbor score reaches
    INF (disconnected pairs, where the oracle's argmin can land on a
    non-neighbor through INF-rounding ties) are re-done densely."""
    cost = np.asarray(cost, dtype=np.float32)
    dist = np.asarray(dist, dtype=np.float32)
    n = cost.shape[0]
    cols = np.asarray(cols, dtype=np.int64)
    off = ~np.eye(n, dtype=bool)
    ea, eb = np.nonzero((cost < INF / 2) & off)
    if ea.size == 0 or np.unique(ea).size < n:
        # Isolated node(s): no neighbor group to reduce over — dense path.
        return next_hop_np(cost, dist)[:, cols]
    starts = np.searchsorted(ea, np.arange(n))
    w = cost[ea, eb]
    inf32 = np.float32(INF)
    eidx = np.arange(ea.size, dtype=np.int64)
    out = np.empty((n, cols.size), dtype=np.int32)
    bc = max(1, (32 << 20) // (8 * max(ea.size, 1)))
    for j0 in range(0, cols.size, bc):
        js = cols[j0:j0 + bc]
        sc = w[:, None] + dist[eb[:, None], js[None, :]]      # (E, C)
        gmin = np.minimum.reduceat(sc, starts, axis=0)        # (N, C)
        first = np.minimum.reduceat(
            np.where(sc == gmin[ea], eidx[:, None], ea.size), starts, axis=0)
        nhc = eb[first].astype(np.int32)
        bad = gmin >= inf32
        if bad.any():
            bi, bj = np.nonzero(bad)
            step = np.where(off, cost, inf32)
            nhc[bi, bj] = (step[bi] + dist[:, js[bj]].T).argmin(
                axis=1).astype(np.int32)
        out[:, j0:j0 + bc] = nhc
    return out


class HostTables(NamedTuple):
    """Cached host routing state for one adjacency: hop-cost matrix, APSP
    distances, next hops, plus the f64 tie-broken shadow (cost_t, dist_t)
    that powers the incremental delta — the unit of Evaluator's cache."""

    cost: np.ndarray    # (N, N) f32: 0 diag, router+wire on edges, INF absent
    dist: np.ndarray    # (N, N) f32 shortest-path distances
    nh: np.ndarray      # (N, N) int32 first-index-argmin next hops
    cost_t: np.ndarray  # (N, N) f64 cost + per-edge tie-breaker
    dist_t: np.ndarray  # (N, N) f64 perturbed distances; floor == dist

    @property
    def nbytes(self) -> int:
        return (self.cost.nbytes + self.dist.nbytes + self.nh.nbytes
                + self.cost_t.nbytes + self.dist_t.nbytes)


def host_tables(cost: np.ndarray) -> HostTables:
    """Full host recompute — the delta path's fallback and seed. One f64
    Floyd–Warshall pass on the tie-broken costs yields both metrics: dist =
    floor(dist_t) (exact — see the shadow-metric note above), bit-equal to
    the f32 oracle. Next hops come from the edge-list argmin (O(E·N)),
    bit-equal to :func:`next_hop_np`."""
    cost = np.ascontiguousarray(cost, dtype=np.float32)
    n = cost.shape[0]
    edge = (cost < INF / 2) & ~np.eye(n, dtype=bool)
    cost_t = cost.astype(np.float64)
    cost_t[edge] += _tie_eps(n).astype(np.float64)[edge]
    dist_t = apsp_fw_np(cost_t)
    dist = np.floor(dist_t).astype(np.float32)
    nh = _nh_cols_sparse(cost, dist, np.arange(n))
    np.fill_diagonal(nh, np.arange(n))
    return HostTables(cost, dist, nh, cost_t, dist_t)


def delta_link_move(
    t: HostTables,
    rem: tuple[int, int],
    add: tuple[int, int],
    w_add: float,
    *,
    max_dirty_frac: float = 0.5,
    max_iters: int | None = None,
) -> HostTables | None:
    """Incremental tables after moving one undirected link: remove edge
    ``rem``, add edge ``add`` with hop cost ``w_add``. Bit-equal to a full
    recompute on the new cost matrix, or ``None`` when the delta bound is
    exceeded (too many touched rows/columns, or the relaxation cap is hit)
    and the caller must fall back to :func:`host_tables`.

    Three exact phases, the first two on the f64 shadow metric (unique
    perturbed shortest paths — see the tie-breaker note above):

    1. *Removal.* A pair (i, j) lengthens only if its unique perturbed
       shortest path used the removed edge: dist_t[i,j] == dist_t[i,a] +
       w_t + dist_t[b,j] (either orientation) — on tie-degenerate meshes
       this dirty set is tiny (the edge's unique-path betweenness), where
       the unperturbed test would flag most of the matrix. Dirty entries
       re-converge by sparse Jacobi–Bellman relaxation on the new shadow
       cost (an (E, N) gather per sweep, E = #dirty entries): they restart
       at INF while clean entries keep their (still exact) base value as
       the upper bound; every iterate stays >= the true distance, so the
       fixpoint is the true distance, in <= N-1 sweeps.
    2. *Addition.* A shortest path uses a new edge at most once, so
       dist'' = min(dist', dist'[:,c] + w_t + dist'[e,:], and symmetric) in
       closed form. The true f32 distances then drop out as
       floor(dist_t) — exact, bit-equal to the oracle.
    3. *Next hops.* nh[i,j] = argmin_m step[i,m] + dist[m,j] (f32 metric)
       can only change where its inputs changed: rows {a, b, c, e} (their
       step-cost row changed) and columns j whose f32 dist column changed.
       Everything else is an argmin over bit-identical arrays — unchanged
       by construction, including first-index ties."""
    n = t.cost.shape[0]
    a, b = int(rem[0]), int(rem[1])
    c, e = int(add[0]), int(add[1])
    eps = _tie_eps(n)
    cost2 = t.cost.copy()
    cost2[a, b] = cost2[b, a] = np.float32(INF)
    cost2[c, e] = cost2[e, c] = np.float32(w_add)
    w2t = np.float64(np.float32(w_add)) + np.float64(eps[c, e])
    cost2_t = t.cost_t.copy()
    cost2_t[a, b] = cost2_t[b, a] = np.float64(INF)
    cost2_t[c, e] = cost2_t[e, c] = w2t

    # Phase 1 — removal (shadow metric).
    dt = t.dist_t
    w_rem_t = t.cost_t[a, b]
    via_ab = dt[:, a:a + 1] + (w_rem_t + dt[b])[None, :]
    via_ba = dt[:, b:b + 1] + (w_rem_t + dt[a])[None, :]
    dirty = (dt == via_ab) | (dt == via_ba)
    di, dj = np.nonzero(dirty)
    dt2 = dt
    if di.size:
        # Entry bound: the dirty count is the removed edge's unique-path
        # betweenness. The byte term caps the (E, N) gather slab.
        if di.size > min(max_dirty_frac * n * n, (256 << 20) // (8 * n)):
            return None
        cap = max_iters if max_iters is not None else n
        dt2 = dt.copy()
        dt2[di, dj] = np.float64(INF)
        cost_cols = np.ascontiguousarray(cost2_t[:, dj].T)   # (E, N)
        cur = dt2[di, dj]
        for _ in range(cap):
            cand = (dt2[di, :] + cost_cols).min(axis=1)
            nd = np.minimum(cur, cand)
            if np.array_equal(nd, cur):
                break
            dt2[di, dj] = nd
            cur = nd
        else:
            return None

    # Phase 2 — addition (closed form; 1e9 + x never rounds below 1e9, so
    # unreachable-through-the-new-edge candidates can't fake a finite path).
    via_c = dt2[:, c:c + 1] + (w2t + dt2[e])[None, :]
    via_e = dt2[:, e:e + 1] + (w2t + dt2[c])[None, :]
    dt3 = np.minimum(dt2, np.minimum(via_c, via_e))
    dist3 = np.floor(dt3).astype(np.float32)

    # Phase 3 — targeted next-hop rebuild (f32 metric): full rows for the
    # four endpoints (their step-cost row changed), changed columns via the
    # sparse edge-list argmin (O(E·C); a long added link can genuinely
    # shortcut many pairs, so C is not assumed small).
    changed_cols = np.flatnonzero((dist3 != t.dist).any(axis=0))
    nh2 = t.nh.copy()
    touched_rows = np.unique(np.array([a, b, c, e], dtype=np.int32))
    nh2[touched_rows] = next_hop_np(cost2, dist3, rows=touched_rows)
    if changed_cols.size:
        nh2[:, changed_cols] = _nh_cols_sparse(cost2, dist3, changed_cols)
        nh2[changed_cols, changed_cols] = changed_cols.astype(np.int32)
    return HostTables(cost2, dist3, nh2, cost2_t, dt3)
