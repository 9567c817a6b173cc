"""Batched design evaluation — the optimizer's compute hot loop.

The paper evaluates candidates one at a time on a Xeon; we reformulate the
whole objective stack (routing + Eqs. 1-10) as a fixed-shape JAX program and
evaluate entire neighborhoods in one jitted, vmapped batch (DESIGN.md §4).

The routing hot spot (batched APSP) is threaded through the backend switch
in core.routing: ``Evaluator(spec, f, backend="auto"|"jnp"|"pallas")``. On
TPU the blocked Pallas min-plus kernel (kernels/minplus.apsp) serves the
whole candidate batch without materializing the (N, N, N) jnp broadcast per
design; the jnp path is the oracle and the CPU execution path. The rest of
the objective stack (path walk + Eqs. 1-10) stays one jitted vmap over the
batch, consuming the precomputed (dist, next-hop) tables.
"""

from __future__ import annotations

import contextlib
import contextvars
from collections import OrderedDict
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import routing
from .objectives import (N_OBJ, SpecConsts, design_cost, design_cost_np,
                         evaluate_with_tables, make_consts)
from .problem import Design, NeighborMoves, SystemSpec

DELTA_MODES = ("auto", "on", "off")

#: ``delta="auto"`` switches move evaluation to incremental host tables at
#: this tile count. Below it (all paper specs: 8-64 tiles) the dense jitted
#: batch is faster than any host round-trip and stays the only path.
DELTA_AUTO_MIN_TILES = 128

#: Transient budget for one batched-APSP dispatch — bounds the (B, N, N, N)
#: (or k-blocked) broadcast by shrinking the chunk size as N grows.
_BATCH_BUDGET_BYTES = 512 << 20

#: ambient SPMD mesh — set via :func:`spmd_scope`; Evaluators constructed
#: inside the scope run their batch pipeline as one shard_map program over
#: it (the same contextvar-at-construction pattern as repro.dist.worker's
#: cooperative deadline).
_SPMD_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "repro_core_spmd_mesh", default=None)


@contextlib.contextmanager
def spmd_scope(mesh):
    """Evaluators constructed inside this scope shard their candidate
    batches across ``mesh`` (a 1-D jax.sharding.Mesh): cost build → batched
    APSP → objective walk run as ONE multi-device program per dispatch,
    each device serving batch/ndev candidates. This is how the distributed
    executor (repro.dist.worker, ``executor="spmd"``) turns a chain batch
    into a single multi-device dispatch instead of per-device processes."""
    token = _SPMD_MESH.set(mesh)
    try:
        yield
    finally:
        _SPMD_MESH.reset(token)


def make_spmd_mesh():
    """1-D mesh over every visible device (axis ``"dev"``)."""
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), ("dev",))


def batch_pipeline(consts: SpecConsts, backend: str, interpret: bool,
                   perms, adjs, f):
    """Cost build → batched APSP on ``backend`` → objective walk for a
    (B, N) / (B, N, N) candidate batch: the traceable body of one
    evaluation dispatch (the SPMD path runs it per device shard)."""
    costs = jax.vmap(partial(design_cost, consts))(adjs)
    dist, nh = routing.routing_tables_batched(
        costs, consts.apsp_iters, backend=backend, interpret=interpret)
    return jax.vmap(partial(evaluate_with_tables, consts),
                    in_axes=(0, 0, None, 0, 0))(perms, adjs, f, dist, nh)


class Evaluator:
    """Jitted batched evaluator for a fixed (spec, traffic) pair.

    Batches are padded to the next power of two to bound recompiles.

    ``backend`` selects the batched-APSP implementation (see core.routing):
    ``"auto"`` (default) resolves to the Pallas kernel on TPU and jnp
    elsewhere. ``interpret=True`` forces the Pallas kernel through the
    interpreter — CPU-only correctness testing of the TPU path."""

    def __init__(self, spec: SystemSpec, f: np.ndarray, *,
                 backend: str = "auto", interpret: bool = False,
                 max_batch: int | None = 256, delta: str = "auto",
                 table_cache_bytes: int = 256 << 20):
        if delta not in DELTA_MODES:
            raise ValueError(f"delta must be one of {DELTA_MODES}, got {delta!r}")
        self.spec = spec
        self.backend = routing.resolve_backend(backend)
        self.interpret = interpret
        n = spec.n_tiles
        if max_batch is not None:
            # Chunk bound for the batched-APSP transient: at 64 tiles a
            # 256-design chunk broadcasts 256 MiB; at 256+ tiles the same
            # chunk would be gigabytes, so the bound shrinks with N.
            per = 4 * n * n * (n if n <= routing.DENSE_NMAX
                               else routing._pow2_block(n))
            max_batch = max(1, min(max_batch, _BATCH_BUDGET_BYTES // per))
        self.max_batch = max_batch
        self.consts: SpecConsts = make_consts(spec)
        self.f = jnp.asarray(f, jnp.float32)
        self._cost_fn = jax.jit(jax.vmap(partial(design_cost, self.consts)))
        self._eval_fn = jax.jit(
            jax.vmap(partial(evaluate_with_tables, self.consts),
                     in_axes=(0, 0, None, 0, 0))
        )
        self.mesh = _SPMD_MESH.get()
        self._spmd_fn = (self._build_spmd_fn() if self.mesh is not None
                         else None)
        # Incremental move evaluation (batch_moves): swap candidates reuse
        # the base design's tables verbatim (adjacency is slot-keyed, a swap
        # only permutes cores); link moves get an O(N²) table delta
        # (routing.delta_link_move) instead of a full APSP. Forced off under
        # SPMD — the shard_map pipeline recomputes tables on device.
        self.delta_mode = delta
        self.delta_on = (self._spmd_fn is None
                         and (delta == "on" or (delta == "auto"
                              and n >= DELTA_AUTO_MIN_TILES)))
        self._tab_cache: OrderedDict[bytes, routing.HostTables] = OrderedDict()
        self._tab_cache_nbytes = 0
        self._tab_cache_max_bytes = int(table_cache_bytes)
        self.delta_stats = {"swap": 0, "delta": 0, "fallback": 0,
                            "table_hits": 0, "table_misses": 0}
        self.n_evals = 0  # evaluation counter (search-cost accounting)
        self.n_calls = 0  # XLA dispatches (batching-efficiency accounting)

    def _build_spmd_fn(self):
        """One jitted shard_map program for the whole batch pipeline: each
        device runs cost → APSP → objective walk on its batch shard; the
        traffic matrix rides in replicated. Numerically identical to the
        single-device path — sharding the batch axis splits independent
        per-design programs, it reorders no reductions."""
        from jax.sharding import PartitionSpec as P

        local_fn = partial(batch_pipeline, self.consts, self.backend,
                           self.interpret)
        p = P(self.mesh.axis_names[0])
        # The Pallas interpreter cannot trace under the varying-axes check
        # (its index arithmetic is not marked varying); compiled kernels can.
        return jax.jit(jax.shard_map(local_fn, mesh=self.mesh,
                                     in_specs=(p, p, P()), out_specs=(p, p),
                                     check_vma=not self.interpret))

    # ------------------------------------------------------------- single
    def __call__(self, d: Design) -> np.ndarray:
        return self.batch([d])[0]

    # -------------------------------------------------------------- batch
    def batch(self, designs: list[Design]) -> np.ndarray:
        """(B, 5) objective rows; invalid designs come back as +INF rows."""
        return self.batch_aux(designs)[0]

    def batch_aux(self, designs: list[Design]) -> tuple[np.ndarray, dict]:
        if not designs:
            return np.zeros((0, N_OBJ)), {"net_lat": np.zeros((0,))}
        if self.max_batch is not None and len(designs) > self.max_batch:
            # Bound the transient (chunk, N, N, N) min-plus broadcast when a
            # multi-chain driver concatenates many neighborhoods.
            outs, auxes = zip(*(
                self.batch_aux(designs[i:i + self.max_batch])
                for i in range(0, len(designs), self.max_batch)))
            return (np.concatenate(outs, axis=0),
                    {k: np.concatenate([a[k] for a in auxes], axis=0)
                     for k in auxes[0]})
        b = len(designs)
        pad = 1 << max(0, (b - 1).bit_length())
        if self._spmd_fn is not None:
            # shard_map needs the batch divisible by the device count; pad
            # further (still outside the jit — same shape-cache discipline).
            ndev = self.mesh.devices.size
            if pad % ndev:
                pad = -(-pad // ndev) * ndev
        perms = np.stack([d.perm for d in designs] + [designs[-1].perm] * (pad - b))
        adjs = np.stack([d.adj for d in designs] + [designs[-1].adj] * (pad - b))
        perms_j, adjs_j = jnp.asarray(perms), jnp.asarray(adjs)
        if self._spmd_fn is not None:
            objs, aux = self._spmd_fn(perms_j, adjs_j, self.f)
        else:
            costs = self._cost_fn(adjs_j)
            dist, nh = routing.routing_tables_batched(
                costs, self.consts.apsp_iters,
                backend=self.backend, interpret=self.interpret)
            objs, aux = self._eval_fn(perms_j, adjs_j, self.f, dist, nh)
        self.n_evals += b
        self.n_calls += 1
        aux = {k: np.asarray(v[:b]) for k, v in aux.items()}
        return np.asarray(objs[:b], dtype=np.float64), aux

    # -------------------------------------------------------------- moves
    def batch_moves(self, moves) -> np.ndarray:
        """(B, 5) objective rows for one or more :class:`NeighborMoves`
        neighborhoods (rows concatenate in neighborhood order, candidates in
        ``materialize`` order: swaps, then link moves).

        With deltas off this is exactly ``batch(materialize_all())`` — same
        numerics, same dispatch/eval accounting. With deltas on, routing
        tables come from the host cache: swaps reuse the base tables
        unchanged, link moves pay one O(N²) incremental update
        (full host recompute as fallback), and only the objective walk runs
        on device. Both paths are bit-equal — see routing's host-mirror
        exactness note."""
        mvs = [moves] if isinstance(moves, NeighborMoves) else list(moves)
        mvs = [m for m in mvs if len(m)]
        if not mvs:
            return np.zeros((0, N_OBJ))
        if not self.delta_on:
            return self.batch([d for m in mvs for d in m.materialize_all()])
        perms, adjs, dists, nhs = [], [], [], []
        for mv in mvs:
            t0 = self._host_tables(mv.base)
            for s in range(mv.swaps.shape[0]):
                a, b = int(mv.swaps[s, 0]), int(mv.swaps[s, 1])
                p = mv.base.perm.copy()
                p[a], p[b] = p[b], p[a]
                perms.append(p)
                adjs.append(mv.base.adj)
                dists.append(t0.dist)
                nhs.append(t0.nh)
                self.delta_stats["swap"] += 1
            for k in range(mv.rem.shape[0]):
                rem = (int(mv.rem[k, 0]), int(mv.rem[k, 1]))
                add = (int(mv.add[k, 0]), int(mv.add[k, 1]))
                t = self._moved_tables(t0, rem, add)
                adj2 = mv.base.adj.copy()
                adj2[rem[0], rem[1]] = adj2[rem[1], rem[0]] = False
                adj2[add[0], add[1]] = adj2[add[1], add[0]] = True
                perms.append(mv.base.perm)
                adjs.append(adj2)
                dists.append(t.dist)
                nhs.append(t.nh)
        return self._eval_from_tables(perms, adjs, dists, nhs)

    def note_accept(self, mv: NeighborMoves, j: int) -> None:
        """Tell the evaluator candidate ``j`` of ``mv`` was accepted: cache
        the winner's host tables (one delta from the already-cached base) so
        the next step's neighborhood starts from a cache hit. No-op when
        deltas are off or the winner is a swap (same adjacency)."""
        if not self.delta_on:
            return
        s = mv.swaps.shape[0]
        if j < s:
            return
        k = j - s
        rem = (int(mv.rem[k, 0]), int(mv.rem[k, 1]))
        add = (int(mv.add[k, 0]), int(mv.add[k, 1]))
        adj2 = mv.base.adj.copy()
        adj2[rem[0], rem[1]] = adj2[rem[1], rem[0]] = False
        adj2[add[0], add[1]] = adj2[add[1], add[0]] = True
        key = np.packbits(adj2).tobytes()
        if key in self._tab_cache:
            self._tab_cache.move_to_end(key)
            return
        t = self._moved_tables(self._host_tables(mv.base), rem, add)
        self._tab_put(key, t)

    def _host_tables(self, base: Design) -> routing.HostTables:
        key = np.packbits(base.adj).tobytes()
        t = self._tab_cache.get(key)
        if t is not None:
            self._tab_cache.move_to_end(key)
            self.delta_stats["table_hits"] += 1
            return t
        self.delta_stats["table_misses"] += 1
        t = routing.host_tables(design_cost_np(self.spec, base.adj),
                                self.consts.apsp_iters)
        self._tab_put(key, t)
        return t

    def _moved_tables(self, t0: routing.HostTables, rem, add
                      ) -> routing.HostTables:
        w = (np.float32(self.spec.router_stages)
             + np.float32(self.spec.link_delay[add[0], add[1]]))
        t = routing.delta_link_move(t0, rem, add, w)
        if t is None:
            self.delta_stats["fallback"] += 1
            cost2 = t0.cost.copy()
            cost2[rem[0], rem[1]] = cost2[rem[1], rem[0]] = np.float32(routing.INF)
            cost2[add[0], add[1]] = cost2[add[1], add[0]] = w
            return routing.host_tables(cost2, self.consts.apsp_iters)
        self.delta_stats["delta"] += 1
        return t

    def _tab_put(self, key: bytes, t: routing.HostTables) -> None:
        old = self._tab_cache.pop(key, None)
        if old is not None:
            self._tab_cache_nbytes -= old.nbytes
        self._tab_cache[key] = t
        self._tab_cache_nbytes += t.nbytes
        while (self._tab_cache_nbytes > self._tab_cache_max_bytes
               and len(self._tab_cache) > 1):
            _, evicted = self._tab_cache.popitem(last=False)
            self._tab_cache_nbytes -= evicted.nbytes

    def _eval_from_tables(self, perms, adjs, dists, nhs) -> np.ndarray:
        """Dispatch the objective walk over candidates with precomputed
        routing tables — chunked by ``max_batch``, padded to the next power
        of two (the same shape-cache discipline as ``batch_aux``); the same
        eval/dispatch counters apply."""
        out = []
        step = self.max_batch if self.max_batch is not None else len(perms)
        for i in range(0, len(perms), step):
            b = len(perms[i:i + step])
            pad = 1 << max(0, (b - 1).bit_length())
            sl = slice(i, i + b)
            tail = pad - b
            pj = jnp.asarray(np.stack(perms[sl] + [perms[i + b - 1]] * tail))
            aj = jnp.asarray(np.stack(adjs[sl] + [adjs[i + b - 1]] * tail))
            dj = jnp.asarray(np.stack(dists[sl] + [dists[i + b - 1]] * tail))
            nj = jnp.asarray(np.stack(nhs[sl] + [nhs[i + b - 1]] * tail))
            objs, _ = self._eval_fn(pj, aj, self.f, dj, nj)
            self.n_evals += b
            self.n_calls += 1
            out.append(np.asarray(objs[:b], dtype=np.float64))
        return np.concatenate(out, axis=0)

    # ---------------------------------------------------------------- EDP
    def edp(self, d: Design) -> float:
        """Network EDP = network latency x network energy (paper §6.1; the
        analytic variant — core/netsim.py provides the simulated one)."""
        objs, aux = self.batch_aux([d])
        return float(aux["net_lat"][0] * objs[0, 3])
