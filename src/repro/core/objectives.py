"""Analytical design objectives — Eqs. 1-10 of the paper, in JAX.

Five objectives, all minimized (paper Eq. 11):

    index 0  umean  — mean expected link utilization, Eq. 3   (throughput proxy)
    index 1  ustd   — std of link utilization,        Eq. 4   (throughput proxy)
    index 2  lat    — average CPU<->LLC latency,      Eq. 1
    index 3  energy — router + link energy,           Eqs. 8-10
    index 4  temp   — thermal metric T,               Eqs. 5-7

The models only need *relative* fidelity — "accurate in determining which
designs are better relative to one another" (paper §4.2.5) — so the physical
constants below are documented stand-ins for the paper's 3D-ICE / PrimePower
calibration (tools unavailable offline; DESIGN.md §5).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from . import routing
from .problem import SystemSpec

OBJ_NAMES = ("umean", "ustd", "lat", "energy", "temp")
N_OBJ = len(OBJ_NAMES)

# Optimization cases (paper §6.2 and §6.5), as objective-index tuples.
CASES: dict[str, tuple[int, ...]] = {
    "case1": (0, 1),            # {U, sigma}
    "case2": (0, 1, 2),         # + Lat
    "case3": (0, 1, 2, 3),      # + E        ("network efficiency / perf")
    "case4": (4,),              # {T}        (thermal-only)
    "case5": (0, 1, 2, 3, 4),   # + T        (joint perf-thermal)
}

# ----------------------------------------------------------------- constants
E_ROUTER_PORT = 1.0     # router logic energy per flit per port (rel. pJ), Eq. 8
E_PLANAR_MM = 0.6       # planar wire energy per flit per tile pitch,     Eq. 9
E_VERTICAL = 0.3        # TSV energy per flit,                            Eq. 9
R_LAYER = 0.25          # vertical thermal resistance R_j (K/W),          Eq. 5
R_BASE = 2.0            # base-layer thermal resistance R_b (K/W),        Eq. 5
T_AMBIENT = 45.0        # coolant/ambient reference (deg C), reporting only


class SpecConsts(NamedTuple):
    """Static per-spec arrays, device-resident for the jitted evaluator."""

    vadj: jnp.ndarray          # (N, N) bool vertical links
    link_delay: jnp.ndarray    # (N, N) wire delay
    manhattan: jnp.ndarray     # (N, N) planar length
    core_types: jnp.ndarray    # (Ncores,) int
    core_power: jnp.ndarray    # (Ncores,) float
    column: jnp.ndarray        # (N,) column (single-tile-stack) id per slot
    layer: jnp.ndarray         # (N,) layer id per slot (0 = at the sink)
    n_cpu: int
    n_llc: int
    router_stages: int
    max_hops: int
    n_links: int
    apsp_iters: int
    n_columns: int
    n_layers: int


@functools.lru_cache(maxsize=64)
def make_consts(spec: SystemSpec) -> SpecConsts:
    col = spec.coords[:, 1] * spec.ny + spec.coords[:, 2]
    return SpecConsts(
        vadj=jnp.asarray(spec.vertical_adj),
        link_delay=jnp.asarray(spec.link_delay, jnp.float32),
        manhattan=jnp.asarray(spec.manhattan, jnp.float32),
        core_types=jnp.asarray(spec.core_types),
        core_power=jnp.asarray(spec.core_power, jnp.float32),
        column=jnp.asarray(col, jnp.int32),
        layer=jnp.asarray(spec.layer_of_slot, jnp.int32),
        n_cpu=spec.n_cpu,
        n_llc=spec.n_llc,
        router_stages=spec.router_stages,
        max_hops=spec.max_hops,
        n_links=spec.n_links,
        apsp_iters=routing.apsp_iters(spec.n_tiles),
        n_columns=spec.tiles_per_layer,
        n_layers=spec.n_layers,
    )


def design_cost(c: SpecConsts, adj: jnp.ndarray) -> jnp.ndarray:
    """(N, N) hop-cost matrix of a design: router pipeline + wire delay on
    present links, INF on absent ones, 0 on the diagonal. The batched
    evaluator stacks these and runs APSP through the selected routing
    backend (core.routing.routing_tables_batched)."""
    n = adj.shape[-1]
    full_adj = adj | c.vadj
    cost = jnp.where(full_adj, c.router_stages + c.link_delay, routing.INF)
    return jnp.where(jnp.eye(n, dtype=bool), 0.0, cost)


def design_cost_np(spec: SystemSpec, adj: np.ndarray) -> np.ndarray:
    """Host twin of :func:`design_cost` — bit-identical f32 hop costs (the
    entries are small integers, exact in f32 on both paths). Shared by the
    flit simulator's table builder and Evaluator's incremental delta path."""
    n = spec.n_tiles
    full_adj = np.asarray(adj, dtype=bool) | spec.vertical_adj
    cost = np.where(
        full_adj,
        np.float32(spec.router_stages) + spec.link_delay.astype(np.float32),
        np.float32(routing.INF),
    ).astype(np.float32)
    np.fill_diagonal(cost, np.float32(0.0))
    return cost


def evaluate_design(
    c: SpecConsts,
    perm: jnp.ndarray,   # (N,) slot -> core id
    adj: jnp.ndarray,    # (N, N) bool planar links
    f: jnp.ndarray,      # (Ncores, Ncores) traffic between CORES
):
    """All five objectives + validity for one design. jit/vmap friendly.

    Single-design reference path: routing tables are computed inline with
    the jnp oracle. The Evaluator hot loop instead batches APSP across the
    candidate set (optionally on the Pallas backend) and calls
    :func:`evaluate_with_tables`."""
    cost = design_cost(c, adj)
    dist, nh = routing.routing_tables(cost, c.apsp_iters)
    return evaluate_with_tables(c, perm, adj, f, dist, nh)


def evaluate_with_tables(
    c: SpecConsts,
    perm: jnp.ndarray,   # (N,) slot -> core id
    adj: jnp.ndarray,    # (N, N) bool planar links
    f: jnp.ndarray,      # (Ncores, Ncores) traffic between CORES
    dist: jnp.ndarray,   # (N, N) APSP distances for this design
    nh: jnp.ndarray,     # (N, N) int32 next hops for this design
):
    """Objectives given precomputed routing tables (Eqs. 1-10). ``aux``
    holds ``connected``, ``net_lat`` and ``walk_steps``, the steps the path
    walk ran (``routing.walk_paths``)."""
    n = perm.shape[0]
    full_adj = adj | c.vadj
    # Traffic between SLOTS under this placement.
    f_slots = f[perm][:, perm] * (1.0 - jnp.eye(n))

    # ---- routing ---------------------------------------------------- Eq. 1
    hops, delay, util_d, visits, all_done, steps = routing.walk_paths(
        nh, c.link_delay, f_slots.astype(jnp.float32), c.max_hops
    )
    connected = jnp.all(dist < routing.INF / 2) & all_done

    # ---- Eq. 1: CPU<->LLC latency ------------------------------------------
    slot_type = c.core_types[perm]                       # type at each slot
    is_cpu = slot_type == 0
    is_llc = slot_type == 1
    pair_cpu_llc = (is_cpu[:, None] & is_llc[None, :]) | (
        is_llc[:, None] & is_cpu[None, :]
    )
    lat_terms = (c.router_stages * hops + delay) * f_slots
    lat = jnp.sum(jnp.where(pair_cpu_llc, lat_terms, 0.0)) / (
        c.n_cpu * c.n_llc
    )

    # ---- Eqs. 2-4: link-utilization mean / std -----------------------------
    # U_k for an undirected link = traffic in both directions.
    util_u = util_d + util_d.T
    upper = jnp.triu(jnp.ones((n, n), dtype=bool), 1)
    link_mask = full_adj & upper
    umean = jnp.sum(jnp.where(link_mask, util_u, 0.0)) / c.n_links
    uvar = jnp.sum(jnp.where(link_mask, (util_u - umean) ** 2, 0.0)) / c.n_links
    ustd = jnp.sqrt(uvar + 1e-12)

    # ---- Eqs. 8-10: energy --------------------------------------------------
    degree = jnp.sum(full_adj, axis=1) + 1               # +1 local port
    e_router = E_ROUTER_PORT * jnp.sum(visits * degree)
    planar = adj & ~c.vadj
    e_planar = E_PLANAR_MM * jnp.sum(
        jnp.where(planar, util_u * c.manhattan, 0.0)
    ) / 2.0  # each undirected link counted twice in the (N,N) sum
    e_vert = E_VERTICAL * jnp.sum(jnp.where(c.vadj, util_u, 0.0)) / 2.0
    energy = e_router + e_planar + e_vert

    # ---- Eqs. 5-7: thermal --------------------------------------------------
    power_slot = c.core_power[perm]
    p_stack = jnp.zeros((c.n_columns, c.n_layers), jnp.float32)
    p_stack = p_stack.at[c.column, c.layer].add(power_slot)
    # layer index i counted 1..K from the sink -> weight i*R_LAYER + R_BASE.
    i_idx = jnp.arange(1, c.n_layers + 1, dtype=jnp.float32)
    weighted = p_stack * (i_idx * R_LAYER + R_BASE)[None, :]
    t_nk = jnp.cumsum(weighted, axis=1)                  # Eq. 5 (T_{n,k})
    dT_k = jnp.max(t_nk, axis=0) - jnp.min(t_nk, axis=0)  # Eq. 6
    temp = jnp.max(t_nk) * jnp.max(dT_k)                 # Eq. 7

    objs = jnp.stack([umean, ustd, lat, energy, temp])
    objs = jnp.where(connected, objs, jnp.full((N_OBJ,), routing.INF))

    # Network-wide average packet latency (all pairs, f-weighted) — used for
    # the paper's network-EDP metric (§6.1), not as a search objective.
    total_f = jnp.sum(f_slots) + 1e-12
    net_lat = jnp.sum((c.router_stages * hops + delay) * f_slots) / total_f
    aux = {"connected": connected, "net_lat": net_lat, "walk_steps": steps}
    return objs, aux


def peak_temperature_celsius(c: SpecConsts, perm: np.ndarray) -> float:
    """Reporting helper (Fig. 10c): peak core temperature in deg C."""
    power_slot = np.asarray(c.core_power)[np.asarray(perm)]
    p = np.zeros((c.n_columns, c.n_layers))
    np.add.at(p, (np.asarray(c.column), np.asarray(c.layer)), power_slot)
    i_idx = np.arange(1, c.n_layers + 1)
    t_nk = np.cumsum(p * (i_idx * R_LAYER + R_BASE)[None, :], axis=1)
    return float(T_AMBIENT + t_nk.max())
