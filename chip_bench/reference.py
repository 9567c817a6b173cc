"""Plain reference of the design objectives and the normalized hypervolume.

A straightforward NumPy restatement of the paper's analytical model
(Joardar et al., arXiv:1810.08869, Eqs. 1-10) that imports nothing of the
program under test. It takes the configuration's tile spec (the numbers in
``configs/<name>.json``), the traffic matrix of the problem, and a design
(placement permutation + planar adjacency), and returns the five objective
rows the evaluator must reproduce:

    0 umean   mean link utilization (Eq. 3)
    1 ustd    std of link utilization (Eq. 4)
    2 lat     CPU<->LLC latency (Eq. 1)
    3 energy  router + planar wire + TSV energy (Eqs. 8-10)
    4 temp    thermal metric (Eqs. 5-7)

Routing is the deterministic minimum-latency shortest path with
lowest-index tie-breaking: Floyd-Warshall distances, next hop
``argmin_m step[i, m] + dist[m, j]`` (first index wins), then a walk of
every (src, dst) pair for at most ``max_hops`` hops. A design whose graph is
disconnected or whose paths need more than ``max_hops`` hops is infeasible
and gets an all-``INF`` row.

``dtype`` selects the precision every stored intermediate is rounded to:
float64 (the reference) or bfloat16 (the control, one precision step below
the evaluator's float32).
"""

from __future__ import annotations

import numpy as np

INF = 1.0e9

# Model constants of the objectives (the paper's relative-fidelity stand-ins
# for 3D-ICE / PrimePower calibration, as the system documents them).
E_ROUTER_PORT = 1.0
E_PLANAR_MM = 0.6
E_VERTICAL = 0.3
R_LAYER = 0.25
R_BASE = 2.0
CORE_POWER = (2.0, 0.8, 3.0)   # CPU, LLC, GPU (W)

CASES = {"case1": (0, 1), "case2": (0, 1, 2), "case3": (0, 1, 2, 3),
         "case4": (4,), "case5": (0, 1, 2, 3, 4)}


class Geometry:
    """Slot geometry and core attributes of one tile spec."""

    def __init__(self, spec: dict):
        nx, ny, nl = spec["nx"], spec["ny"], spec["n_layers"]
        self.nx, self.ny, self.n_layers = nx, ny, nl
        self.n = n = nx * ny * nl
        self.n_cpu, self.n_llc = spec["n_cpu"], spec["n_llc"]
        self.router_stages = spec.get("router_stages", 3)
        self.max_hops = spec.get("max_hops", 24)
        slots = np.arange(n)
        self.layer = slots // (nx * ny)
        self.x = (slots % (nx * ny)) // ny
        self.y = slots % ny
        self.column = self.x * ny + self.y
        tpl = nx * ny
        self.vadj = np.zeros((n, n), dtype=bool)
        for s in range(n - tpl):
            self.vadj[s, s + tpl] = self.vadj[s + tpl, s] = True
        self.manhattan = (np.abs(self.x[:, None] - self.x[None, :])
                          + np.abs(self.y[:, None] - self.y[None, :])
                          ).astype(np.float64)
        same_layer = self.layer[:, None] == self.layer[None, :]
        planar_ok = same_layer & ~np.eye(n, dtype=bool)
        self.delay = np.where(planar_ok, self.manhattan, 0.0)
        self.delay = np.where(self.vadj, 1.0, self.delay)
        self.core_type = np.array([0] * self.n_cpu + [1] * self.n_llc
                                  + [2] * (n - self.n_cpu - self.n_llc))
        self.core_power = np.array([CORE_POWER[t] for t in self.core_type])
        planar = (nx * (ny - 1) + ny * (nx - 1)) * nl
        self.n_links = planar + tpl * (nl - 1)

    def mesh(self) -> tuple[np.ndarray, np.ndarray]:
        """The 3D-mesh design (identity placement, nearest-neighbour links)."""
        n, ny = self.n, self.ny
        adj = np.zeros((n, n), dtype=bool)
        for s in range(n):
            if self.y[s] + 1 < ny:
                adj[s, s + 1] = adj[s + 1, s] = True
            if self.x[s] + 1 < self.nx:
                adj[s, s + ny] = adj[s + ny, s] = True
        return np.arange(n), adj


def _rounder(dtype):
    if np.dtype(dtype) == np.float64:
        return lambda a: np.asarray(a, dtype=np.float64)
    return lambda a: np.asarray(a).astype(dtype).astype(np.float64)


def _shortest_paths(geo: Geometry, adj: np.ndarray):
    full = adj | geo.vadj
    cost = np.where(full, geo.router_stages + geo.delay, INF)
    np.fill_diagonal(cost, 0.0)
    dist = cost.copy()
    for k in range(geo.n):           # Floyd-Warshall
        np.minimum(dist, dist[:, k:k + 1] + dist[k:k + 1, :], out=dist)
    step = cost.copy()
    np.fill_diagonal(step, INF)       # staying put is not a hop
    nh = np.empty((geo.n, geo.n), dtype=np.int64)
    for i in range(geo.n):            # first-index argmin over neighbours m
        nh[i] = np.argmin(step[i][:, None] + dist, axis=0)
    nh[np.arange(geo.n), np.arange(geo.n)] = np.arange(geo.n)
    return full, dist, nh


def objectives(geo: Geometry, f: np.ndarray, perm: np.ndarray,
               adj: np.ndarray, dtype=np.float64) -> np.ndarray:
    """(5,) objective row of one design; all-INF when infeasible."""
    r = _rounder(dtype)
    n = geo.n
    perm = np.asarray(perm, dtype=np.int64)
    adj = np.asarray(adj, dtype=bool)
    full, dist, nh = _shortest_paths(geo, adj)
    fs = r(np.asarray(f, dtype=np.float64)[perm][:, perm])
    np.fill_diagonal(fs, 0.0)

    src = np.repeat(np.arange(n), n)
    dst = np.tile(np.arange(n), n)
    w = fs.reshape(-1)
    cur = src.copy()
    hops = np.zeros(n * n)
    dsum = np.zeros(n * n)
    util = np.zeros(n * n)
    visits = np.zeros(n)
    for _ in range(geo.max_hops):
        live = cur != dst
        if not live.any():
            break
        c, d, wl = cur[live], dst[live], w[live]
        nxt = nh[c, d]
        util = r(util + np.bincount(c * n + nxt, weights=wl,
                                    minlength=n * n))
        visits = r(visits + np.bincount(c, weights=wl, minlength=n))
        dsum[live] = r(dsum[live] + geo.delay[c, nxt])
        hops[live] += 1
        cur[live] = nxt
    if (cur != dst).any() or (dist >= INF / 2).any():
        return np.full(5, INF)
    visits = r(visits + fs.sum(axis=0))
    hops = hops.reshape(n, n)
    dsum = dsum.reshape(n, n)
    util = util.reshape(n, n)

    # Eq. 1: CPU<->LLC latency.
    t = geo.core_type[perm]
    cl = ((t[:, None] == 0) & (t[None, :] == 1)) | (
        (t[:, None] == 1) & (t[None, :] == 0))
    lat_terms = r((geo.router_stages * hops + dsum) * fs)
    lat = r(np.sum(np.where(cl, lat_terms, 0.0))) / (geo.n_cpu * geo.n_llc)

    # Eqs. 2-4: utilization of each undirected link, both directions.
    uu = r(util + util.T)
    links = full & np.triu(np.ones((n, n), dtype=bool), 1)
    umean = r(np.sum(uu[links])) / geo.n_links
    uvar = r(np.sum((uu[links] - umean) ** 2)) / geo.n_links
    ustd = np.sqrt(uvar + 1e-12)

    # Eqs. 8-10: energy.
    degree = full.sum(axis=1) + 1
    e_router = E_ROUTER_PORT * r(np.sum(visits * degree))
    planar = adj & ~geo.vadj
    e_planar = E_PLANAR_MM * r(np.sum(np.where(planar, uu * geo.manhattan,
                                               0.0))) / 2.0
    e_vert = E_VERTICAL * r(np.sum(np.where(geo.vadj, uu, 0.0))) / 2.0
    energy = e_router + e_planar + e_vert

    # Eqs. 5-7: thermal.
    p = np.zeros((geo.nx * geo.ny, geo.n_layers))
    np.add.at(p, (geo.column, geo.layer), geo.core_power[perm])
    k = np.arange(1, geo.n_layers + 1)
    t_nk = np.cumsum(p * (k * R_LAYER + R_BASE)[None, :], axis=1)
    temp = t_nk.max() * (t_nk.max(axis=0) - t_nk.min(axis=0)).max()
    return r(np.array([umean, ustd, lat, energy, temp]))


def objectives_many(geo: Geometry, f: np.ndarray, designs, dtype=np.float64
                    ) -> np.ndarray:
    """(B, 5) rows for a sequence of ``(perm, adj)`` pairs."""
    return np.stack([objectives(geo, f, p, a, dtype) for p, a in designs]) \
        if designs else np.zeros((0, 5))


# ------------------------------------------------------------ hypervolume
def hypervolume(points: np.ndarray, ref: np.ndarray) -> float:
    """Exact hypervolume dominated by ``points`` (minimization) below
    ``ref``, by slicing along the first objective."""
    pts = np.minimum(np.asarray(points, dtype=np.float64), ref)
    pts = pts[np.all(pts < ref, axis=1)]
    if len(pts) == 0:
        return 0.0
    if pts.shape[1] == 1:
        return float(ref[0] - pts[:, 0].min())
    if pts.shape[1] == 2:             # staircase
        order = np.lexsort((pts[:, 1], pts[:, 0]))
        x, y = pts[order, 0], np.minimum.accumulate(pts[order, 1])
        return float(np.sum((np.append(x[1:], ref[0]) - x) * (ref[1] - y)))
    xs = np.unique(pts[:, 0])
    bounds = np.append(xs, ref[0])
    vol = 0.0
    for i, x in enumerate(xs):
        slab = pts[pts[:, 0] <= x, 1:]
        keep = np.array([not np.any(np.all(slab <= q, axis=1)
                                    & np.any(slab < q, axis=1))
                         for q in slab])
        vol += (bounds[i + 1] - x) * hypervolume(slab[keep], ref[1:])
    return vol


def front_phv(rows: np.ndarray, mesh_row: np.ndarray, case: str,
              ref_scale: float = 1.6) -> float:
    """Normalized hypervolume of objective ``rows``: the case's objectives
    divided by the mesh design's, against the point ``ref_scale`` in every
    normalized objective."""
    idx = list(CASES[case])
    base = np.where(mesh_row[idx] <= 0, 1.0, mesh_row[idx])
    pts = np.asarray(rows, dtype=np.float64)[:, idx] / base
    return hypervolume(pts, np.full(len(idx), ref_scale))


def rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest relative gap between two sets of objective rows. A row that
    is infeasible on one side only gives ``inf``."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    if got.shape != want.shape:
        return float("inf")
    if got.size == 0:
        return 0.0
    inf_g = got >= INF / 2
    inf_w = want >= INF / 2
    if np.any(inf_g != inf_w):
        return float("inf")
    ok = ~inf_w
    if not ok.any():
        return 0.0
    return float(np.max(np.abs(got[ok] - want[ok])
                        / np.maximum(np.abs(want[ok]), 1e-30)))
