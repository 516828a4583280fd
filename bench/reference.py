"""Plain reference for the DSE matrix: the timing and energy model of every
cell, evaluated straight from its raw dependency graph in float64.

It imports nothing of the program.  Its inputs are plain arrays: one
`Graph` per tile program (the instruction dependency graph with per-node
latencies, as the program's graph builder derives it from the modelled
accelerator and the traced program), the run-length list of each network
cell, and the configuration file's knobs and energy tables.  It shares none
of the packed evaluator's condensation, packing, bucketing, padding or
composition: it walks every node of every graph, level by level, and
replays every storage queue access by access.

The model (paper [16] fixed point, as the configuration states it):

    w_i     = max(1, fu_i * theta[op knob of i] + mem_i * theta[storage knob])
    t_i     = w_i + max(b_i, max_j (t_j + extra_ji))          (forward DAG)
    queues  : per storage, accesses replayed in arrival order (t_i - w_i,
              stable) against its request slots; done + fu_i - w_i raises
              b_i above the static base.  Two such rounds follow the first
              relaxation.
    cycles  = sum over runs of reps * max_i t_i               (sequential)
    energy  = sum_k edyn_k / theta_k + static_pj * cycles
    latency = mean over a query's cells of cycles / cycles at theta = 1
    cost    = sum_k weight_k / theta_k

`dtype` selects the arithmetic: float64 is the reference; a lower one
(bfloat16) is the control that a sound comparison has to reject.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

N_ITERS = 2          # queueing rounds after the first relaxation


@dataclass
class Graph:
    """One tile program's dependency graph, as plain arrays."""

    fu: np.ndarray                 # (n,) functional-unit latency
    mem: np.ndarray                # (n,) total storage latency
    base: np.ndarray               # (n,) static earliest start
    preds: np.ndarray              # (n, P) predecessor ids, -1 = none
    extra: np.ndarray              # (n, P) edge delay
    op_class: np.ndarray           # (n,) op-class id
    class_names: List[str]         # op-class id -> name
    op_scale: np.ndarray           # (n,) macs or words per instruction
    mem_words: np.ndarray          # (n,) words moved per storage access
    # (name, node ids in access order, per-access latency, request slots)
    storages: List[Tuple[str, np.ndarray, np.ndarray, int]]
    _levels: Optional[List[np.ndarray]] = field(default=None, repr=False)

    @property
    def n(self) -> int:
        return int(self.fu.shape[0])

    def levels(self) -> List[np.ndarray]:
        """Node ids grouped by longest-path depth from the sources."""
        if self._levels is None:
            depth = np.zeros(self.n, np.int64)
            for i in range(self.n):
                p = self.preds[i]
                p = p[p >= 0]
                if p.size:
                    if (p >= i).any():
                        raise ValueError(f"node {i} has a later predecessor")
                    depth[i] = depth[p].max() + 1
            order = np.argsort(depth, kind="stable")
            cuts = np.flatnonzero(np.diff(depth[order])) + 1
            self._levels = np.split(order, cuts)
        return self._levels


@dataclass
class Cell:
    """One matrix cell: its tile graphs and the runs that compose them."""

    name: str
    arch: str
    workload: str
    graphs: List[Graph]
    runs: List[Tuple[int, float]]     # (graph index, repetitions)


def knob_of(patterns: Sequence[str], name: str) -> int:
    """Index of the first knob whose pattern matches ``name``, else the
    identity column ``len(patterns)``."""
    for k, pat in enumerate(patterns):
        if pat and re.search(pat, name):
            return k
    return len(patterns)


def _first_match(table: Sequence[Tuple[str, str]], name: str,
                 default: str) -> str:
    for label, pat in table:
        if re.search(pat, name):
            return label
    return default


class Reference:
    """The reference model of one configuration (see the module text).

    ``config`` is the configuration file's dict: ``knobs`` (name, lo, hi,
    ops, storages) and ``energy`` (per-architecture tables and the name
    classifiers)."""

    def __init__(self, cells: Sequence[Cell], config: Dict,
                 dtype=np.float64):
        self.cells = list(cells)
        self.knobs = config["knobs"]
        self.energy_cfg = config["energy"]
        self.dtype = dtype
        self.K = len(self.knobs)
        self._edyn = [self._cell_edyn(c) for c in self.cells]
        self.weights = self._knob_weights()

    # -- per-graph knob maps ----------------------------------------------

    def _maps(self, g: Graph) -> Tuple[np.ndarray, np.ndarray, List[int]]:
        """(per-node op knob, per-node storage knob, per-storage knob)."""
        op_pats = [k["ops"] for k in self.knobs]
        st_pats = [k["storages"] for k in self.knobs]
        cls_knob = np.asarray([knob_of(op_pats, nm) for nm in g.class_names],
                              np.int64)
        opk = cls_knob[g.op_class]
        stk = np.full(g.n, self.K, np.int64)
        st_knob = []
        for name, nodes, _, _ in sorted(g.storages, key=lambda s: s[0]):
            stk[nodes] = knob_of(st_pats, name)
        for name, _, _, _ in g.storages:
            st_knob.append(knob_of(st_pats, name))
        return opk, stk, st_knob

    # -- energy and cost ----------------------------------------------------

    def _graph_edyn(self, g: Graph, arch: str) -> np.ndarray:
        tab = self.energy_cfg["tables"][arch]
        op_cat = [tuple(x) for x in self.energy_cfg["op_categories"]]
        st_cls = [tuple(x) for x in self.energy_cfg["storage_classes"]]
        op_pats = [k["ops"] for k in self.knobs]
        st_pats = [k["storages"] for k in self.knobs]
        e = np.zeros(self.K + 1, np.float64)
        counts = np.bincount(g.op_class, minlength=len(g.class_names))
        for cid, nm in enumerate(g.class_names):
            pj = tab["op"][_first_match(op_cat, nm, "ctrl")]
            e[knob_of(op_pats, nm)] += float(counts[cid]) * pj
        for name, nodes, _, _ in g.storages:
            pj = tab["word"][_first_match(st_cls, name, "reg")]
            e[knob_of(st_pats, name)] += float(g.mem_words[nodes].sum()) * pj
        return e

    def _cell_edyn(self, c: Cell) -> np.ndarray:
        per = [self._graph_edyn(g, c.arch) for g in c.graphs]
        e = np.zeros(self.K + 1, np.float64)
        for gi, reps in c.runs:
            e += float(reps) * per[gi]
        return e

    def _knob_weights(self) -> np.ndarray:
        """Area weight per knob: the instruction volume (op knobs) and word
        traffic (storage knobs) each knob governs, over every cell,
        normalized to mean 1."""
        w = np.zeros(self.K + 1, np.float64)
        for c in self.cells:
            reps = np.zeros(len(c.graphs), np.float64)
            for gi, r in c.runs:
                reps[gi] += float(r)
            for g, r in zip(c.graphs, reps):
                opk, _, st_knob = self._maps(g)
                wg = np.zeros(self.K + 1, np.float64)
                np.add.at(wg, opk, g.op_scale.astype(np.float64))
                for (name, nodes, _, _), k in zip(g.storages, st_knob):
                    wg[k] += float(g.mem_words[nodes].sum())
                w += wg * r
        w = w[: self.K]
        total = w.sum()
        return np.ones(self.K) if total <= 0 else w / total * self.K

    def cost(self, theta: np.ndarray) -> np.ndarray:
        """(B,) area proxy of (B, K) candidates (float64 in every mode: the
        program computes it on the host in float64 as well)."""
        t = np.asarray(theta, np.float64)
        return (self.weights[None, :] / t).sum(axis=1)

    # -- timing -------------------------------------------------------------

    def _makespan(self, g: Graph, kn: np.ndarray) -> np.ndarray:
        """(B,) makespan of one graph for (B, K + 1) knob values."""
        dt = self.dtype
        B = kn.shape[0]
        opk, stk, st_knob = self._maps(g)
        fu = g.fu.astype(dt)
        one = np.asarray(1, dt)
        w = np.maximum(one, fu[None, :] * kn[:, opk]
                       + g.mem.astype(dt)[None, :] * kn[:, stk])
        base0 = np.broadcast_to(g.base.astype(dt)[None, :], (B, g.n))
        extra = g.extra.astype(dt)
        neg = np.asarray(-np.inf, dt)
        levels = g.levels()

        def relax(b):
            t = np.zeros((B, g.n), dt)
            for nodes in levels:
                p = g.preds[nodes]
                ok = p >= 0
                m = b[:, nodes]
                if ok.any():
                    via = np.where(ok[None], t[:, np.maximum(p, 0)]
                                   + extra[nodes][None], neg)
                    m = np.maximum(m, via.max(axis=2))
                t[:, nodes] = m + w[:, nodes]
            return t

        t = relax(base0)
        for _ in range(N_ITERS):
            b = base0.copy()
            for (name, nodes, lat, slots), k in zip(g.storages, st_knob):
                arr = t[:, nodes] - w[:, nodes]
                order = np.argsort(arr, axis=1, kind="stable")
                arr_s = np.take_along_axis(arr, order, axis=1)
                lat_s = (lat.astype(dt)[None, :] * kn[:, k:k + 1])
                lat_s = np.take_along_axis(lat_s, order, axis=1)
                done_s = np.empty_like(arr_s)
                free = np.zeros((B, slots), dt)
                rows = np.arange(B)
                for a in range(arr_s.shape[1]):
                    j = np.argmin(free, axis=1)
                    d = np.maximum(arr_s[:, a], free[rows, j]) + lat_s[:, a]
                    free[rows, j] = d
                    done_s[:, a] = d
                done = np.empty_like(done_s)
                np.put_along_axis(done, order, done_s, axis=1)
                need = done + fu[nodes][None, :] - w[:, nodes]
                for a, i in enumerate(nodes):
                    b[:, i] = np.maximum(b[:, i], need[:, a])
            t = relax(b)
        return t.max(axis=1)

    def evaluate(self, theta: np.ndarray, cols: Optional[Sequence[int]] = None
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """(B, K) candidates -> ((B, S) cycles, (B, S) energy pJ), computed
        in ``self.dtype`` for the cells ``cols`` (all when None); columns
        not computed are NaN."""
        dt = self.dtype
        theta = np.asarray(theta, np.float64)
        B = theta.shape[0]
        kn = np.concatenate([theta, np.ones((B, 1))], axis=1).astype(dt)
        cols = range(len(self.cells)) if cols is None else cols
        memo: Dict[int, np.ndarray] = {}
        S = len(self.cells)
        cyc = np.full((B, S), np.nan)
        en = np.full((B, S), np.nan)
        for ci in cols:
            c = self.cells[ci]
            total = np.zeros(B, dt)
            for gi, reps in c.runs:
                g = c.graphs[gi]
                m = memo.get(id(g))
                if m is None:
                    m = memo[id(g)] = self._makespan(g, kn)
                total = total + np.asarray(reps, dt) * m
            edyn = self._edyn[ci].astype(dt)
            tab = self.energy_cfg["tables"][c.arch]
            e = (edyn[None, :] / kn).sum(axis=1) + np.asarray(
                tab["static"], dt) * total
            cyc[:, ci] = total.astype(np.float64)
            en[:, ci] = e.astype(np.float64)
        return cyc, en
