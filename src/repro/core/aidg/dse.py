"""Design-space exploration over ACADL accelerator parameters (paper §1/§7:
"the timing simulation can be used in the optimization loop of
hardware-aware NAS and DNN/HW Co-Design").

The AIDG separates *structure* (the dependency DAG, built once per
workload) from *weights* (per-instruction latencies).  Latencies are
re-parameterized as multiplicative factors over the baseline:

    fu_lat_i(θ)  = θ_op[op_class_i]    · fu_lat_i
    mem_lat_i(θ) = θ_st[storage(i)]    · mem_lat_i

so θ = 1 reproduces the modeled accelerator exactly, θ_op[gemm@mxu#] = 0.5
models a 2× faster matrix unit, θ_st[hbm#] = 2 a half-bandwidth memory, etc.
``sweep`` evaluates thousands of candidate accelerators in one batched JAX
call via ``vmap`` over θ — the trace and graph are never rebuilt.

Because the whole evaluator is JAX end-to-end, the makespan is also
*differentiable in θ*: ``evaluate_theta_soft`` swaps the hard max-plus
engine for the temperature-τ smooth family (``maxplus.fixed_point_soft``)
and ``grad_sweep`` returns a cached ``jit(vmap(value_and_grad))`` that maps
a batch of *shared knob vectors* straight to (soft cycles, d cycles / d
knob) — the chain through ``DesignSpace.projection`` is part of the traced
function, so gradients land on the few shared knobs rather than the
per-scenario θ columns.  ``repro.core.aidg.gradient`` turns this into a
projected-Adam design-space optimizer.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...tracing import span
from .builder import (AIDG, CompiledAIDG, CondensedAIDG, compile_aidg,
                      condense_aidg, longest_path_fixed_point)
from .maxplus import (DEFAULT_ENGINE, NEG, condensed_scan, fixed_point_jax,
                      fixed_point_soft, softmax_reduce, softmaximum)

__all__ = ["DSEProblem", "make_problem", "evaluate_theta", "compiled_sweep",
           "sweep", "evaluate_theta_soft", "grad_sweep", "LayerStack",
           "NETWORK_MODES", "compiled_network_sweep", "grad_network_sweep",
           "PackSpec", "PackedMatrix"]


@dataclass
class DSEProblem:
    """One workload's parameterized timing model: the immutable AIDG plus
    the gather maps that turn a θ vector (one factor per op class / storage
    class) into per-node latency scalings, and the per-problem cache of
    compiled evaluators.  Built once per (architecture, workload) cell by
    ``make_problem``; every sweep re-weights this structure."""

    aidg: AIDG
    op_names: List[str]          # op-class index -> name
    storage_names: List[str]     # storage-class index -> name
    # per-node gather indices
    node_op: np.ndarray          # (n,) int32
    node_storage: Dict[str, int] = field(default_factory=dict)  # name -> id
    # build-time compilation artifact (level schedule + padded gathers),
    # shared by every sweep over this problem
    caidg: Optional[CompiledAIDG] = None
    # (n_iters, engine) -> jitted vmapped evaluator, and
    # ("grad", n_iters, projection bytes) -> jitted vmapped value_and_grad
    # (jax.jit caches by function identity, so re-creating the lambda per
    # sweep() would re-trace)
    _compiled: Dict[Tuple, Callable] = field(default_factory=dict, repr=False)

    @property
    def n_op(self) -> int:
        """Number of op classes = columns of a θ_op candidate row."""
        return len(self.op_names)

    @property
    def n_st(self) -> int:
        """Number of storage classes = columns of a θ_st candidate row."""
        return len(self.storage_names)

    @property
    def compiled_aidg(self) -> CompiledAIDG:
        """The build-time compile artifact (level schedule + gathers)."""
        if self.caidg is None:  # hand-built problems compile lazily
            self.caidg = compile_aidg(self.aidg)
        return self.caidg


def make_problem(aidg: AIDG) -> DSEProblem:
    """AIDG -> DSEProblem: name the op/storage classes, build the per-node
    gather indices, and run the build-time compile pipeline
    (``compile_aidg``) so every sweep shares one level schedule."""
    op_names = [None] * len(aidg.classes)
    for name, idx in aidg.classes.items():
        op_names[idx] = name
    st_names = sorted(aidg.storage_nodes.keys())
    return DSEProblem(aidg=aidg, op_names=op_names, storage_names=st_names,
                      node_op=aidg.op_class,
                      node_storage={s: i for i, s in enumerate(st_names)},
                      caidg=compile_aidg(aidg))


def _reweight(prob: DSEProblem, theta_op: jnp.ndarray, theta_st: jnp.ndarray,
              floor: Callable = jnp.maximum
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], jnp.ndarray]:
    """θ -> (per-node work, scaled storage latencies, scaled fu latencies).
    ``floor`` applies the 1-cycle occupancy minimum — ``jnp.maximum`` on
    the hard path, a τ-``softmaximum`` on the smooth one (one shared
    re-weighting, so hard and soft evaluators can't drift apart)."""
    aidg = prob.aidg
    fu = jnp.asarray(aidg.fu_lat) * theta_op[prob.node_op]
    mem_scale = jnp.ones(aidg.n, dtype=jnp.float32)
    st_lat: Dict[str, jnp.ndarray] = {}
    for st, cid in prob.node_storage.items():
        nodes = aidg.storage_nodes[st]
        st_lat[st] = jnp.asarray(aidg.storage_lat[st]) * theta_st[cid]
        mem_scale = mem_scale.at[jnp.asarray(nodes)].set(theta_st[cid])
    mem = jnp.asarray(aidg.mem_lat) * mem_scale
    work = floor(jnp.float32(1.0), fu + mem)
    return work, st_lat, fu


def evaluate_theta(prob: DSEProblem, theta_op: jnp.ndarray,
                   theta_st: jnp.ndarray, n_iters: int = 2,
                   engine: str = DEFAULT_ENGINE) -> jnp.ndarray:
    """Estimated cycles for one parameter point (jit/vmap-able)."""
    work, st_lat, fu = _reweight(prob, theta_op, theta_st)
    # fixed_point_jax reads fu_lat for the queueing fold-back; the scaled fu
    # enters through `work`, so pass base/work/storage latencies explicitly.
    # The CompiledAIDG carries the level schedule, built once per scenario.
    t = fixed_point_jax(prob.compiled_aidg, n_iters=n_iters, work=work,
                        storage_lat=st_lat, engine=engine)
    return t.max()


def compiled_sweep(prob: DSEProblem, n_iters: int = 2,
                   engine: str = DEFAULT_ENGINE) -> Callable:
    """Cached jit(vmap) evaluator for ``prob``: (B, n_op), (B, n_st) ->
    (B,) cycles.  The first call per (problem, n_iters, engine) traces;
    every later sweep over the same AIDG re-uses the compiled kernel — the
    property the multi-scenario explorer relies on for its configs/sec
    throughput."""
    fn = prob._compiled.get((n_iters, engine))
    if fn is None:
        f = lambda to, ts: evaluate_theta(prob, to, ts, n_iters=n_iters,
                                          engine=engine)
        fn = jax.jit(jax.vmap(f))
        prob._compiled[(n_iters, engine)] = fn
    return fn


def sweep(prob: DSEProblem, thetas_op: np.ndarray, thetas_st: np.ndarray,
          n_iters: int = 2, batched: bool = True,
          chunk: Optional[int] = None,
          engine: str = DEFAULT_ENGINE) -> np.ndarray:
    """Evaluate a batch of candidate accelerators.

    ``thetas_op``: (B, n_op), ``thetas_st``: (B, n_st) -> (B,) cycles.
    One ``vmap`` + ``jit`` over the whole batch: the DSE loop the paper
    motivates, shaped for a single device launch.

    ``chunk``: split very large batches into fixed-size device launches to
    bound peak memory (the tail chunk is padded to ``chunk`` rows so the
    compiled kernel is reused rather than re-traced per remainder shape).

    ``engine``: the DAG relaxation used inside the fixed point —
    ``"wavefront"`` (default, level-scheduled), ``"condensed"``
    (chain-condensed wavefront, see ``builder.condense_aidg``), ``"scan"``
    (per-node), or ``"blocked"`` (max-plus closure blocks).
    """
    if chunk is not None and chunk <= 0:
        raise ValueError(f"chunk must be positive, got {chunk}")
    if not batched:
        f = lambda to, ts: evaluate_theta(prob, to, ts, n_iters=n_iters,
                                          engine=engine)
        return np.asarray([f(jnp.asarray(a), jnp.asarray(b))
                           for a, b in zip(thetas_op, thetas_st)])
    fn = compiled_sweep(prob, n_iters, engine)
    to = jnp.asarray(thetas_op, jnp.float32)
    ts = jnp.asarray(thetas_st, jnp.float32)
    B = to.shape[0]
    if chunk is None or B <= chunk:
        return np.asarray(fn(to, ts))
    out = np.empty(B, dtype=np.float32)
    for s in range(0, B, chunk):
        e = min(s + chunk, B)
        if e - s < chunk:  # pad the tail to the compiled batch shape
            pad = chunk - (e - s)
            co = jnp.concatenate([to[s:e], jnp.ones((pad, to.shape[1]),
                                                    jnp.float32)])
            cs = jnp.concatenate([ts[s:e], jnp.ones((pad, ts.shape[1]),
                                                    jnp.float32)])
            out[s:e] = np.asarray(fn(co, cs))[: e - s]
        else:
            out[s:e] = np.asarray(fn(to[s:e], ts[s:e]))
    return out


# ---------------------------------------------------------------------------
# smooth evaluation + knob-space gradients (the co-design inner loop)
# ---------------------------------------------------------------------------


def evaluate_theta_soft(prob: DSEProblem, theta_op: jnp.ndarray,
                        theta_st: jnp.ndarray, tau, n_iters: int = 2,
                        engine: str = DEFAULT_ENGINE) -> jnp.ndarray:
    """Smooth estimated cycles for one parameter point: the τ-tempered
    counterpart of ``evaluate_theta`` (soft occupancy floor, soft wavefront
    fixed point, soft makespan reduction).  Upper-bounds the hard estimate
    and converges to it as τ → 0; smooth in (θ_op, θ_st) everywhere — the
    hard ``max(1, fu + mem)`` floor would have zero gradient wherever θ has
    pushed a node under it, killing descent directions exactly where fast
    hardware stops paying, so the floor is softened too.  ``engine``:
    ``"wavefront"`` (default) or ``"condensed"`` (exact chain sums on a
    shorter sequential scan — a tighter soft relaxation)."""
    work, st_lat, _ = _reweight(prob, theta_op, theta_st,
                                floor=lambda a, b: softmaximum(a, b, tau))
    t = fixed_point_soft(prob.compiled_aidg, tau=tau, n_iters=n_iters,
                         work=work, storage_lat=st_lat, engine=engine)
    return softmax_reduce(t, tau)


def grad_sweep(prob: DSEProblem, op_idx: np.ndarray, st_idx: np.ndarray,
               n_iters: int = 2) -> Callable:
    """Cached ``jit(vmap(value_and_grad))`` from *shared knob space*:
    ``fn(knobs (B, K), tau) -> (soft cycles (B,), d cycles/d knob (B, K))``.

    ``op_idx`` / ``st_idx`` are ``DesignSpace.projection(prob)`` gather maps
    (op-class/storage -> knob, with K = identity column); baking them into
    the traced function chains the projection inside autodiff, so the
    returned gradient is already in the K shared knobs — no per-scenario θ
    chain rule on the host.  τ is traced: annealing re-uses the kernel."""
    op_idx = np.asarray(op_idx, np.int64)
    st_idx = np.asarray(st_idx, np.int64)
    key = ("grad", n_iters, op_idx.tobytes(), st_idx.tobytes())
    fn = prob._compiled.get(key)
    if fn is None:
        oi, si = jnp.asarray(op_idx), jnp.asarray(st_idx)

        def f(knobs, tau):
            padded = jnp.concatenate(
                [knobs, jnp.ones((1,), knobs.dtype)])   # identity column
            return evaluate_theta_soft(prob, padded[oi], padded[si], tau,
                                       n_iters=n_iters)

        fn = jax.jit(jax.vmap(jax.value_and_grad(f), in_axes=(0, None)))
        prob._compiled[key] = fn
    return fn


# ---------------------------------------------------------------------------
# stacked per-layer programs: whole-network end-to-end latency
# ---------------------------------------------------------------------------

NETWORK_MODES = ("sequential", "pipelined")


@dataclass
class LayerStack:
    """A whole network as a *stack* of per-layer DSE problems plus the
    max-plus composition structure (built by ``repro.core.network``).

    ``problems[u]`` is the AIDG of one **unique** layer program; the
    network's execution order is a sequence of *runs* — maximal stretches
    of ``run_reps[r]`` consecutive instances of unique layer
    ``run_layer[r]`` (a transformer's 16 identical blocks are one run of
    16, a tiled operator's ``tiles`` repeats fold in multiplicatively).

    ``prologue_len[u]`` is the static length of the layer's load-only
    instruction prefix (no compute op has executed yet): its completion
    time is the part of the layer a *double-buffered* pipeline can overlap
    with the previous layer's tail.  ``fits_within[r]`` / ``fits_between[r]``
    are 0/1 capacity gates — overlap is only credited when the two layers'
    stationary working sets fit the architecture's on-chip buffer together.

    Composition (per candidate, all in the traced function):

    * ``sequential``: Σ_r reps_r · m_{l(r)} — every instance back-to-back,
      the mode whose θ = 1 value matches the per-layer event-sim oracle
      composition exactly.
    * ``pipelined``: the sequential total minus the credited overlaps
      min(p_next, m_prev) — never below any single layer, never above the
      sequential total.
    """

    problems: List[DSEProblem]
    prologue_len: np.ndarray        # (L,) int   — load-only prefix length
    run_layer: np.ndarray           # (R,) int   — unique-layer id per run
    run_reps: np.ndarray            # (R,) float — instances per run
    fits_within: np.ndarray         # (R,) float — 0/1 double-buffer gate
    fits_between: np.ndarray        # (R-1,) float — 0/1 gate to next run
    _compiled: Dict[Tuple, Callable] = field(default_factory=dict, repr=False)

    @property
    def n_layers(self) -> int:
        """Unique per-layer programs in the stack (the compile unit)."""
        return len(self.problems)

    @property
    def instances(self) -> float:
        """Total layer instances composed end-to-end (Σ run reps)."""
        return float(np.asarray(self.run_reps, np.float64).sum())


def _layer_times(prob: DSEProblem, theta_op: jnp.ndarray,
                 theta_st: jnp.ndarray, n_iters: int, engine: str,
                 k_prologue: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One layer's (makespan, prologue completion) at θ — the prologue is
    the hard max over the first ``k_prologue`` (load-only) instructions."""
    work, st_lat, _ = _reweight(prob, theta_op, theta_st)
    t = fixed_point_jax(prob.compiled_aidg, n_iters=n_iters, work=work,
                        storage_lat=st_lat, engine=engine)
    p = t[:k_prologue].max() if k_prologue > 0 else jnp.float32(0.0)
    return t.max(), p


def _compose(stack: LayerStack, m: jnp.ndarray, p: jnp.ndarray, mode: str,
             minimum: Callable = jnp.minimum) -> jnp.ndarray:
    """(L,) per-unique-layer makespans/prologues -> end-to-end cycles.
    ``minimum`` is the overlap clip — ``jnp.minimum`` on the hard path, a
    τ-softmin on the smooth one (overlap can't exceed the previous layer's
    makespan or the next layer's prologue)."""
    rl = jnp.asarray(stack.run_layer)
    reps = jnp.asarray(stack.run_reps, jnp.float32)
    mr, pr = m[rl], p[rl]
    total = (reps * mr).sum()
    if mode == "sequential":
        return total
    fw = jnp.asarray(stack.fits_within, jnp.float32)
    within = ((reps - 1.0) * minimum(pr, mr) * fw).sum()
    if stack.run_layer.shape[0] > 1:
        fb = jnp.asarray(stack.fits_between, jnp.float32)
        between = (minimum(pr[1:], mr[:-1]) * fb).sum()
    else:
        between = jnp.float32(0.0)
    return total - within - between


def compiled_network_sweep(stack: LayerStack, n_iters: int = 2,
                           engine: str = DEFAULT_ENGINE,
                           mode: str = "sequential") -> Callable:
    """Cached jit(vmap) end-to-end evaluator for a layer stack:
    ``fn(tuple of (B, n_op_l), tuple of (B, n_st_l)) -> (B,) cycles``.

    The per-layer wavefronts and the max-plus composition live in ONE
    traced function, so a candidate batch costs one device launch per
    network cell regardless of depth — and repeated layers are evaluated
    once per unique program, not once per instance."""
    if mode not in NETWORK_MODES:
        raise ValueError(f"mode must be one of {NETWORK_MODES}, got {mode!r}")
    key = (n_iters, engine, mode)
    fn = stack._compiled.get(key)
    if fn is None:
        ks = [int(k) for k in stack.prologue_len]

        def f(tos, tss):
            times = [_layer_times(prob, to, ts, n_iters, engine, k)
                     for prob, k, to, ts
                     in zip(stack.problems, ks, tos, tss)]
            m = jnp.stack([t[0] for t in times])
            p = jnp.stack([t[1] for t in times])
            return _compose(stack, m, p, mode)

        fn = jax.jit(jax.vmap(f))
        stack._compiled[key] = fn
    return fn


def _layer_times_soft(prob: DSEProblem, theta_op: jnp.ndarray,
                      theta_st: jnp.ndarray, tau, n_iters: int,
                      k_prologue: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Smooth counterpart of ``_layer_times`` (soft floor, soft fixed
    point, soft reductions) — differentiable in θ everywhere."""
    work, st_lat, _ = _reweight(prob, theta_op, theta_st,
                                floor=lambda a, b: softmaximum(a, b, tau))
    t = fixed_point_soft(prob.compiled_aidg, tau=tau, n_iters=n_iters,
                         work=work, storage_lat=st_lat)
    p = (softmax_reduce(t[:k_prologue], tau) if k_prologue > 0
         else jnp.float32(0.0))
    return softmax_reduce(t, tau), p


def grad_network_sweep(stack: LayerStack, projections: Sequence[Tuple],
                       n_iters: int = 2, mode: str = "sequential"
                       ) -> Callable:
    """Cached ``jit(vmap(value_and_grad))`` of *end-to-end* network latency
    from shared knob space: ``fn(knobs (B, K), tau) -> (soft cycles (B,),
    d cycles/d knob (B, K))``.

    ``projections[u]`` is ``DesignSpace.projection(problems[u])``; baking
    every per-layer gather into one traced function chains projection →
    per-layer soft wavefront → max-plus composition inside autodiff, so
    the K shared knobs receive the full network's gradient in one call.
    In ``sequential`` mode the soft value upper-bounds the hard one (every
    softened reduction does); ``pipelined`` additionally softens the
    overlap clip with a softmin, which approximates rather than bounds."""
    if mode not in NETWORK_MODES:
        raise ValueError(f"mode must be one of {NETWORK_MODES}, got {mode!r}")
    projections = [(np.asarray(oi, np.int64), np.asarray(si, np.int64))
                   for oi, si in projections]
    key = (("grad", n_iters, mode)
           + tuple(oi.tobytes() + si.tobytes() for oi, si in projections))
    fn = stack._compiled.get(key)
    if fn is None:
        ks = [int(k) for k in stack.prologue_len]
        gathers = [(jnp.asarray(oi), jnp.asarray(si))
                   for oi, si in projections]

        def f(knobs, tau):
            padded = jnp.concatenate(
                [knobs, jnp.ones((1,), knobs.dtype)])   # identity column
            times = [_layer_times_soft(prob, padded[oi], padded[si], tau,
                                       n_iters, k)
                     for prob, k, (oi, si)
                     in zip(stack.problems, ks, gathers)]
            m = jnp.stack([t[0] for t in times])
            p = jnp.stack([t[1] for t in times])
            softmin = lambda a, b: -softmaximum(-a, -b, tau)
            return _compose(stack, m, p, mode, minimum=softmin)

        fn = jax.jit(jax.vmap(jax.value_and_grad(f), in_axes=(0, None)))
        stack._compiled[key] = fn
    return fn


# ---------------------------------------------------------------------------
# matrix packing: ALL cells x ALL candidates in one traced dispatch
# ---------------------------------------------------------------------------

_BIG = 1e18


@dataclass(frozen=True)
class PackSpec:
    """One cell's contribution to a :class:`PackedMatrix`: its (unique)
    per-layer problems + projections and the max-plus composition arrays.
    An operator cell is the trivial spec — one problem, one run of one
    repetition, no overlap gates; a network cell mirrors its
    :class:`LayerStack` (``fits_*`` all-zero encodes sequential mode, so
    one composition formula serves both modes)."""

    problems: Tuple[DSEProblem, ...]
    projections: Tuple[Tuple[np.ndarray, np.ndarray], ...]
    prologue_len: np.ndarray     # (L,) int — per-problem load-only prefix
    run_layer: np.ndarray        # (R,) int — local problem index per run
    run_reps: np.ndarray         # (R,) float
    fits_within: np.ndarray      # (R,) float 0/1 (0 = no overlap credited)
    fits_between: np.ndarray     # (R-1,) float 0/1
    # energy objective (optional — zero when absent): per-problem folded
    # dynamic pJ per knob (repro.core.aidg.energy.fold_dyn_energy, each
    # (n_knobs + 1,)) and the cell's static leakage pJ per cycle
    edyn: Tuple[np.ndarray, ...] = ()
    static_pj: float = 0.0

    @staticmethod
    def operator(problem: DSEProblem, projection, edyn=None,
                 static_pj: float = 0.0) -> "PackSpec":
        """The single-problem spec of an operator cell."""
        return PackSpec((problem,), (tuple(projection),),
                        np.zeros(1, np.int64), np.zeros(1, np.int64),
                        np.ones(1, np.float32), np.zeros(1, np.float32),
                        np.zeros(0, np.float32),
                        () if edyn is None else (np.asarray(edyn),),
                        float(static_pj))


@dataclass
class _PackedRow:
    """Per-unique-problem numpy staging arrays (permuted kept space)."""

    problem: DSEProblem
    cond: CondensedAIDG
    fu: np.ndarray               # (nk,) raw FU latency, permuted kept order
    mem: np.ndarray              # (nk,) raw memory latency
    base: np.ndarray             # (nk,) static base
    opk: np.ndarray              # (nk,) knob id scaling fu (K = identity)
    stk: np.ndarray              # (nk,) knob id scaling mem
    prol: np.ndarray             # (nk,) bool — original id < prologue_len
    ab_fu: np.ndarray            # (n_ab,) absorbed-node raw FU latency
    ab_opk: np.ndarray           # (n_ab,) knob id scaling it
    # storages as (perm positions, lats, knob, slots, ordered) — slots == 1
    # solves closed-form, > 1 runs the slot-vector scan; ``ordered`` means
    # the arrival order is PROVABLY static (each access an ancestor of the
    # next), so the per-candidate argsort is the identity and is skipped
    queues: List[Tuple[np.ndarray, np.ndarray, int, int, bool]]


def _stage_row(prob: DSEProblem, proj, k_prologue: int) -> _PackedRow:
    """Condense one problem (prologue boundary force-kept) and gather its
    θ-independent arrays into the permuted kept layout."""
    a = prob.aidg
    cond = condense_aidg(a, boundary=int(k_prologue) if k_prologue else None)
    op_idx, st_idx = (np.asarray(proj[0], np.int64),
                      np.asarray(proj[1], np.int64))
    kop = cond.kept_perm                          # original ids, permuted
    stk_full = np.full(a.n, -1, dtype=np.int64)   # -1 -> identity (patched)
    for st, cid in prob.node_storage.items():
        stk_full[a.storage_nodes[st]] = st_idx[cid]
    queues: List[Tuple[np.ndarray, np.ndarray, int, int, bool]] = []
    ca = prob.compiled_aidg
    for name in ca.storage_order:
        perm_pos = cond.schedule.rank[
            cond.kept_rank[a.storage_nodes[name]]].astype(np.int64)
        lat = np.asarray(a.storage_lat[name], np.float32)
        knob = int(st_idx[prob.node_storage[name]])
        slots = int(a.storage_slots[name])
        queues.append((perm_pos, lat, knob, slots,
                       cond.storage_static_order(name)))
    return _PackedRow(
        problem=prob, cond=cond,
        fu=a.fu_lat[kop].astype(np.float32),
        mem=a.mem_lat[kop].astype(np.float32),
        base=a.base[kop].astype(np.float32),
        opk=op_idx[a.op_class[kop]],
        stk=stk_full[kop],
        prol=(kop < k_prologue),
        ab_fu=a.fu_lat[cond.absorbed].astype(np.float32),
        ab_opk=op_idx[a.op_class[cond.absorbed]],
        queues=queues)


def _sum_last(x: jnp.ndarray) -> jnp.ndarray:
    """Sum over the last axis in a fixed pairwise order.  XLA leaves the
    order of a ``reduce`` to each backend, and network cells sum run
    makespans past 2**24 where the order decides the rounding; halving
    with elementwise adds rounds the same on the TPU and on the host (and
    keeps the energy matvec off the MXU's reduced-precision passes)."""
    n = x.shape[-1]
    width = 1 << max(0, n - 1).bit_length()
    if width > n:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (width - n,), x.dtype)], axis=-1)
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


# an instruction of the compiled evaluator's HLO text and the packed scope
# its ``op_name`` names (``_matrix_fn``'s ``jax.named_scope``s)
_SCOPED_OP = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+) = [^\n]*?op_name="[^"\n]*?'
    r'\b(packed\.(?:bucket\d+|compose))\b', re.M)


class PackedMatrix:
    """The whole scenario/network matrix as ONE traced evaluator.

    Every unique (condensed) per-layer problem across all cells becomes one
    *row*: its level windows, predecessor slots, absorbed-prefix tables,
    and storage queues are padded to shared shapes and evaluated by a
    ``vmap`` over rows, each row's scans carrying the candidate batch as
    their trailing (lane) axis — all cells x all candidates in a single
    jitted dispatch, with masking keeping padded
    rows/slots/accesses inert.  Rows are grouped into *shape buckets*
    (``_bucketize``) so a width-1 chain cell never pays a wide systolic
    cell's window; every bucket's vmapped scan lives in the same trace, so
    it is still one dispatch per batch.  Cells then compose their rows'
    makespans
    (and prologue times, for pipelined network cells) with the same
    run-length max-plus formula as :class:`LayerStack` — a tile program
    shared by several networks is evaluated once per candidate, not once
    per cell.

    Built by :meth:`build` from cell :class:`PackSpec`s;
    ``repro.core.aidg.explorer.Explorer`` (``engine="packed"``, the
    default) routes ``evaluate`` / coordinate descent / the gradient
    engine through it.
    """

    def __init__(self, rows: List[_PackedRow], specs: List[PackSpec],
                 row_of: List[List[int]], n_knobs: int, n_iters: int):
        self.rows = rows
        self.specs = specs
        self.row_of = row_of          # per cell: global row id per problem
        self.n_knobs = n_knobs
        self.n_iters = n_iters
        self._arrays = None           # lazily-built jnp constant pytree
        self._buckets: Optional[List[List[int]]] = None
        self._compiled: Dict[Tuple, Callable] = {}
        self._last_rows: Optional[int] = None   # last unsharded batch

    # -- construction -------------------------------------------------------

    @staticmethod
    def build(specs: Sequence[PackSpec], n_knobs: int,
              n_iters: int = 2) -> "PackedMatrix":
        """Dedup problems across cells (by object identity — the scenario
        cache already shares repeated tile programs), condense each exactly
        once with its prologue boundary, and stage the packed arrays."""
        by_id: Dict[int, int] = {}
        staged: List[Tuple[DSEProblem, Tuple, int]] = []
        row_of: List[List[int]] = []
        for spec in specs:
            ids = []
            for prob, proj, k in zip(spec.problems, spec.projections,
                                     spec.prologue_len):
                rid = by_id.get(id(prob))
                if rid is None:
                    rid = len(staged)
                    by_id[id(prob)] = rid
                    staged.append([prob, proj, int(k)])
                else:
                    staged[rid][2] = max(staged[rid][2], int(k))
                ids.append(rid)
            row_of.append(ids)
        rows = [_stage_row(prob, proj, k) for prob, proj, k in staged]
        return PackedMatrix(rows, list(specs), row_of, n_knobs, n_iters)

    @property
    def n_rows(self) -> int:
        """Unique packed problems (the vmap-over-cells extent)."""
        return len(self.rows)

    @property
    def n_cells(self) -> int:
        """Matrix cells composed from the packed rows."""
        return len(self.specs)

    def stats(self) -> Dict[str, object]:
        """Aggregate packing/condensation statistics (for benchmarks and
        docs): total vs kept nodes, original vs condensed level totals,
        shape-bucket count, and the padded sequential scan total (one scan
        per bucket, all in one dispatch).

        ``bucket_detail`` describes each shape bucket in ``_bucketize``
        order, the order of the ``packed.bucketNN`` named scopes in the
        compiled evaluator: its row count, padded ``LV`` (scan steps), ``W``
        and ``P``, its sequential queue steps per dispatch, the matrix
        columns (cells) whose rows it holds, and the cost model's summed
        per-row cost ``rcost`` and padded cost ``bcost``.
        ``pad_efficiency`` is Σ ``rcost`` / Σ ``bcost`` over all buckets:
        the share of the padded work that is real (1.0 without padding).
        ``op_scopes`` is :meth:`op_scopes`."""
        conds = [r.cond for r in self.rows]
        lv0 = sum(c.stats["levels"] for c in conds)
        lv1 = sum(c.stats["levels_condensed"] for c in conds)
        buckets = self._bucketize()
        cells_of = {}
        for ci, ids in enumerate(self.row_of):
            for rid in ids:
                cells_of.setdefault(rid, set()).add(ci)
        detail = []
        for b in buckets:
            lv, w, p, q = self._bucket_dims(b)
            detail.append({
                "rows": len(b), "LV": lv, "W": w, "P": p, "queue_steps": q,
                "cells": sorted(set().union(*(cells_of[i] for i in b))),
                "rcost": sum(self._row_cost(i) for i in b),
                "bcost": self._bucket_cost(b)})
        return {"rows": self.n_rows, "cells": self.n_cells,
                "nodes": sum(c.n for c in conds),
                "kept": sum(c.n_kept for c in conds),
                "levels": lv0, "levels_condensed": lv1,
                "level_reduction": lv0 / max(1, lv1),
                "buckets": len(buckets),
                "scan_len": sum(d["LV"] for d in detail),
                "bucket_detail": detail,
                "pad_efficiency": (sum(d["rcost"] for d in detail)
                                   / sum(d["bcost"] for d in detail)),
                "op_scopes": self.op_scopes()}

    def op_scopes(self) -> Dict[str, str]:
        """The named scope (``packed.bucketNN`` or ``packed.compose``) of
        each instruction of the compiled evaluator at the batch size of
        the latest unsharded dispatch, keyed by HLO instruction name: the
        name a TPU trace gives each device operation, so a trace's device
        time can be summed per bucket.  Empty before the first dispatch;
        after it the lowering and compile are cached, so this costs one
        pass over the module's text."""
        if self._last_rows is None:
            return {}
        x = jnp.zeros((self._last_rows, self.n_knobs), jnp.float32)
        text = self._full_fn().lower(x).compile().as_text()
        return dict(_SCOPED_OP.findall(text))

    # -- packed constant arrays --------------------------------------------

    def _queue_len(self, i: int) -> int:
        """Row ``i``'s sequential multi-slot queue steps per iteration."""
        return max((len(nd) for nd, _, _, sl, _ in self.rows[i].queues
                    if sl > 1), default=0)

    def _bucket_dims(self, members: List[int]) -> Tuple[int, int, int, int]:
        """A bucket's padded (levels, width, preds) maxima and its
        sequential queue steps per dispatch (``n_iters`` rounds)."""
        conds = [self.rows[i].cond for i in members]
        return (max(c.schedule.n_levels for c in conds),
                max(c.schedule.width for c in conds),
                max(c.preds_lv.shape[1] for c in conds),
                self.n_iters * max(self._queue_len(i) for i in members))

    def _row_cost(self, i: int) -> int:
        """Cost model: row ``i``'s own work, unpadded."""
        return self._bucket_cost([i])

    def _bucket_cost(self, members: List[int]) -> int:
        """Cost model: a bucket's work with every member padded to the
        bucket's maxima."""
        lv, w, p, q = self._bucket_dims(members)
        return len(members) * (max(1, lv) * max(1, w) * max(1, p) + q * 8)

    def _bucketize(self) -> List[List[int]]:
        """Group rows into shape buckets so padding waste stays bounded:
        the vmapped wavefront pads every bucket member to the bucket's
        (levels, width, preds) maxima, so a single global bucket would make
        every small cell pay the largest cell's scan — measured 20x+ WORSE
        than the per-cell loop on the default matrix.  Greedy assignment in
        descending per-row cost, joining a bucket only when the added
        padded work stays within 1.5x the row's own work.  All buckets
        still evaluate inside ONE jitted function (one dispatch).
        Memoized — ``stats`` and ``_build_arrays`` share one assignment."""
        if self._buckets is not None:
            return self._buckets
        rows = self.rows
        rcost, bcost = self._row_cost, self._bucket_cost

        order = sorted(range(len(rows)), key=lambda i: (-rcost(i), i))
        buckets: List[List[int]] = []
        # rows with affine chains never share a bucket with chain-free rows
        # (the in-window associative scan is a trace-time constant per
        # bucket, and it costs real per-step kernels)
        chainy = [rows[i].cond.stats["n_coupled"] > 0
                  for i in range(len(rows))]
        for i in order:
            best, best_delta = None, None
            for b in buckets:
                if chainy[b[0]] != chainy[i]:
                    continue
                delta = bcost(b + [i]) - bcost(b)
                if best_delta is None or delta < best_delta:
                    best, best_delta = b, delta
            if best is not None and best_delta <= 1.5 * rcost(i):
                best.append(i)
            else:
                buckets.append([i])
        self._buckets = buckets
        return buckets

    def _bucket_arrays(self, members: List[int]):
        """Stage one bucket's stacked jnp constants (dims = bucket maxima)."""
        rows = [self.rows[i] for i in members]
        K = self.n_knobs
        NK = max(r.cond.n_kept for r in rows)
        W = max(r.cond.schedule.width for r in rows)
        P = max(r.cond.preds_lv.shape[1] for r in rows)
        LV = max(r.cond.schedule.n_levels for r in rows)
        AB = max(1, max(r.cond.n_absorbed for r in rows))
        R = len(rows)

        fu = np.zeros((R, NK), np.float32)
        mem = np.zeros((R, NK), np.float32)
        base = np.full((R, NK), NEG, np.float32)
        opk = np.full((R, NK), K, np.int64)
        stk = np.full((R, NK), K, np.int64)
        nmask = np.zeros((R, NK), bool)
        prol = np.zeros((R, NK), bool)
        has_prol = np.zeros((R,), np.float32)
        preds = np.full((R, NK + W, P), -1, np.int32)
        const = np.zeros((R, NK + W, P), np.float32)
        pidx = np.full((R, NK + W, P), -1, np.int32)
        vc = np.full((R, NK + W), NEG, np.float32)
        vp = np.full((R, NK + W), -1, np.int32)
        starts = np.full((R, LV), NK, np.int32)
        ab_fu = np.zeros((R, AB), np.float32)
        ab_opk = np.full((R, AB), K, np.int64)
        ab_const = np.zeros((R, AB), np.float32)
        ab_seg = np.tile(np.arange(AB, dtype=np.int64), (R, 1))

        for i, r in enumerate(rows):
            c = r.cond
            nk, w, p = c.n_kept, c.schedule.width, c.preds_lv.shape[1]
            fu[i, :nk] = r.fu
            mem[i, :nk] = r.mem
            base[i, :nk] = r.base
            opk[i, :nk] = r.opk
            stk[i, :nk] = np.where(r.stk >= 0, r.stk, K)
            nmask[i, :nk] = True
            prol[i, :nk] = r.prol
            has_prol[i] = float(r.prol.any())
            preds[i, : nk + w, :p] = c.preds_lv
            const[i, : nk + w, :p] = c.const_lv
            pidx[i, : nk + w, :p] = c.pidx_lv
            vc[i, : nk + w] = c.v_const_lv
            vp[i, : nk + w] = c.v_pidx_lv
            starts[i, : c.schedule.n_levels] = c.schedule.starts
            na = c.n_absorbed
            if na:
                ab_fu[i, :na] = r.ab_fu
                ab_opk[i, :na] = r.ab_opk
                ab_const[i, :na] = c.ab_const
                ab_seg[i, :na] = c.ab_segstart

        # storage queues in four families — (single-slot | multi-slot) x
        # (statically-ordered | dynamic) — padded over (row, storage,
        # access); ordered families skip the per-candidate argsort
        def select(r, single, ordered):
            return [(nd, lat, kn, sl) for nd, lat, kn, sl, o in r.queues
                    if (sl == 1) == single and o == ordered]

        J = jnp.asarray
        groups = {}
        for key, single, ordered in (("s1o", True, True),
                                     ("s1d", True, False),
                                     ("smo", False, True),
                                     ("smd", False, False)):
            sel = [select(r, single, ordered) for r in rows]
            NS = max(1, max(len(s) for s in sel))
            SA = max(1, max((len(nd) for s in sel for nd, _, _, _ in s),
                            default=1))
            SL = max(1, max((sl for s in sel for _, _, _, sl in s),
                            default=1))
            g_nd = np.full((R, NS, SA), -1, np.int64)
            g_lat = np.zeros((R, NS, SA), np.float32)
            g_kn = np.full((R, NS), K, np.int64)
            g_sl = np.ones((R, NS), np.int32)
            present = False
            for i, s in enumerate(sel):
                for si, (nd, lat, kn, sl) in enumerate(s):
                    g_nd[i, si, : len(nd)] = nd
                    g_lat[i, si, : len(nd)] = lat
                    g_kn[i, si] = kn
                    g_sl[i, si] = sl
                    present = True
            groups[key] = dict(nd=J(g_nd), lat=J(g_lat), kn=J(g_kn),
                               sl=J(g_sl), SL=SL, present=present)

        return dict(
            NK=NK, W=W, P=P, LV=LV, AB=AB,
            has_chains=any(r.cond.stats["n_coupled"] > 0 for r in rows),
            has_absorbed=any(r.cond.n_absorbed > 0 for r in rows),
            fu=J(fu), mem=J(mem), base=J(base), opk=J(opk), stk=J(stk),
            nmask=J(nmask), prol=J(prol), has_prol=J(has_prol),
            preds=J(preds), const=J(const), pidx=J(pidx), vc=J(vc), vp=J(vp),
            starts=J(starts),
            ab_fu=J(ab_fu), ab_opk=J(ab_opk), ab_const=J(ab_const),
            ab_seg=J(ab_seg), queues=groups)

    def _build_arrays(self):
        if self._arrays is not None:
            return self._arrays
        buckets = self._bucketize()
        bucket_arrays = [self._bucket_arrays(b) for b in buckets]
        # inverse permutation: concatenated bucket outputs -> global row ids
        flat = [i for b in buckets for i in b]
        inv = np.empty(len(flat), np.int64)
        inv[flat] = np.arange(len(flat))

        # composition arrays over cells (global row ids)
        CL = len(self.specs)
        RU = max(1, max(len(s.run_layer) for s in self.specs))
        runs = np.zeros((CL, RU), np.int64)
        reps = np.zeros((CL, RU), np.float32)
        fw = np.zeros((CL, RU), np.float32)
        fb = np.zeros((CL, max(1, RU - 1)), np.float32)
        # per-cell dynamic-energy knob vectors: Σ_runs reps · edyn[layer]
        # (energy is work — overlap shortens the makespan, not the joules)
        edyn_c = np.zeros((CL, self.n_knobs + 1), np.float64)
        pstat = np.zeros((CL,), np.float64)
        for ci, spec in enumerate(self.specs):
            nr = len(spec.run_layer)
            runs[ci, :nr] = np.asarray(self.row_of[ci])[spec.run_layer]
            reps[ci, :nr] = spec.run_reps
            fw[ci, :nr] = spec.fits_within
            if nr > 1:
                fb[ci, : nr - 1] = spec.fits_between
            if spec.edyn:
                for li, r in zip(spec.run_layer, spec.run_reps):
                    edyn_c[ci] += float(r) * np.asarray(spec.edyn[int(li)],
                                                        np.float64)
            pstat[ci] = spec.static_pj

        J = jnp.asarray
        self._arrays = dict(
            buckets=bucket_arrays, inv=J(inv), RU=RU,
            runs=J(runs), reps=J(reps), fw=J(fw), fb=J(fb),
            edyn=J(edyn_c.astype(np.float32)),
            pstat=J(pstat.astype(np.float32)))
        return self._arrays

    # -- the traced evaluator ----------------------------------------------

    _ROW_KEYS = ("fu", "mem", "base", "opk", "stk", "nmask", "prol",
                 "has_prol", "preds", "const", "pidx", "vc", "vp", "starts",
                 "ab_fu", "ab_opk", "ab_const", "ab_seg")

    def _row_fn(self, A, soft: bool):
        """One packed row's fixed point for a batch of candidates:
        (row-array dict, kn (K+1, B), tau) -> (makespan (B,), prologue
        completion (B,)).  The candidate axis is the trailing (lane) axis
        of every value array, so each scan step reads and writes whole
        lane rows: the relaxation's state write is a dense slice and the
        multi-slot queue's slot update a select, never a per-candidate
        scatter, and a queue's service order is a sort that carries its
        data along, never a gather across the lanes.  Python-level ``soft`` selects the hard max family or the
        τ-tempered LSE family at trace time; the queue families' static
        attributes (slot width, ordered-ness, presence) specialize the
        trace per bucket."""
        NK, W = A["NK"], A["W"]
        n_iters = self.n_iters
        qstatic = [(key, g["SL"], key.startswith("s1"), key.endswith("o"))
                   for key, g in A["queues"].items() if g["present"]]

        def fn(args, kn, tau):
            (fu, mem, base0, opk, stk, nmask, prol, has_prol, preds, const,
             pidx, vc, vp, starts, ab_fu, ab_opk, ab_const, ab_seg) = (
                args[k] for k in self._ROW_KEYS)
            B = kn.shape[1]
            if soft:
                floor = lambda x: softmaximum(jnp.float32(1.0), x, tau)
                reduce2 = lambda a, b: softmaximum(a, b, tau)
            else:
                floor = lambda x: jnp.maximum(jnp.float32(1.0), x)
                reduce2 = jnp.maximum
            w = floor(fu[:, None] * kn[opk] + mem[:, None] * kn[stk])
            if A["has_absorbed"]:
                aw = floor(ab_fu[:, None] * kn[ab_opk]) + ab_const[:, None]
                tot0 = jnp.concatenate([jnp.zeros((1, B), jnp.float32),
                                        jnp.cumsum(aw, axis=0)])
                prefix = tot0[1:] - tot0[ab_seg]
                extra = const[..., None] + jnp.where(
                    (pidx >= 0)[..., None], prefix[jnp.maximum(pidx, 0)],
                    0.0)
                vpre = jnp.where((vp >= 0)[:, None],
                                 prefix[jnp.maximum(vp, 0)], 0.0)
            else:
                # θ reaches the extras only through absorbed prefixes, so
                # here they keep one lane for the scan to broadcast: a
                # constant (NK+W, P, B) extra was re-filled at every step
                extra, vpre = const[..., None], 0.0
            w_pad = jnp.concatenate([w, jnp.zeros((W, B), jnp.float32)])
            v_lv = jnp.where((vc > NEG / 2)[:, None],
                             vc[:, None] + vpre + w_pad, NEG)

            def relax(b):
                return condensed_scan(w, b, extra, v_lv, preds, starts,
                                      tau=tau if soft else None,
                                      has_chains=A["has_chains"])

            def arrivals(nd0, lat0, knob, t, ordered):
                """A storage's accesses (SA,) as (mask, node, arrival and
                latency (SA, B) in service order, the order)."""
                msk = nd0 >= 0
                nd = jnp.maximum(nd0, 0)
                lat = lat0[:, None] * kn[knob]
                arr = jnp.where(msk[:, None], t[nd] - w[nd], _BIG)
                if ordered:   # provably static order: argsort = id
                    return msk, nd, arr, lat, None
                # one stable sort per candidate carries the latencies and
                # the order along: a gather along the access axis would
                # fetch across the candidate lanes element by element
                iota = jax.lax.broadcasted_iota(jnp.int32, arr.shape, 0)
                arr_s, lat_s, o = jax.lax.sort((arr, lat, iota), dimension=0,
                                               num_keys=1, is_stable=True)
                return msk, nd, arr_s, lat_s, o

            def unsort(done_s, o, msk, nd):
                """Service completions back in access order, as the
                queue's (node, need) pairs."""
                if o is not None:
                    # sort back by the order: on the chip a scatter across
                    # the candidate lanes cost ten times this sort
                    _, done_s = jax.lax.sort((o, done_s), dimension=0,
                                             num_keys=1)
                need = jnp.where(msk[:, None], done_s + fu[nd][:, None]
                                 - w[nd], NEG)
                return jnp.where(msk, nd, NK), need

            def q_single(ordered):
                def q(nd0, lat0, knob, t):
                    msk, nd, arr_s, lat_s, o = arrivals(nd0, lat0, knob, t,
                                                        ordered)
                    S = jnp.cumsum(lat_s, axis=0)
                    z = arr_s - S + lat_s
                    if soft:
                        done_s = S + tau * jax.lax.cumlogsumexp(z / tau,
                                                                axis=0)
                    else:
                        done_s = S + jax.lax.cummax(z, axis=0)
                    return unsort(done_s, o, msk, nd)
                return q

            def q_multi(ordered, SL):
                slot = jnp.arange(SL)[:, None]

                def q(nd0, lat0, knob, slots, t):
                    msk, nd, arr_s, lat_s, o = arrivals(nd0, lat0, knob, t,
                                                        ordered)

                    def step(free, inp):
                        a, l = inp
                        # earliest-free slot (first on ties), read and
                        # written by select so the candidates stay on
                        # the lanes; free[k] is min(free)
                        hot = slot == jnp.argmin(free, axis=0)
                        done = reduce2(a, jnp.sum(jnp.where(hot, free, 0.0),
                                                  axis=0)) + l
                        return jnp.where(hot, done, free), done

                    free0 = jnp.broadcast_to(
                        jnp.where(slot < slots, 0.0, _BIG), (SL, B))
                    _, done_s = jax.lax.scan(step, free0, (arr_s, lat_s))
                    return unsort(done_s, o, msk, nd)
                return q

            t = relax(jnp.broadcast_to(base0[:, None], (NK, B)))
            for _ in range(n_iters):
                need_full = jnp.full((NK + 1, B), NEG, jnp.float32)
                for key, SL, single, ordered in qstatic:
                    qa = args["queues"][key]
                    if single:
                        nd_g, need_g = jax.vmap(
                            q_single(ordered), in_axes=(0, 0, 0, None))(
                            qa["nd"], qa["lat"], qa["kn"], t)
                    else:
                        nd_g, need_g = jax.vmap(
                            q_multi(ordered, SL),
                            in_axes=(0, 0, 0, 0, None))(
                            qa["nd"], qa["lat"], qa["kn"], qa["sl"], t)
                    need_full = need_full.at[nd_g.reshape(-1)].max(
                        need_g.reshape(-1, B))
                if soft:
                    b = softmaximum(base0[:, None], need_full[:NK], tau)
                else:
                    b = jnp.maximum(base0[:, None], need_full[:NK])
                t = relax(b)

            tm = jnp.where(nmask[:, None], t, NEG)
            tp = jnp.where(prol[:, None], t, NEG)
            if soft:
                m = softmax_reduce(tm, tau, axis=0)
                p = softmax_reduce(tp, tau, axis=0)
            else:
                m = tm.max(axis=0)
                p = tp.max(axis=0)
            return m, jnp.where(has_prol > 0, p, 0.0)

        return fn

    def _matrix_fn(self, soft: bool):
        """knobs (B, K) [, tau] -> per-cell ``(cycles (B, S), energy (B,
        S))``, fully traced: one wavefront fixed point per shape bucket,
        vmapped over the bucket's rows with the candidates on the trailing
        axis (all inside the one trace), bucket outputs re-ordered to
        global rows, then the run-length composition per cell.  The energy
        objective rides the SAME trace — the pre-folded dynamic term
        ``Σₖ edynₖ / θₖ`` plus the static term ``P_static · cycles`` — so
        a 3-objective evaluation is still a single dispatch with no second
        pass.  Every sum over a cell's runs or knobs goes through
        :func:`_sum_last`, so the TPU and the host round alike."""
        A = self._build_arrays()

        def bucket_args(BA):
            d = {k: BA[k] for k in self._ROW_KEYS}
            d["queues"] = {key: {f: g[f] for f in ("nd", "lat", "kn", "sl")}
                           for key, g in BA["queues"].items()
                           if g["present"]}
            return d

        per_bucket = [(self._row_fn(BA, soft), bucket_args(BA))
                      for BA in A["buckets"]]
        inv = A["inv"]
        runs, reps, fw, fb = A["runs"], A["reps"], A["fw"], A["fb"]
        edyn, pstat = A["edyn"], A["pstat"]
        RU = A["RU"]

        def fn(knobs, tau):
            kn = jnp.concatenate([knobs.astype(jnp.float32),
                                  jnp.ones((knobs.shape[0], 1), jnp.float32)],
                                 axis=1)
            ms, ps = [], []
            # one named scope per bucket (``stats()["bucket_detail"]``
            # order), so a device trace attributes each scan to its bucket
            for i, (row_fn, row_args) in enumerate(per_bucket):
                with jax.named_scope(f"packed.bucket{i:02d}"):
                    m_b, p_b = jax.vmap(row_fn, in_axes=(0, None, None))(
                        row_args, kn.T, tau)
                ms.append(m_b)
                ps.append(p_b)
            with jax.named_scope("packed.compose"):
                m = jnp.concatenate(ms)[inv].T
                p = jnp.concatenate(ps)[inv].T
                mr, pr = m[:, runs], p[:, runs]
                clip = ((lambda a, b: -softmaximum(-a, -b, tau)) if soft
                        else jnp.minimum)
                total = _sum_last(reps * mr)
                within = _sum_last((reps - 1.0) * clip(pr, mr) * fw)
                if RU > 1:
                    between = _sum_last(clip(pr[..., 1:], mr[..., :-1]) * fb)
                else:
                    between = 0.0
                cycles = total - within - between
                # DVFS-style dynamic term (faster units burn more pJ per
                # op) plus leakage over the makespan — analytic in θ, and
                # the static part differentiates through the soft makespan
                energy = (_sum_last(edyn * (1.0 / kn[:, None]))
                          + pstat * cycles)
            return cycles, energy

        return fn

    # -- public evaluation surface -----------------------------------------

    def _full_fn(self) -> Callable:
        """Cached ``jit`` hard evaluator of the FULL objective tuple:
        ``fn(knobs (B, K)) -> ((B, S) cycles, (B, S) energy pJ)`` — the
        whole matrix in one dispatch, energy in the same trace."""
        fn = self._compiled.get("hard")
        if fn is None:
            f = self._matrix_fn(soft=False)
            fn = jax.jit(lambda k: f(k, jnp.float32(1.0)))
            self._compiled["hard"] = fn
        return fn

    def evaluate_fn(self) -> Callable:
        """The cycles-only view of :meth:`_full_fn`:
        ``fn(knobs (B, K)) -> (B, S) cycles`` (same compiled dispatch)."""
        full = self._full_fn()
        return lambda kt: full(kt)[0]

    def n_shards(self, n_devices: Optional[int] = None) -> int:
        """Devices the sharded evaluator spreads the candidate axis over:
        ``n_devices`` capped by what the backend exposes (force more host
        CPU devices with ``XLA_FLAGS=--xla_force_host_platform_device_count
        =8``), all local devices when ``None``."""
        avail = jax.local_device_count()
        if n_devices is None:
            return avail
        if not (1 <= n_devices <= avail):
            raise ValueError(f"n_devices must be in [1, {avail}], "
                             f"got {n_devices}")
        return int(n_devices)

    def sharded_fn(self, n_devices: Optional[int] = None) -> Callable:
        """Cached device-sharded hard evaluator: ``fn(knobs (B, K)) ->
        ((B, S) cycles, (B, S) energy)`` with the CANDIDATE axis split
        across ``n_shards`` devices via ``jax.shard_map`` — each device
        runs the same batched packed evaluator over its B/D slice, so
        results are bitwise identical to the single-device path
        (per-candidate rows are independent; asserted by
        ``tests/test_serve.py``).  B must be a multiple of the device
        count — ``evaluate(sharded=True)`` pads for you."""
        D = self.n_shards(n_devices)
        key = ("sharded", D)
        fn = self._compiled.get(key)
        if fn is None:
            from jax.sharding import Mesh, PartitionSpec as P
            f = self._matrix_fn(soft=False)
            batched = lambda k: f(k, jnp.float32(1.0))
            mesh = Mesh(np.asarray(jax.local_devices()[:D]), ("cand",))
            # check_vma=False: the body is the unsharded evaluator, whose
            # scan carries start as replicated constants and come out
            # varying over "cand"; the per-device program is unchanged
            fn = jax.jit(jax.shard_map(batched, mesh=mesh,
                                       in_specs=P("cand"),
                                       out_specs=(P("cand"), P("cand")),
                                       check_vma=False))
            self._compiled[key] = fn
        return fn

    def evaluate(self, knob_thetas: np.ndarray,
                 chunk: Optional[int] = None, sharded: bool = False,
                 n_devices: Optional[int] = None) -> np.ndarray:
        """(B, n_knobs) candidates -> (B, S) estimated cycles.

        ``chunk`` bounds peak memory; every partial chunk is padded to the
        compiled batch shape (no per-remainder re-trace).  ``sharded``
        splits the candidate axis across ``n_devices`` local devices
        (``sharded_fn``) for near-linear multi-device throughput with
        bitwise-identical results; the batch is padded with θ = 1 rows up
        to a device multiple and sliced back."""
        return self.evaluate_full(knob_thetas, chunk=chunk, sharded=sharded,
                                  n_devices=n_devices)[0]

    def evaluate_full(self, knob_thetas: np.ndarray,
                      chunk: Optional[int] = None, sharded: bool = False,
                      n_devices: Optional[int] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """(B, n_knobs) candidates -> ``((B, S) cycles, (B, S) energy
        pJ)``, both objectives from the SAME compiled dispatch (energy is
        one folded matvec plus the static term inside the latency trace —
        see :meth:`_matrix_fn`); cells built without energy coefficients
        report 0.  Options as :meth:`evaluate`."""
        if sharded:
            mult = self.n_shards(n_devices)
            fn = self.sharded_fn(mult)
        else:
            mult = 1
            fn = self._full_fn()
        kt = np.atleast_2d(np.asarray(knob_thetas, np.float32))
        B = kt.shape[0]

        def run(block, rows):
            """Evaluate ``block`` padded with θ = 1 rows up to ``rows``:
            upload and launch, wait for the device, then fetch."""
            n = block.shape[0]
            if not sharded:
                self._last_rows = rows
            with span("packed.dispatch"):
                if n < rows:
                    block = np.concatenate(
                        [block, np.ones((rows - n, kt.shape[1]), np.float32)])
                out = fn(jnp.asarray(block))
            with span("packed.wait"):
                c, en = jax.block_until_ready(out)
            with span("packed.fetch"):
                return np.asarray(c)[:n], np.asarray(en)[:n]

        up = lambda n: -(-n // mult) * mult   # round up to device multiple
        if chunk is None or B <= chunk:
            return run(kt, up(B))
        step = up(chunk)
        out_c = np.empty((B, self.n_cells), dtype=np.float32)
        out_e = np.empty((B, self.n_cells), dtype=np.float32)
        for s in range(0, B, step):
            e = min(s + step, B)
            out_c[s:e], out_e[s:e] = run(kt[s:e], step)
        return out_c, out_e

    def export_training_table(self, knob_thetas: np.ndarray,
                              chunk: Optional[int] = None
                              ) -> Dict[str, np.ndarray]:
        """Sweep-output export for surrogate training
        (``repro.surrogate``): evaluate ``(N, n_knobs)`` candidates plus
        the θ = 1 reference in ONE chunked pass and return the
        self-describing table ``{"theta" (N, K), "cycles" (N, S),
        "energy" (N, S), "cycles_base" (S,), "energy_base" (S,)}`` —
        baselines from the same dispatch, so ratios are exactly the
        quantities the packed engine normalizes by."""
        kt = np.atleast_2d(np.asarray(knob_thetas, np.float32))
        stacked = np.concatenate(
            [np.ones((1, kt.shape[1]), np.float32), kt], axis=0)
        cycles, energy = self.evaluate_full(stacked, chunk=chunk)
        return {"theta": kt,
                "cycles": cycles[1:], "energy": energy[1:],
                "cycles_base": np.asarray(cycles[0], np.float64),
                "energy_base": np.asarray(energy[0], np.float64)}

    def grad_fn(self, baselines: np.ndarray) -> Callable:
        """Cached ``jit(vmap(value_and_grad))`` over the soft family:
        ``fn(knobs (B, K), tau) -> (mean normalized latency (B,),
        d latency / d knob (B, K))`` — the whole matrix's end-to-end
        gradient in one dispatch (τ traced, annealing never re-traces)."""
        key = ("grad", np.asarray(baselines, np.float64).tobytes())
        fn = self._compiled.get(key)
        if fn is None:
            f = self._matrix_fn(soft=True)
            bl = jnp.asarray(baselines, jnp.float32)

            def val(knobs, tau):    # one candidate: a lane axis of width 1
                return (f(knobs[None], tau)[0][0] / bl).mean()

            fn = jax.jit(jax.vmap(jax.value_and_grad(val),
                                  in_axes=(0, None)))
            self._compiled[key] = fn
        return fn

    def grad3_fn(self, baselines: np.ndarray,
                 energy_baselines: np.ndarray) -> Callable:
        """Cached multi-objective gradient dispatch over the soft family:
        ``fn(knobs (B, K), tau) -> (values (B, 2), jacobian (B, 2, K))``
        where row 0 is mean normalized latency and row 1 mean normalized
        energy — one ``jacrev`` through the shared soft trace, so the
        energy gradient (analytic ``-edyn_k/θ_k²`` plus the static term
        through the soft makespan) costs no extra dispatch."""
        key = ("grad3", np.asarray(baselines, np.float64).tobytes(),
               np.asarray(energy_baselines, np.float64).tobytes())
        fn = self._compiled.get(key)
        if fn is None:
            f = self._matrix_fn(soft=True)
            bl = jnp.asarray(baselines, jnp.float32)
            ebl = jnp.asarray(np.maximum(
                np.asarray(energy_baselines, np.float64), 1e-30), jnp.float32)

            def vals(knobs, tau):
                c, en = f(knobs[None], tau)
                return jnp.stack([(c[0] / bl).mean(), (en[0] / ebl).mean()])

            def vg(knobs, tau):
                return vals(knobs, tau), jax.jacrev(vals)(knobs, tau)

            fn = jax.jit(jax.vmap(vg, in_axes=(0, None)))
            self._compiled[key] = fn
        return fn
