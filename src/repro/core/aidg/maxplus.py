"""Max-plus evaluation of the AIDG in JAX (the TPU-native adaptation).

Three engines for the same recurrence  t_i = w_i + max(base_i, max_j (t_j + d_ji)),
all consuming the build-time ``CompiledAIDG`` artifact
(trace → AIDG → LevelSchedule → CompiledAIDG, see ``builder.compile_aidg``):

* ``longest_path_wavefront`` — the default: a ``jax.lax.scan`` over
  topological *levels* with vectorized predecessor gathers and a max over
  the predecessor axis inside each level.  Sequential depth is the DAG's
  critical depth (``LevelSchedule.n_levels``), typically far smaller than
  the node count — the compiled-estimator payoff of Lübeck et al. 2024.
* ``longest_path_scan`` — exact forward pass as a ``lax.scan`` over nodes
  (one sequential step per instruction); kept as the reference device path.
* ``longest_path_blocked`` — the AIDG adjacency banded into dense blocks;
  each block solved by the max-plus Kleene closure  t_b = M*_b ⊗ h_b  with
  M* computed by repeated max-plus squaring, the whole block recurrence a
  single device-resident ``lax.scan``.  ``matmul=maxplus_matmul_pallas``
  routes every ⊗ through the ``repro.kernels.maxplus`` Pallas kernel
  (max/add on the VPU in the MXU-aligned layout).

All three are differentiable in the latency parameters and ``vmap``-able
over parameter batches; ``fixed_point_jax(engine=...)`` selects the
relaxation used between storage-queueing folds, and ``fixed_point_batch``
vmaps the whole fixed point.

The storage request-slot queueing (arrival-ordered service, Figs. 12/13) is
``slot_queue_scan``: per storage, accesses sorted by arrival relax against a
sorted slot vector via ``lax.scan`` — also vmappable over parameters.

**The smooth relaxation family** (gradient-based co-design, §1/§7): every
hard ``max`` above is piecewise-linear in the latency parameters, so
``jax.grad`` returns a subgradient that is blind across kinks and dead on
plateaus.  ``longest_path_soft`` / ``slot_queue_soft`` / ``fixed_point_soft``
replace each ``max`` with the temperature-τ log-sum-exp

    softmax_τ(x₁, …, x_K) = τ · log Σ_k exp(x_k / τ)
                          ∈ [max_k x_k,  max_k x_k + τ·log K]

which is smooth everywhere, monotone in every argument, and recovers the
exact wavefront result as τ → 0 (the overestimate is at most τ·log K per
reduction, K = in-degree + 1).  τ is a *traced* scalar, so annealing it
inside an optimization loop never re-traces the compiled evaluator —
``repro.core.aidg.gradient`` builds projected Adam on top of this.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .builder import AIDG, CompiledAIDG, CondensedAIDG, NEG, compile_aidg, \
    condense_aidg

__all__ = [
    "ENGINES",
    "DEFAULT_ENGINE",
    "longest_path_wavefront",
    "longest_path_scan",
    "longest_path_blocked",
    "longest_path_condensed",
    "condensed_prefix",
    "condensed_scan",
    "slot_queue_scan",
    "fixed_point_jax",
    "fixed_point_batch",
    "maxplus_matmul_jnp",
    "maxplus_closure",
    "softmaximum",
    "softmax_reduce",
    "longest_path_soft",
    "slot_queue_soft",
    "fixed_point_soft",
]

# NEG (the max-plus -inf sentinel) is defined once in builder and
# re-exported here — condense_aidg writes it into coupling tables that the
# evaluators compare against, so there must be exactly one definition

ENGINES = ("wavefront", "scan", "blocked", "condensed")
DEFAULT_ENGINE = "wavefront"

AIDGLike = Union[AIDG, CompiledAIDG]


def _as_compiled(aidg: AIDGLike) -> CompiledAIDG:
    return aidg if isinstance(aidg, CompiledAIDG) else compile_aidg(aidg)


# ---------------------------------------------------------------------------
# per-node scan evaluation (reference device path)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n",))
def _scan_impl(n: int, work: jnp.ndarray, base: jnp.ndarray,
               preds: jnp.ndarray, pred_extra: jnp.ndarray) -> jnp.ndarray:
    """t_i = w_i + max(base_i, max_k t[preds_ik] + extra_ik), forward order."""

    def step(t, i):
        js = preds[i]
        vals = jnp.where(js >= 0, t[jnp.maximum(js, 0)] + pred_extra[i], NEG)
        m = jnp.maximum(base[i], vals.max())
        t = t.at[i].set(m + work[i])
        return t, ()

    t0 = jnp.zeros((n,), dtype=jnp.float32)
    t, _ = jax.lax.scan(step, t0, jnp.arange(n))
    return t


def longest_path_scan(aidg: AIDGLike, work: Optional[jnp.ndarray] = None,
                      base: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Exact forward relaxation as a ``lax.scan`` over nodes (one
    sequential step per instruction) — the reference device path the
    wavefront and blocked engines are checked against."""
    ca = _as_compiled(aidg)
    a = ca.aidg
    w = jnp.asarray(a.work if work is None else work, jnp.float32)
    b = jnp.asarray(a.base if base is None else base, jnp.float32)
    return _scan_impl(a.n, w, b, jnp.asarray(a.preds),
                      jnp.asarray(a.pred_extra))


# ---------------------------------------------------------------------------
# level-scheduled wavefront evaluation (the default engine)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n", "width"))
def _wavefront_impl(n: int, width: int, work: jnp.ndarray, base: jnp.ndarray,
                    preds_lv: jnp.ndarray, extra_lv: jnp.ndarray,
                    starts: jnp.ndarray, order: jnp.ndarray,
                    rank: jnp.ndarray) -> jnp.ndarray:
    """One ``lax.scan`` step per *level* over the level-major renumbering:
    each step slices a contiguous ``width`` window of (preds, extra, work,
    base), gathers the (strictly shallower, already-final) predecessor
    times, reduces over the predecessor axis, and writes the window back
    with one dynamic-update-slice — no scatters.  Window lanes that spill
    past the level's true extent compute garbage from not-yet-final inputs
    and are deterministically overwritten when their own level runs."""
    work_lv = jnp.concatenate(
        [work.astype(jnp.float32)[order], jnp.zeros((width,), jnp.float32)])
    base_lv = jnp.concatenate(
        [base.astype(jnp.float32)[order], jnp.full((width,), NEG, jnp.float32)])
    p = preds_lv.shape[1]

    def step(t, start):
        js = jax.lax.dynamic_slice(preds_lv, (start, 0), (width, p))
        ex = jax.lax.dynamic_slice(extra_lv, (start, 0), (width, p))
        wv = jax.lax.dynamic_slice(work_lv, (start,), (width,))
        bv = jax.lax.dynamic_slice(base_lv, (start,), (width,))
        vals = jnp.where(js >= 0, t[jnp.maximum(js, 0)] + ex, NEG)
        m = jnp.maximum(bv, vals.max(axis=1))
        t = jax.lax.dynamic_update_slice(t, m + wv, (start,))
        return t, ()

    t0 = jnp.zeros((n + width,), dtype=jnp.float32)
    t, _ = jax.lax.scan(step, t0, starts)
    return t[rank]


def longest_path_wavefront(aidg: AIDGLike,
                           work: Optional[jnp.ndarray] = None,
                           base: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Exact longest path in ``n_levels`` sequential device steps (vs ``n``
    for ``longest_path_scan``) — identical results, the wavefront order is
    just a parallel schedule of the same relaxation."""
    ca = _as_compiled(aidg)
    a = ca.aidg
    s = ca.schedule
    w = jnp.asarray(a.work if work is None else work, jnp.float32)
    b = jnp.asarray(a.base if base is None else base, jnp.float32)
    return _wavefront_impl(a.n, s.width, w, b, jnp.asarray(ca.preds_lv),
                           jnp.asarray(ca.extra_lv), jnp.asarray(s.starts),
                           jnp.asarray(s.order), jnp.asarray(s.rank))


# ---------------------------------------------------------------------------
# condensed wavefront evaluation (chain super-edges, sequential depth =
# the CONDENSED critical depth)
# ---------------------------------------------------------------------------


def condensed_prefix(cond: CondensedAIDG, w: jnp.ndarray) -> jnp.ndarray:
    """(n_ab,) inclusive prefix weights of every absorbed node: the exact
    θ-reweighted super-edge dot product ``Σ_prefix (edge extra + w_i)``,
    one ``cumsum`` + two gathers (segment boundaries are static)."""
    aw = w[jnp.asarray(cond.absorbed)] + jnp.asarray(cond.ab_const)
    tot0 = jnp.concatenate([jnp.zeros((1,), aw.dtype), jnp.cumsum(aw)])
    pos = jnp.arange(cond.n_absorbed)
    return tot0[pos + 1] - tot0[jnp.asarray(cond.ab_segstart)]


def condensed_scan(w_perm: jnp.ndarray, b_perm: jnp.ndarray,
                   extra_lv: jnp.ndarray, v_lv: jnp.ndarray,
                   preds_lv: jnp.ndarray, starts: jnp.ndarray,
                   tau=None, has_chains: bool = True) -> jnp.ndarray:
    """The condensed wavefront: one ``lax.scan`` step per UNIT level.  Each
    step gathers the (already-final) cross-unit predecessor times, reduces
    with the window's base, and then resolves every affine chain inside
    the window closed-form with one ``associative_scan`` of the max-plus
    affine composition

        (v₁, h₁) ∘ (v₂, h₂) = (v₁ + v₂, max(h₁ + v₂, h₂))

    (the τ-soft family composes under the SAME operator with
    ``softmaximum`` — smooth chains stay one associative scan).  ``v_lv``
    is the per-permuted-slot coupling weight (NEG = chain break), already
    including the target's own work; everything is in the level-major
    permuted layout of ``builder.condense_aidg``.  ``has_chains=False``
    (a trace-time constant) skips the affine scan entirely for graphs
    with no coupled nodes — the step then reduces to the plain wavefront.

    The value arrays may carry trailing candidate axes (``w_perm`` (NK,
    *lanes), ``extra_lv`` (NK+W, P, *lanes), or one lane where the extras
    are the same for every candidate); the graph arrays ``preds_lv`` and
    ``starts`` never do.  Each step then reads and writes whole ``lanes``
    rows, so with one trailing batch axis the candidates sit on the TPU's
    lanes and every state write is a dense slice."""
    NK = w_perm.shape[0]
    lanes = w_perm.shape[1:]
    W = preds_lv.shape[0] - NK
    P = preds_lv.shape[1]
    zeros = (0,) * len(lanes)
    work_pad = jnp.concatenate([w_perm, jnp.zeros((W,) + lanes, jnp.float32)])
    base_pad = jnp.concatenate([b_perm,
                                jnp.full((W,) + lanes, NEG, jnp.float32)])

    def op(a, c):
        va, ha = a
        vb, hb = c
        if tau is None:
            h = jnp.maximum(ha + vb, hb)
        else:
            h = softmaximum(ha + vb, hb, tau)
        return jnp.maximum(va + vb, NEG), h

    def step(t, start):
        js = jax.lax.dynamic_slice(preds_lv, (start, 0), (W, P))
        ex = jax.lax.dynamic_slice(extra_lv, (start, 0) + zeros,
                                   (W, P) + extra_lv.shape[2:])
        wv = jax.lax.dynamic_slice(work_pad, (start,) + zeros, (W,) + lanes)
        bv = jax.lax.dynamic_slice(base_pad, (start,) + zeros, (W,) + lanes)
        vv = jax.lax.dynamic_slice(v_lv, (start,) + zeros, (W,) + lanes)
        live = (js >= 0).reshape((W, P) + (1,) * len(lanes))
        vals = jnp.where(live, t[jnp.maximum(js, 0)] + ex, NEG)
        # compose the reductions instead of concatenating (LSE composes
        # exactly: lse(b, v₁..v_k) = lse(b, lse(v)) — and the fused
        # gather→where→reduce chain avoids materializing a (W, P+1) buffer)
        if tau is None:
            r = jnp.maximum(bv, vals.max(axis=1))
        else:
            r = softmaximum(bv, softmax_reduce(vals, tau, axis=1), tau)
        if has_chains:
            _, tw = jax.lax.associative_scan(op, (vv, r + wv))
        else:
            tw = r + wv
        return jax.lax.dynamic_update_slice(t, tw, (start,) + zeros), ()

    t0 = jnp.zeros((NK + W,) + lanes, dtype=jnp.float32)
    t, _ = jax.lax.scan(step, t0, starts)
    return t[:NK]


def _condensed_relax(cond: CondensedAIDG, w: jnp.ndarray, b: jnp.ndarray,
                     tau=None) -> jnp.ndarray:
    """Condensed relaxation returning the FULL (n,) completion-time vector:
    kept nodes via the unit-level (soft) wavefront with in-window affine
    chains, absorbed nodes reconstructed as anchor + exact prefix sum.
    ``tau`` None = hard max; a traced scalar = the smooth LSE family
    (absorbed steps and chain couplings keep their exact sums — a tighter
    relaxation than softening every per-node max)."""
    kept_perm = jnp.asarray(cond.kept_perm)
    wk = w[kept_perm].astype(jnp.float32)
    bk = b[kept_perm].astype(jnp.float32)
    W = cond.schedule.width
    vc = jnp.asarray(cond.v_const_lv)
    coupled = vc > NEG / 2
    w_pad = jnp.concatenate([wk, jnp.zeros((W,), jnp.float32)])
    if cond.n_absorbed:
        prefix = condensed_prefix(cond, w.astype(jnp.float32))
        pidx = jnp.asarray(cond.pidx_lv)
        extra = (jnp.asarray(cond.const_lv)
                 + jnp.where(pidx >= 0, prefix[jnp.maximum(pidx, 0)], 0.0))
        vp = jnp.asarray(cond.v_pidx_lv)
        vpre = jnp.where(vp >= 0, prefix[jnp.maximum(vp, 0)], 0.0)
    else:
        extra = jnp.asarray(cond.const_lv)
        vpre = 0.0
    v_lv = jnp.where(coupled, vc + vpre + w_pad, NEG)
    tk = condensed_scan(wk, bk, extra, v_lv, jnp.asarray(cond.preds_lv),
                        jnp.asarray(cond.schedule.starts), tau=tau,
                        has_chains=cond.stats["n_coupled"] > 0)
    t = jnp.zeros((cond.n,), jnp.float32).at[kept_perm].set(tk)
    if cond.n_absorbed:
        t = t.at[jnp.asarray(cond.absorbed)].set(
            tk[jnp.asarray(cond.ab_anchor_perm)] + prefix)
    return t


def longest_path_condensed(aidg: AIDGLike,
                           work: Optional[jnp.ndarray] = None,
                           base: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Exact longest path in ``levels_condensed`` sequential device steps:
    chain interiors are folded into θ-parametric super-edges
    (``builder.condense_aidg``), so chain-dominated graphs lose most of
    their sequential scan length.  Identical to ``longest_path_wavefront``
    for any work vector with the ≥ 1-cycle floor (all shipped evaluators)."""
    ca = _as_compiled(aidg)
    a = ca.aidg
    cond = condense_aidg(a)
    w = jnp.asarray(a.work if work is None else work, jnp.float32)
    b = jnp.asarray(a.base if base is None else base, jnp.float32)
    return _condensed_relax(cond, w, b)


# ---------------------------------------------------------------------------
# blocked max-plus closure evaluation (device-resident)
# ---------------------------------------------------------------------------


def maxplus_matmul_jnp(A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """(A ⊗ B)_ij = max_k A_ik + B_kj (pure-jnp reference path)."""
    return jnp.max(A[..., :, :, None] + B[..., None, :, :], axis=-2)


def maxplus_closure(M: jnp.ndarray, steps: int,
                    matmul=maxplus_matmul_jnp) -> jnp.ndarray:
    """Kleene star M* = (I ⊕ M)^(2^steps) by repeated max-plus squaring."""
    n = M.shape[-1]
    eye = jnp.where(jnp.eye(n, dtype=bool), 0.0, NEG)
    P = jnp.maximum(M, eye)
    for _ in range(steps):
        P = jnp.maximum(P, matmul(P, P))
    return P


def _blocked_structure(ca: CompiledAIDG, block: int) -> Tuple[np.ndarray, ...]:
    """Banded structure-only edge matrices, cached per block size on the
    CompiledAIDG.

    Returns (D_diag, D_sub, far_src, far_dst, far_w): per block b,
    ``D_diag[b][i, j]`` is the extra delay of edge (local j -> local i)
    inside the block (NEG if absent) *without* w_i (runtime work is folded
    at eval so the blocked engine stays θ-reweightable), ``D_sub`` the same
    for edges from the previous block, and the ``far_*`` arrays a padded
    per-block gather list for edges reaching further back (pad: weight NEG,
    dst ``block`` — a scratch slot)."""
    hit = ca._block_cache.get(block)
    if hit is not None:
        return hit
    a = ca.aidg
    n = a.n
    nb = max(1, (n + block - 1) // block)
    Dd = np.full((nb, block, block), NEG, dtype=np.float32)
    Ds = np.full((nb, block, block), NEG, dtype=np.float32)
    far: Dict[int, list] = {b: [] for b in range(nb)}
    for i in range(n):
        bi, li = divmod(i, block)
        for k in range(a.preds.shape[1]):
            j = int(a.preds[i, k])
            if j < 0:
                break
            d = float(a.pred_extra[i, k])
            bj, lj = divmod(j, block)
            if bj == bi:
                Dd[bi, li, lj] = max(Dd[bi, li, lj], d)
            elif bj == bi - 1:
                Ds[bi, li, lj] = max(Ds[bi, li, lj], d)
            else:
                far[bi].append((j, li, d))
    F = max(1, max(len(v) for v in far.values()))
    far_src = np.zeros((nb, F), dtype=np.int32)
    far_dst = np.full((nb, F), block, dtype=np.int32)
    far_w = np.full((nb, F), NEG, dtype=np.float32)
    for b, lst in far.items():
        for k, (j, li, d) in enumerate(lst):
            far_src[b, k] = j
            far_dst[b, k] = li
            far_w[b, k] = d
    out = (Dd, Ds, far_src, far_dst, far_w)
    ca._block_cache[block] = out
    return out


@partial(jax.jit, static_argnames=("n", "block", "matmul"))
def _blocked_core(n: int, block: int, Dd: jnp.ndarray, Ds: jnp.ndarray,
                  far_src: jnp.ndarray, far_dst: jnp.ndarray,
                  far_w: jnp.ndarray, work: jnp.ndarray, base: jnp.ndarray,
                  matmul: Callable = maxplus_matmul_jnp) -> jnp.ndarray:
    """Device-resident block recurrence: for each block b,
    h_b = max(base+w, far-edge gathers, M_sub ⊗ t_{b-1}), t_b = M*_bb ⊗ h_b,
    the whole loop one ``lax.scan`` (carry: the global t vector)."""
    nb = Dd.shape[0]
    pad = nb * block - n
    w_p = jnp.concatenate(
        [work.astype(jnp.float32), jnp.zeros((pad,), jnp.float32)])
    b_p = jnp.concatenate(
        [base.astype(jnp.float32), jnp.full((pad,), NEG, jnp.float32)])
    wb = w_p.reshape(nb, block)
    h0 = (b_p + w_p).reshape(nb, block)
    steps = int(np.ceil(np.log2(max(2, block))))
    # absorb runtime work into edge weights: m_ij = d_ij + w_i (target row)
    Md = Dd + wb[:, :, None]
    Ms = Ds + wb[:, :, None]
    closures = jax.vmap(lambda M: maxplus_closure(M, steps, matmul))(Md)

    def step(t, inp):
        bi, clo, Ms_b, w_b, fs, fd, fwgt, h_b = inp
        start = jnp.maximum(bi - 1, 0) * block
        prev = jax.lax.dynamic_slice(t, (start,), (block,))
        # block 0 has an all-NEG Ms_b, so the (garbage) prev is masked out
        h = jnp.maximum(h_b, matmul(Ms_b, prev[:, None])[:, 0])
        w_pad = jnp.concatenate([w_b, jnp.zeros((1,), jnp.float32)])
        contrib = t[fs] + fwgt + w_pad[fd]        # pad rows: + NEG, inert
        h = jnp.concatenate([h, jnp.full((1,), NEG, jnp.float32)])
        h = h.at[fd].max(contrib)[:block]
        tb = matmul(clo, h[:, None])[:, 0]        # closure includes identity
        t = jax.lax.dynamic_update_slice(t, tb, (bi * block,))
        return t, ()

    t0 = jnp.full((nb * block,), NEG, dtype=jnp.float32)
    t, _ = jax.lax.scan(
        step, t0, (jnp.arange(nb), closures, Ms, wb, far_src, far_dst, far_w,
                   h0))
    return t[:n]


def longest_path_blocked(aidg: AIDGLike, block: int = 128,
                         matmul: Callable = maxplus_matmul_jnp,
                         work: Optional[jnp.ndarray] = None,
                         base: Optional[jnp.ndarray] = None) -> np.ndarray:
    """Fully device-resident blocked evaluation (one ``lax.scan`` over
    blocks).  Pass ``matmul=repro.kernels.maxplus.maxplus_matmul_pallas`` to
    run every max-plus ⊗ through the Pallas kernel."""
    ca = _as_compiled(aidg)
    a = ca.aidg
    Dd, Ds, fs, fd, fw = _blocked_structure(ca, block)
    w = jnp.asarray(a.work if work is None else work, jnp.float32)
    b = jnp.asarray(a.base if base is None else base, jnp.float32)
    t = _blocked_core(a.n, block, jnp.asarray(Dd), jnp.asarray(Ds),
                      jnp.asarray(fs), jnp.asarray(fd), jnp.asarray(fw),
                      w, b, matmul=matmul)
    return np.asarray(t, dtype=np.float64)


# ---------------------------------------------------------------------------
# engine dispatch
# ---------------------------------------------------------------------------


def _relaxer(ca: CompiledAIDG, engine: str, block: int = 128
             ) -> Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]:
    """(work, base) -> t closure for the chosen engine, structure arrays
    bound once (they are jit-constant across a sweep)."""
    a = ca.aidg
    if engine == "wavefront":
        s = ca.schedule
        pl, el = jnp.asarray(ca.preds_lv), jnp.asarray(ca.extra_lv)
        st = jnp.asarray(s.starts)
        od, rk = jnp.asarray(s.order), jnp.asarray(s.rank)
        return lambda w, b: _wavefront_impl(a.n, s.width, w, b, pl, el, st,
                                            od, rk)
    if engine == "scan":
        preds = jnp.asarray(a.preds)
        extra = jnp.asarray(a.pred_extra)
        return lambda w, b: _scan_impl(a.n, w, b, preds, extra)
    if engine == "blocked":
        Dd, Ds, fs, fd, fw = _blocked_structure(ca, block)
        Dd, Ds = jnp.asarray(Dd), jnp.asarray(Ds)
        fs, fd, fw = jnp.asarray(fs), jnp.asarray(fd), jnp.asarray(fw)
        return lambda w, b: _blocked_core(a.n, block, Dd, Ds, fs, fd, fw,
                                          w, b)
    if engine == "condensed":
        cond = condense_aidg(a)
        return lambda w, b: _condensed_relax(cond, w, b)
    raise ValueError(f"unknown engine {engine!r}; choose from {ENGINES}")


# ---------------------------------------------------------------------------
# storage request-slot queueing in jnp (vmappable)
# ---------------------------------------------------------------------------


def slot_queue_scan(arrival: jnp.ndarray, lat: jnp.ndarray, slots: int
                    ) -> jnp.ndarray:
    """Service completion per access, arrival-ordered FIFO over ``slots``
    request slots.  ``arrival``/``lat`` are in *arrival order*.

    A single-slot queue is max-plus *linear*:
    ``done_k = max(arrival_k, done_{k-1}) + lat_k`` unrolls to
    ``done_k = S_k + max_{j<=k} (arrival_j - S_{j-1})`` with S the latency
    prefix sum — one ``cumsum`` + one ``cummax`` instead of k sequential
    scan steps.  Multi-slot queues keep the sorted-slot-vector scan (the
    min over slot frees breaks max-plus linearity)."""
    if slots == 1:
        S = jnp.cumsum(lat)
        return S + jax.lax.cummax(arrival - S + lat)

    def step(slot_free, inp):
        arr, l = inp
        begin = jnp.maximum(arr, slot_free[0])
        done = begin + l
        slot_free = jnp.sort(slot_free.at[0].set(done))
        return slot_free, done

    init = jnp.zeros((slots,), dtype=jnp.float32)
    _, done = jax.lax.scan(step, init, (arrival, lat))
    return done


# ---------------------------------------------------------------------------
# smooth max-plus relaxation (temperature-τ log-sum-exp family)
# ---------------------------------------------------------------------------


def softmaximum(a: jnp.ndarray, b: jnp.ndarray, tau) -> jnp.ndarray:
    """Smooth two-argument max: τ·logaddexp(a/τ, b/τ) ≥ max(a, b), exact as
    τ → 0.  Shift-stable (logaddexp subtracts the pairwise max internally),
    monotone in both arguments, and smooth everywhere — the gradient splits
    between a and b by their softmax weights instead of picking a winner."""
    return tau * jnp.logaddexp(a / tau, b / tau)


def softmax_reduce(x: jnp.ndarray, tau, axis: int = -1) -> jnp.ndarray:
    """Smooth max-reduction: τ·logsumexp(x/τ) over ``axis``.  Entries at the
    ``NEG`` sentinel contribute softmax weight exp(NEG/τ - max/τ) = 0, so
    padded predecessor slots stay inert exactly as under the hard max."""
    return tau * jax.nn.logsumexp(x / tau, axis=axis)


@partial(jax.jit, static_argnames=("n", "width"))
def _wavefront_soft_impl(n: int, width: int, tau: jnp.ndarray,
                         work: jnp.ndarray, base: jnp.ndarray,
                         preds_lv: jnp.ndarray, extra_lv: jnp.ndarray,
                         starts: jnp.ndarray, order: jnp.ndarray,
                         rank: jnp.ndarray) -> jnp.ndarray:
    """``_wavefront_impl`` with the per-node hard max over (base, preds)
    replaced by ``softmax_reduce``.  τ is traced, not static: annealing it
    re-uses the compiled kernel."""
    work_lv = jnp.concatenate(
        [work.astype(jnp.float32)[order], jnp.zeros((width,), jnp.float32)])
    base_lv = jnp.concatenate(
        [base.astype(jnp.float32)[order], jnp.full((width,), NEG, jnp.float32)])
    p = preds_lv.shape[1]

    def step(t, start):
        js = jax.lax.dynamic_slice(preds_lv, (start, 0), (width, p))
        ex = jax.lax.dynamic_slice(extra_lv, (start, 0), (width, p))
        wv = jax.lax.dynamic_slice(work_lv, (start,), (width,))
        bv = jax.lax.dynamic_slice(base_lv, (start,), (width,))
        vals = jnp.where(js >= 0, t[jnp.maximum(js, 0)] + ex, NEG)
        m = softmax_reduce(jnp.concatenate([bv[:, None], vals], axis=1), tau,
                           axis=1)
        t = jax.lax.dynamic_update_slice(t, m + wv, (start,))
        return t, ()

    t0 = jnp.zeros((n + width,), dtype=jnp.float32)
    t, _ = jax.lax.scan(step, t0, starts)
    return t[rank]


def longest_path_soft(aidg: AIDGLike, tau: float = 0.05,
                      work: Optional[jnp.ndarray] = None,
                      base: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Smooth wavefront relaxation: upper-bounds ``longest_path_wavefront``
    node-wise, with per-node slack at most depth·τ·log(in-degree + 1), so
    the τ → 0 limit is the exact longest path.  Differentiable in (work,
    base) everywhere, including across critical-path switches."""
    ca = _as_compiled(aidg)
    a = ca.aidg
    s = ca.schedule
    w = jnp.asarray(a.work if work is None else work, jnp.float32)
    b = jnp.asarray(a.base if base is None else base, jnp.float32)
    return _wavefront_soft_impl(a.n, s.width, jnp.asarray(tau, jnp.float32),
                                w, b, jnp.asarray(ca.preds_lv),
                                jnp.asarray(ca.extra_lv),
                                jnp.asarray(s.starts), jnp.asarray(s.order),
                                jnp.asarray(s.rank))


def slot_queue_soft(arrival: jnp.ndarray, lat: jnp.ndarray, slots: int,
                    tau) -> jnp.ndarray:
    """``slot_queue_scan`` with every hard max softened.

    The single-slot closed form stays closed-form: the unrolled recurrence
    ``done_k = S_k + max_{j<=k}(arrival_j - S_{j-1})`` becomes
    ``S_k + τ·cumlogsumexp((arrival - S + lat)/τ)`` — the running soft-max
    via one associative scan (pairwise shift-stable), matching the hard
    cumsum + cummax path as τ → 0.  Multi-slot queues keep the sorted
    slot-vector scan with a ``softmaximum`` service-begin; the sort itself
    is piecewise-constant in the parameters and needs no smoothing."""
    if slots == 1:
        S = jnp.cumsum(lat)
        return S + tau * jax.lax.cumlogsumexp((arrival - S + lat) / tau)

    def step(slot_free, inp):
        arr, l = inp
        begin = softmaximum(arr, slot_free[0], tau)
        done = begin + l
        slot_free = jnp.sort(slot_free.at[0].set(done))
        return slot_free, done

    init = jnp.zeros((slots,), dtype=jnp.float32)
    _, done = jax.lax.scan(step, init, (arrival, lat))
    return done


def fixed_point_soft(aidg: AIDGLike, tau: float = 0.05, n_iters: int = 3,
                     work: Optional[jnp.ndarray] = None,
                     base: Optional[jnp.ndarray] = None,
                     storage_lat: Optional[Dict[str, jnp.ndarray]] = None,
                     engine: str = DEFAULT_ENGINE) -> jnp.ndarray:
    """``fixed_point_jax`` over the smooth family: soft wavefront
    relaxations between queueing folds, ``slot_queue_soft`` inside them, and
    a ``softmaximum`` base fold-back.  The arrival-order ``argsort`` is
    piecewise-constant in θ (its subgradient contribution is zero almost
    everywhere), so treating it as a constant gather keeps the whole fixed
    point ``jax.grad``-safe.  ``engine``: ``"wavefront"`` (default) or
    ``"condensed"`` (chain super-edges keep their exact sums — a tighter
    soft relaxation on a shorter sequential scan)."""
    ca = _as_compiled(aidg)
    a = ca.aidg
    tau = jnp.asarray(tau, jnp.float32)
    w = jnp.asarray(a.work if work is None else work, jnp.float32)
    b0 = jnp.asarray(a.base if base is None else base, jnp.float32)
    if engine == "condensed":
        cond = condense_aidg(a)
        relax = lambda w_, b_: _condensed_relax(cond, w_, b_, tau=tau)
    elif engine == "wavefront":
        s = ca.schedule
        pl, el = jnp.asarray(ca.preds_lv), jnp.asarray(ca.extra_lv)
        st_, od, rk = (jnp.asarray(s.starts), jnp.asarray(s.order),
                       jnp.asarray(s.rank))
        relax = lambda w_, b_: _wavefront_soft_impl(a.n, s.width, tau, w_,
                                                    b_, pl, el, st_, od, rk)
    else:
        raise ValueError(f"fixed_point_soft supports engines 'wavefront' "
                         f"and 'condensed', got {engine!r}")
    queue = lambda arr, lat, slots: slot_queue_soft(arr, lat, slots, tau)

    def fold(b, nd, need):
        # scatter the access needs into node space (duplicates keep the
        # hard max — a zero-measure kink), then soft-fold into the base:
        # softmaximum(b, NEG) == b exactly, so untouched nodes are inert
        need_full = jnp.full_like(b, NEG).at[nd].max(need)
        return softmaximum(b, need_full, tau)

    return _fixed_point_core(ca, relax, queue, fold, w, b0, storage_lat,
                             n_iters)


def _fixed_point_core(ca: CompiledAIDG, relax: Callable, queue: Callable,
                      fold: Callable, w: jnp.ndarray, b0: jnp.ndarray,
                      storage_lat: Optional[Dict[str, jnp.ndarray]],
                      n_iters: int) -> jnp.ndarray:
    """The one queueing fixed point shared by the hard and soft evaluators
    (so the gradient always descends the same objective the hard path
    scores): relax the DAG, replay each storage's accesses in estimated-
    arrival order through ``queue``, ``fold`` the service needs back into
    the bases, iterate.  Node-space gathers use the *constant* scatter
    indices; only the (θ-dependent) sort into service order and back needs
    batched-index gathers."""
    a = ca.aidg
    fu_lat = jnp.asarray(a.fu_lat, jnp.float32)
    t = relax(w, b0)
    if not a.storage_nodes:
        return t
    for _ in range(n_iters):
        b = b0
        for st_name in ca.storage_order:
            lats = jnp.asarray(
                a.storage_lat[st_name] if storage_lat is None
                else storage_lat[st_name], jnp.float32)
            nd = jnp.asarray(ca.storage_scatter[st_name])
            slots = a.storage_slots[st_name]
            w_nd = w[nd]
            arrival = t[nd] - w_nd
            order = jnp.argsort(arrival)
            done_sorted = queue(arrival[order], lats[order], slots)
            done = done_sorted[jnp.argsort(order)]    # back to access order
            need = done + fu_lat[nd] - w_nd
            b = fold(b, nd, need)
        t = relax(w, b)
    return t


def fixed_point_jax(aidg: AIDGLike, n_iters: int = 3,
                    work: Optional[jnp.ndarray] = None,
                    base: Optional[jnp.ndarray] = None,
                    storage_lat: Optional[Dict[str, jnp.ndarray]] = None,
                    engine: str = DEFAULT_ENGINE) -> jnp.ndarray:
    """JAX version of ``builder.longest_path_fixed_point`` — jit/vmap-able
    over (work, base, storage latencies) for design-space exploration.
    ``engine`` selects the DAG relaxation between queueing folds."""
    ca = _as_compiled(aidg)
    a = ca.aidg
    w = jnp.asarray(a.work if work is None else work, jnp.float32)
    b0 = jnp.asarray(a.base if base is None else base, jnp.float32)
    return _fixed_point_core(
        ca, _relaxer(ca, engine), slot_queue_scan,
        lambda b, nd, need: b.at[nd].max(need), w, b0, storage_lat, n_iters)


def fixed_point_batch(aidg: AIDGLike, works: Optional[jnp.ndarray] = None,
                      bases: Optional[jnp.ndarray] = None,
                      storage_lats: Optional[Dict[str, jnp.ndarray]] = None,
                      n_iters: int = 3,
                      engine: str = DEFAULT_ENGINE) -> jnp.ndarray:
    """Batched ``fixed_point_jax``: any of ``works`` (B, n), ``bases``
    (B, n), ``storage_lats`` {name: (B, k)} may carry a leading batch axis;
    omitted inputs broadcast from the AIDG baseline.  Returns (B, n)
    completion times in one vmapped device launch — the raw-latency-space
    counterpart of ``dse.sweep`` (which batches multiplicative θ factors).
    """
    ca = _as_compiled(aidg)
    a = ca.aidg
    batched = [x for x in (works, bases) if x is not None]
    if storage_lats is not None:
        unknown = set(storage_lats) - set(a.storage_lat)
        if unknown:
            raise KeyError(f"unknown storage(s) {sorted(unknown)}; "
                           f"AIDG has {sorted(a.storage_lat)}")
        batched.extend(storage_lats.values())
    if not batched:
        raise ValueError("fixed_point_batch needs at least one batched input")
    shapes = [np.shape(x) for x in batched]
    if any(len(s) != 2 for s in shapes) or len({s[0] for s in shapes}) != 1:
        raise ValueError(f"batched inputs must be 2-D with one shared "
                         f"leading batch dim, got shapes {shapes}")
    B = batched[0].shape[0]
    w = (jnp.broadcast_to(jnp.asarray(a.work, jnp.float32), (B, a.n))
         if works is None else jnp.asarray(works, jnp.float32))
    b = (jnp.broadcast_to(jnp.asarray(a.base, jnp.float32), (B, a.n))
         if bases is None else jnp.asarray(bases, jnp.float32))
    sl = {name: (jnp.broadcast_to(jnp.asarray(lat, jnp.float32),
                                  (B, len(lat)))
                 if storage_lats is None or name not in storage_lats
                 else jnp.asarray(storage_lats[name], jnp.float32))
          for name, lat in a.storage_lat.items()}

    def one(w_, b_, sl_):
        return fixed_point_jax(ca, n_iters=n_iters, work=w_, base=b_,
                               storage_lat=sl_, engine=engine)

    return jax.vmap(one)(w, b, sl)
