"""Blocked max-plus Pallas kernels.

The AIDG longest-path relaxation is a max-plus matmul (DESIGN.md §4):
``(A ⊗ B)_ij = max_k (A_ik + B_kj)``.  The matmul kernel tiles exactly like
an MXU matmul — (8, 128)-aligned VMEM blocks, k-innermost grid accumulation
— but reduces with max/add on the VPU instead of mul/add on the MXU.

Inside a block, each k step broadcasts column k of A across the lanes and
row k of B across the sublanes.  The k loop is unrolled at trace time: a
static column index is the only lane-axis slice Mosaic accepts that is not
a multiple of 128.  The working set stays at ``bm*bk + bk*bn + bm*bn``
floats.

The matvec kernel takes A transposed, so the reduced k axis lies on the
sublanes and the output is one lane-dense row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["maxplus_matmul_kernel", "maxplus_matmul_pallas",
           "maxplus_matvec_pallas"]

NEG = -1e18


def maxplus_matmul_kernel(a_ref, b_ref, o_ref, *, bk: int):
    """One (bm, bn) output tile, accumulating max over the k grid axis."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, NEG)

    a = a_ref[...]            # (bm, bk)
    b = b_ref[...]            # (bk, bn)
    acc = o_ref[...]
    for kk in range(bk):
        acc = jnp.maximum(acc, a[:, kk:kk + 1] + b[kk:kk + 1, :])
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def maxplus_matmul_pallas(a: jnp.ndarray, b: jnp.ndarray, *,
                          bm: int = 128, bk: int = 128, bn: int = 128,
                          interpret: bool = False) -> jnp.ndarray:
    """C = A ⊗ B for (M, K) ⊗ (K, N); shapes must divide the block sizes
    (``ops.maxplus_matmul`` pads ragged shapes)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(maxplus_matmul_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32))


def maxplus_matvec_kernel(at_ref, v_ref, o_ref):
    """One lane-dense (1, bm) output row: ``max_k (Aᵀ_ki + v_k)`` reduced
    over the sublane axis, accumulating max over the k grid axis."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, NEG)

    cand = jnp.max(at_ref[...] + v_ref[...], axis=0, keepdims=True)
    o_ref[...] = jnp.maximum(o_ref[...], cand)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def maxplus_matvec_pallas(A: jnp.ndarray, v: jnp.ndarray, *,
                          bm: int = 128, bk: int = 128,
                          interpret: bool = False) -> jnp.ndarray:
    """(A ⊗ v)_i = max_k (A_ik + v_k) for (M, K) ⊗ (K,) — the per-block
    propagation step of the AIDG blocked evaluator
    (``repro.core.aidg.maxplus.longest_path_blocked``).  Shapes must
    divide the block sizes."""
    m, k = A.shape
    assert v.shape == (k,), (A.shape, v.shape)
    bm, bk = min(bm, m), min(bk, k)
    assert m % bm == 0 and k % bk == 0, (m, k, bm, bk)
    out = pl.pallas_call(
        maxplus_matvec_kernel,
        grid=(m // bm, k // bk),
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, kk: (kk, i)),
            pl.BlockSpec((bk, 1), lambda i, kk: (kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm), lambda i, kk: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.float32),
        interpret=interpret,
    )(A.T.astype(jnp.float32), v.astype(jnp.float32)[:, None])
    return out[0]
