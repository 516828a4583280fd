"""Chunked online-softmax attention Pallas kernel (flash-attention style).

The compute hot-spot of every attention architecture in the model zoo:
prefill_32k would otherwise materialize a 32k x 32k score matrix per head.
The kernel streams KV blocks through VMEM with the classic running
(max, sum, acc) online-softmax state held in VMEM scratch across the
kv grid axis.

Causal masking is block-aware: KV blocks strictly above the diagonal are
skipped via the mask (the q >= k condition is evaluated per element only on
the diagonal blocks).  Sliding-window attention (h2o-danube) additionally
masks keys older than ``window`` positions.

Layout: (B*H, S, D) — batch and heads flattened into the leading grid axis,
S and D in the (8, 128)-aligned trailing dims.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel", "flash_attention_pallas"]

NEG = -1e18


def flash_attention_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                           *, scale: float, causal: bool, window: int,
                           bq: int, bk: int, n_k: int):
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                      # (bq, d)
    k = k_ref[0]                      # (bk, d)
    v = v_ref[0]                      # (bk, d)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)

    q_pos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    k_pos = kv_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=bool)
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]               # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)            # masked entries underflow to 0
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kv_idx == n_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)  # fully-masked rows -> 0 output
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "causal", "window",
                                             "scale", "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           bq: int = 128, bk: int = 128, causal: bool = True,
                           window: int = 0, scale: float | None = None,
                           interpret: bool = False) -> jnp.ndarray:
    """(BH, Sq, D) x (BH, Sk, D) x (BH, Sk, D) -> (BH, Sq, D).

    Block sizes must divide the sequence lengths (ops.flash_attention pads).
    """
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, sk, bq, bk)
    grid = (bh, sq // bq, sk // bk)
    return pl.pallas_call(
        functools.partial(flash_attention_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denominator
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
