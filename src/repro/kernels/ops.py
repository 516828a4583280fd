"""Jit'd public wrappers around the Pallas kernels.

Handle ragged shapes by padding to block multiples (max-plus pads with
-inf, gemm with zeros, attention with masked keys).  A shape the padding
cannot make exact raises instead of quietly switching to the pure-jnp
reference in ``ref.py``.

Every kernel runs compiled by default (``interpret=False``), which needs a
TPU.  On the CPU pass ``interpret=True``: the kernel then runs through the
Pallas interpreter, which checks results, not speed.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from .flash_attention import flash_attention_pallas
from .maxplus import maxplus_matmul_pallas
from .selective_scan import selective_scan_pallas
from .systolic_gemm import systolic_gemm_pallas

__all__ = ["maxplus_matmul", "gemm", "flash_attention", "selective_scan"]

NEG = -1e18


def _pad_to(x: jnp.ndarray, mults, value) -> jnp.ndarray:
    pads = []
    needs = False
    for dim, m in zip(x.shape, mults):
        p = (-dim) % m
        pads.append((0, p))
        needs = needs or p > 0
    return jnp.pad(x, pads, constant_values=value) if needs else x


def maxplus_matmul(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 128,
                   bk: int = 128, bn: int = 128,
                   interpret: bool = False) -> jnp.ndarray:
    """(A ⊗ B) with -inf padding for ragged shapes."""
    m, k = a.shape
    _, n = b.shape
    ap = _pad_to(a, (bm, bk), NEG)
    bp = _pad_to(b, (bk, bn), NEG)
    out = maxplus_matmul_pallas(ap, bp, bm=bm, bk=bk, bn=bn,
                                interpret=interpret)
    return out[:m, :n]


def gemm(a: jnp.ndarray, b: jnp.ndarray, *, activation: int = 0,
         bm: int = 128, bk: int = 128, bn: int = 128,
         out_dtype=jnp.float32, interpret: bool = False) -> jnp.ndarray:
    """act(A @ B) with zero padding for ragged shapes."""
    m, k = a.shape
    _, n = b.shape
    ap = _pad_to(a, (bm, bk), 0)
    bp = _pad_to(b, (bk, bn), 0)
    out = systolic_gemm_pallas(ap, bp, bm=bm, bk=bk, bn=bn,
                               activation=activation, out_dtype=out_dtype,
                               interpret=interpret)
    return out[:m, :n]


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128,
                    scale: Optional[float] = None,
                    interpret: bool = False) -> jnp.ndarray:
    """Attention over (B, H, S, D) or (BH, S, D) inputs.

    Padded keys are masked through the causal/positional mask: key padding
    appends positions > every real query position, which the causal mask
    excludes.  For non-causal inputs nothing masks them, so a non-causal
    ``sk`` that is not a multiple of ``bk`` raises ``ValueError``.
    """
    squeeze = q.ndim == 4
    if squeeze:
        b, h, s, d = q.shape
        q = q.reshape(b * h, s, d)
        k = k.reshape(b * h, k.shape[2], d)
        v = v.reshape(b * h, v.shape[2], d)
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    if sk % bk and not causal:
        raise ValueError(f"non-causal attention needs sk ({sk}) to be a "
                         f"multiple of bk ({bk}): padded keys are unmasked")
    qp = _pad_to(q, (1, bq, 1), 0)
    kp = _pad_to(k, (1, bk, 1), 0)
    vp = _pad_to(v, (1, bk, 1), 0)
    out = flash_attention_pallas(qp, kp, vp, bq=bq, bk=bk, causal=causal,
                                 window=window, scale=scale,
                                 interpret=interpret)[:, :sq]
    if squeeze:
        out = out.reshape(b, h, sq, d)
    return out


def selective_scan(x, dt, b, c, a, d, *, bd: int = 128,
                   interpret: bool = False):
    """Mamba-1 selective scan; pads the channel dim to the block size."""
    B, S, D = x.shape
    p = (-D) % bd if D > bd else 0
    if D < bd:
        bd = D
    if p:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, p)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, p)))
        a = jnp.pad(a, ((0, p), (0, 0)))
        d = jnp.pad(d, ((0, p),))
    out = selective_scan_pallas(x, dt, b, c, a, d, bd=bd,
                                interpret=interpret)
    return out[:, :, :D]
