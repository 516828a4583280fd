"""Selective-scan (Mamba-1) Pallas kernel — beyond-paper addition for the
ssm/hybrid architectures (falcon-mamba, jamba).

    h_t = exp(dt_t ⊙ A) ⊙ h_{t-1} + (dt_t ⊙ x_t) ⊗ B_t
    y_t = h_t · C_t + D ⊙ x_t

The CUDA kernel the Mamba paper ships keeps h resident in shared memory
and streams (x, dt, B, C) through it; the TPU-native expression keeps the
(bd, N) state tile resident in VMEM across a sequential time loop, with
the channel dimension blocked over the grid — channels are independent, so
the grid parallelizes cleanly over cores while time stays a `fori_loop`
inside the kernel (HBM -> VMEM -> VREG, DESIGN.md §4).

Layout: x/dt (B, S, D); B/C (B, S, N); A (D, N); grid (B, D/bd).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["selective_scan_kernel", "selective_scan_pallas"]


def selective_scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref,
                          h_ref, *, seq_len: int):
    h_ref[...] = jnp.zeros_like(h_ref)
    A = a_ref[0]                       # (bd, N)
    D = d_ref[0]                       # (bd,)

    def step(t, _):
        x_t = x_ref[0, t]              # (bd,)
        dt_t = dt_ref[0, t]            # (bd,)
        b_t = b_ref[0, t]              # (N,)
        c_t = c_ref[0, t]              # (N,)
        dA = jnp.exp(dt_t[:, None] * A)                     # (bd, N)
        h = dA * h_ref[...] + (dt_t * x_t)[:, None] * b_t[None, :]
        h_ref[...] = h
        y_ref[0, t] = (h @ c_t) + D * x_t
        return 0

    jax.lax.fori_loop(0, seq_len, step, 0)


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def selective_scan_pallas(x: jnp.ndarray, dt: jnp.ndarray, b: jnp.ndarray,
                          c: jnp.ndarray, a: jnp.ndarray, d: jnp.ndarray, *,
                          bd: int = 128, interpret: bool = False) -> jnp.ndarray:
    """x/dt: (B, S, D); b/c: (B, S, N); a: (D, N); d: (D,) -> y (B, S, D)."""
    B, S, Dm = x.shape
    N = b.shape[-1]
    bd = min(bd, Dm)
    assert Dm % bd == 0, (Dm, bd)
    grid = (B, Dm // bd)
    return pl.pallas_call(
        functools.partial(selective_scan_kernel, seq_len=S),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, S, bd), lambda i, j: (i, 0, j)),   # x
            pl.BlockSpec((1, S, bd), lambda i, j: (i, 0, j)),   # dt
            pl.BlockSpec((1, S, N), lambda i, j: (i, 0, 0)),    # B
            pl.BlockSpec((1, S, N), lambda i, j: (i, 0, 0)),    # C
            pl.BlockSpec((1, bd, N), lambda i, j: (0, j, 0)),   # A
            pl.BlockSpec((1, bd), lambda i, j: (0, j)),         # D
        ],
        out_specs=pl.BlockSpec((1, S, bd), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, S, Dm), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],      # resident h
        interpret=interpret,
    )(x, dt, b, c, a[None], d[None])
