"""MXU-tiled GeMM Pallas kernel — the TPU-native adaptation of the paper's
systolic array (§4.2) and of Γ̈'s fused ``gemm`` instruction (§4.3).

Hardware adaptation (DESIGN.md §4): the paper's PE-grid dataflow (operands
skewed through a 2-D grid, output-stationary accumulators) *is* what the
MXU implements in silicon.  The TPU-idiomatic expression is therefore not a
PE-by-PE emulation but a blocked matmul whose BlockSpec tiling plays the
role of the load/store units: (bm, bk) × (bk, bn) VMEM tiles stream through
the MXU with a float32 accumulator tile held resident across the k grid
axis — exactly the output-stationary discipline of Fig. 4, one level up the
memory hierarchy (HBM -> VMEM -> MXU instead of DRAM -> load units -> PEs).

The optional fused ReLU on the final k step reproduces the Γ̈ ``gemm``
instruction's activation parameter (Listing 4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["systolic_gemm_kernel", "systolic_gemm_pallas"]


def systolic_gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, activation: int,
                         n_k: int):
    """Output-stationary (bm, bn) tile: accumulate over the k grid axis in a
    float32 scratch accumulator, write (+ activation) on the last step."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        out = acc_ref[...]
        if activation == 1:
            out = jnp.maximum(out, 0.0)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "activation",
                                             "out_dtype", "interpret"))
def systolic_gemm_pallas(a: jnp.ndarray, b: jnp.ndarray, *,
                         bm: int = 128, bk: int = 128, bn: int = 128,
                         activation: int = 0, out_dtype=jnp.float32,
                         interpret: bool = False) -> jnp.ndarray:
    """C = act(A @ B); (M, K) @ (K, N), block sizes must divide the shapes
    (ops.systolic_gemm pads ragged inputs)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(systolic_gemm_kernel, activation=activation,
                          n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        # float32 accumulator tile resident in VMEM across the k axis
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
