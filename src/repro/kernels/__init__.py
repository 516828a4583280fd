"""Pallas TPU kernels for the framework's compute hot-spots.

* ``maxplus``         — blocked max-plus matmul (AIDG longest-path closure)
* ``systolic_gemm``   — MXU-tiled GeMM with fused activation (paper §4.2/§4.3
                        adapted to the TPU memory hierarchy)
* ``flash_attention`` — chunked online-softmax attention (prefill hot-spot)

Each kernel: ``<name>.py`` (pl.pallas_call + BlockSpec), compiled for the
TPU by default and validated on the CPU in ``interpret=True`` mode against
the pure-jnp oracles in ``ref.py``; public entry points with padding logic
live in ``ops.py``.
"""

from . import ops, ref
from .flash_attention import flash_attention_pallas
from .maxplus import maxplus_matmul_pallas
from .systolic_gemm import systolic_gemm_pallas

__all__ = ["ops", "ref", "flash_attention_pallas", "maxplus_matmul_pallas",
           "systolic_gemm_pallas"]
