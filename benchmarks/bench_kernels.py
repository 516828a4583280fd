"""Pallas kernel microbenchmarks, compiled on a TPU.

Each row names the device it ran on.  Off the TPU the kernels would only
run through the Pallas interpreter, whose time says nothing about the
kernel, so every row then reads "not measured"."""

from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

NAMES = ("kernel/maxplus_256", "kernel/systolic_gemm_256",
         "kernel/flash_attn_256")


def _time(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run(rows: List[Dict]) -> None:
    dev = jax.devices()[0]
    device = (f"platform={dev.platform};kind={dev.device_kind};"
              f"count={jax.device_count()}")
    if dev.platform != "tpu":
        rows.extend({"name": n, "us_per_call": 0.0,
                     "derived": f"{device};not measured"} for n in NAMES)
        return
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
    Ab, Bb = A.astype(jnp.bfloat16), B.astype(jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(4, 256, 64)), jnp.float32)
    timings = [
        (_time(lambda a, b: ops.maxplus_matmul(a, b), A, B), "f32"),
        (_time(lambda a, b: ops.gemm(a, b), Ab, Bb), "bf16"),
        (_time(lambda x: ops.flash_attention(x, x, x), q), "f32"),
    ]
    for name, (dt, dtype) in zip(NAMES, timings):
        rows.append({"name": name, "us_per_call": dt * 1e6,
                     "derived": f"{device};{dtype}"})
