"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps in
interpret=True mode (the CPU validation contract).  The kernels default to
compiled mode, so every call here asks for the interpreter explicitly."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("m,k,n", [(32, 32, 32), (128, 128, 128),
                                   (100, 77, 130), (256, 64, 192), (8, 8, 8)])
def test_maxplus_matmul(m, k, n, rng):
    A = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    out = ops.maxplus_matmul(A, B, bm=32, bk=32, bn=32, interpret=True)
    np.testing.assert_allclose(out, ref.maxplus_matmul_ref(A, B), atol=1e-5)


def test_maxplus_associativity(rng):
    A, B, C = (jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)
               for _ in range(3))
    mm = functools.partial(ops.maxplus_matmul, interpret=True)
    left = mm(mm(A, B), C)
    right = mm(A, mm(B, C))
    np.testing.assert_allclose(left, right, atol=1e-4)


def test_maxplus_matvec(rng):
    """The single-column wrapper the blocked AIDG evaluator uses."""
    from repro.kernels.maxplus import maxplus_matvec_pallas
    A = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    out = maxplus_matvec_pallas(A, v, interpret=True)
    want = jnp.max(A + v[None, :], axis=1)
    np.testing.assert_allclose(out, want, atol=1e-5)


@pytest.mark.parametrize("m,k,n,dt", [
    (128, 128, 128, jnp.float32),
    (64, 200, 96, jnp.bfloat16),
    (37, 53, 29, jnp.float32),
    (256, 128, 64, jnp.bfloat16),
])
@pytest.mark.parametrize("act", [0, 1])
def test_systolic_gemm(m, k, n, dt, act, rng):
    A = jnp.asarray(rng.normal(size=(m, k)), dt)
    B = jnp.asarray(rng.normal(size=(k, n)), dt)
    out = ops.gemm(A, B, activation=act, bm=32, bk=64, bn=32,
                   interpret=True)
    want = ref.gemm_ref(A, B, activation=act)
    atol = 2e-2 if dt == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(out, want, atol=atol, rtol=1e-2)


@pytest.mark.parametrize("b,h,s,d,causal,window", [
    (1, 2, 128, 64, True, 0),
    (2, 2, 256, 64, True, 0),
    (1, 1, 160, 64, True, 0),       # ragged -> padded
    (1, 2, 128, 64, False, 0),
    (1, 2, 256, 64, True, 64),      # sliding window
    (1, 2, 256, 128, True, 0),
])
def test_flash_attention(b, h, s, d, causal, window, rng):
    q, k, v = (jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
               for _ in range(3))
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              bq=64, bk=64, interpret=True)
    # windowed reference
    s_ = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    qp = jnp.arange(s)[:, None]
    kp = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= qp >= kp
    if window > 0:
        mask &= kp > qp - window
    s_ = jnp.where(mask, s_, -1e18)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s_, axis=-1), v)
    np.testing.assert_allclose(out, want, atol=2e-4, rtol=1e-3)


def test_flash_attention_bf16(rng):
    q, k, v = (jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.bfloat16)
               for _ in range(3))
    out = ops.flash_attention(q, k, v, causal=True, bq=64, bk=64,
                              interpret=True)
    want = ref.flash_attention_ref(q[:, None].transpose(0, 1, 2, 3).reshape(2, 1, 128, 64),
                                   k.reshape(2, 1, 128, 64),
                                   v.reshape(2, 1, 128, 64), causal=True)
    np.testing.assert_allclose(out.astype(jnp.float32).reshape(2, 1, 128, 64),
                               want.astype(jnp.float32), atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("B,S,D,N,bd", [(2, 16, 32, 4, 16),
                                        (1, 64, 128, 16, 64),
                                        (2, 33, 48, 8, 16),
                                        (1, 20, 100, 8, 64)])
def test_selective_scan(B, S, D, N, bd, rng):
    x = jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32) * 0.5
    dt = jnp.asarray(np.abs(rng.normal(size=(B, S, D))) * 0.1, jnp.float32)
    b = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    a = -jnp.asarray(np.abs(rng.normal(size=(D, N))) + 0.1, jnp.float32)
    d = jnp.asarray(rng.normal(size=(D,)), jnp.float32)
    got = ops.selective_scan(x, dt, b, c, a, d, bd=bd, interpret=True)
    want = ref.selective_scan_ref(x, dt, b, c, a, d)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_flash_attention_noncausal_ragged_raises(rng):
    """Padded keys cannot be masked without the causal mask: the wrapper
    refuses instead of quietly running the jnp reference."""
    q = jnp.asarray(rng.normal(size=(1, 2, 160, 64)), jnp.float32)
    with pytest.raises(ValueError, match="multiple of bk"):
        ops.flash_attention(q, q, q, causal=False, bq=64, bk=64,
                            interpret=True)
