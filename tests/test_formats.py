"""Format codec tests: exactness, posit-standard properties, packing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import formats as F
from repro.core import packing as P
from repro.core import quire as Q

SPECS = [F.FP4, F.POSIT4, F.POSIT8, F.POSIT16, F.FP8_E4M3, F.FP8_E5M2,
         F.FXP4, F.FXP8]


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name)
def test_roundtrip_exact(spec):
    """Every representable value encodes back to itself."""
    vals = F.code_values(spec)
    fin = np.isfinite(vals)
    enc = np.asarray(F.encode(spec, jnp.asarray(vals[fin])))
    dec = np.asarray(F.decode(spec, jnp.asarray(enc)))
    assert np.array_equal(dec, vals[fin])


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name)
def test_algorithmic_decoder_matches_table(spec):
    """The kernel-safe bit decoder agrees with the exact table decoder
    on every code (NaR/NaN -> 0, the hardware exception path)."""
    vals = F.code_values(spec)
    dec2 = np.asarray(F.decode_bits(spec, jnp.arange(spec.ncodes)))
    tab = np.where(np.isfinite(vals), vals, 0.0)
    assert np.array_equal(dec2, tab)


def test_posit_known_values():
    # posit(8,0): maxpos = 2^6; posit(16,1): maxpos = 2^28; posit(4,1): 16
    assert np.nanmax(F.code_values(F.POSIT8)) == 64.0
    assert np.nanmax(F.code_values(F.POSIT16)) == 2.0 ** 28
    assert np.nanmax(F.code_values(F.POSIT4)) == 16.0
    # fp4 e2m1 value set (OCP)
    v = sorted(set(float(x) for x in F.code_values(F.FP4) if x >= 0))
    assert v == [0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0]


def test_encode_monotone_and_saturating():
    for spec in (F.POSIT8, F.FP4, F.POSIT16):
        xs = jnp.linspace(-1e38, 1e38, 4097)
        codes = F.encode(spec, xs)
        vals = np.asarray(F.decode(spec, codes))
        assert np.all(np.diff(vals) >= 0)          # monotone
        assert vals[0] == -np.nanmax(F.code_values(spec))  # clamps
        assert vals[-1] == np.nanmax(F.code_values(spec))


def test_nan_maps_to_nar():
    c = int(F.encode(F.POSIT8, jnp.asarray([float("nan")]))[0])
    assert c == F.nar_code(F.POSIT8) == 0x80


@settings(max_examples=200, deadline=None)
@given(st.floats(min_value=-100.0, max_value=100.0,
                 allow_nan=False, allow_infinity=False))
def test_rne_nearest_property_minifloat(x):
    """Minifloat encode picks a nearest representable value (IEEE RNE;
    posits round in BIT space -- covered by the agreement test below)."""
    for spec in (F.FP4, F.FP8_E4M3):
        vals = F.code_values(spec)
        fin = np.sort(vals[np.isfinite(vals)])
        q = float(F.decode(spec, F.encode(spec, jnp.float32(x))))
        best = np.min(np.abs(fin - np.float64(np.float32(x))))
        assert abs(abs(q - np.float32(x)) - best) <= 1e-12


@settings(max_examples=60, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_algorithmic_encoder_agrees_with_table(seed):
    """The branch-free encoder (hot path) matches the table encoder
    (posit-standard bit-space RNE boundaries) on random sweeps."""
    rng = np.random.default_rng(seed)
    xs = np.concatenate([rng.normal(size=500),
                         rng.normal(size=200) * 1e-5,
                         rng.normal(size=200) * 1e5]).astype(np.float32)
    for spec in (F.POSIT4, F.POSIT8, F.POSIT16, F.FP4, F.FP8_E4M3):
        d_tab = np.asarray(F.decode_bits(spec, F.encode(spec, jnp.asarray(xs))))
        d_alg = np.asarray(F.decode_bits(spec, F.encode_bits(spec,
                                                             jnp.asarray(xs))))
        assert np.array_equal(d_tab, d_alg), spec.name


def test_posit_bitspace_rounding_boundary():
    """Posit-standard (softposit) rounding: the boundary between two
    posits across a regime change is the (n+1)-bit midpoint pattern (the
    geometric mean), NOT the arithmetic midpoint.  posit(4,1): between
    0.0625 (2^-4) and 0.25 (2^-2) the boundary is 2^-3 = 0.125."""
    for x, want in [(0.124, 0.0625), (0.126, 0.25), (0.2, 0.25)]:
        q = float(F.decode(F.POSIT4, F.encode(F.POSIT4, jnp.float32(x))))
        assert q == want, (x, q, want)
    # nonzero never rounds to zero: clamps to +-minpos
    q = float(F.decode(F.POSIT4, F.encode(F.POSIT4, jnp.float32(1e-6))))
    assert q == 0.0625  # minpos of posit(4,1)
    q = float(F.decode(F.POSIT4, F.encode(F.POSIT4, jnp.float32(-1e-6))))
    assert q == -0.0625


@settings(max_examples=50, deadline=None)
@given(st.integers(min_value=1, max_value=200),
       st.sampled_from([4, 8, 16]),
       st.integers(min_value=0, max_value=2**31 - 1))
def test_pack_unpack_roundtrip(k, bits, seed):
    rng = np.random.default_rng(seed)
    c = rng.integers(0, 1 << bits, size=(3, k))
    w = P.pack(jnp.asarray(c), bits)
    assert w.dtype == jnp.uint32
    back = np.asarray(P.unpack(w, bits, k))
    assert np.array_equal(back, c)


def test_packed_bytes_ratio():
    """The SIMD packing achieves the nominal compression (paper's
    memory-bandwidth claim at the storage level)."""
    shape = (1024, 1024)
    fp32_bytes = 1024 * 1024 * 4
    assert P.packed_nbytes(shape, 4) == fp32_bytes // 8
    assert P.packed_nbytes(shape, 8) == fp32_bytes // 4
    assert P.packed_nbytes(shape, 16) == fp32_bytes // 2


def test_quire_exact_vs_f64():
    rng = np.random.default_rng(0)
    a = rng.integers(0, 256, 64)
    b = rng.integers(0, 256, 64)
    ex = Q.quire_dot_exact(F.POSIT8, a, b)
    tab = F.code_values(F.POSIT8).astype(np.float64)
    tab = np.where(np.isnan(tab), 0.0, tab)
    assert abs(ex - float(np.sum(tab[a] * tab[b]))) < 1e-9


def test_simd_lanes():
    assert F.simd_lanes(F.FP4) == 4          # 4x per 16-bit lane
    assert F.simd_lanes(F.POSIT8) == 2
    assert F.simd_lanes(F.POSIT16) == 1
