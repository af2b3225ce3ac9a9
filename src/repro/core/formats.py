"""Number formats of the XR-NPE SIMD datapath.

The paper's engine supports, selected at runtime by ``prec_sel``:

  * HFP4      -- 4-bit minifloat e2m1 (sign / 2 exp / 1 mantissa), no inf/NaN
  * Posit(4,1)
  * Posit(8,0)
  * Posit(16,1)

plus the comparison formats used in its accuracy studies (FP8 e4m3, BF16,
FP16, FP32, fixed-point).  A format here is *not* a JAX dtype: a tensor in
format ``f`` is an integer tensor of raw codes (``int32`` holding
``f.bits``-bit patterns) together with the ``FormatSpec``.  ``decode`` maps
codes to float32 values, ``encode`` maps float32 to the nearest code
(round-to-nearest, ties-to-even-code -- the posit-standard rounding, which
coincides with IEEE RNE for minifloats), and ``quantize = decode . encode``.

This module is the *primitive* layer: two cross-validated implementations
of every codec operation, with no opinion about which to use --

  * table-based (``encode`` / ``decode``): enumerate all ``2^bits`` code
    values with an exact numpy scalar decoder, sort, and use
    ``searchsorted`` -- exact and simple;
  * algorithmic (``encode_bits`` / ``decode_bits``): branch-free integer
    bit manipulation, usable inside Pallas kernels where a 64K-entry
    gather would thrash VMEM, and on giant tensors where a table
    broadcast would blow memory.  This mirrors the paper's RMMEC decode
    circuitry: the regime/exponent extraction is the "exponent
    processing" half and the mantissa assembly the
    reconfigurable-multiplier half.

The choice between them lives in ONE place: the codec registry
(``core.codec``).  Consumers -- QAT, the packed serving plane, kernels,
gradient/optimizer compression -- call ``codec.encode/decode/quantize``
and never pick a path; only this module's tests and the codec registry
itself touch the per-path functions directly.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "FormatSpec", "FORMATS", "FP4", "POSIT4", "POSIT8", "POSIT16",
    "FP8_E4M3", "FP8_E5M2", "FXP4", "FXP8", "BF16", "FP16", "FP32",
    "decode", "encode", "quantize", "code_values", "nar_code",
    "decode_posit_bits", "decode_minifloat_bits", "bits_per_value",
    "simd_lanes", "format_by_name", "storage_bits",
]


@dataclasses.dataclass(frozen=True)
class FormatSpec:
    """A (de)codable number format.

    kind:
      'posit'     -- posit(bits, es); NaR at 1000...0
      'minifloat' -- sign/ebits/mbits, subnormals, saturating (no inf);
                     NaN at the all-ones code only if ``has_nan``
      'fixed'     -- two's-complement fixed point with ``frac_bits``
      'native'    -- a JAX dtype (bf16/fp16/fp32); encode = bitcast
    """

    name: str
    bits: int
    kind: str
    es: int = 0
    ebits: int = 0
    mbits: int = 0
    has_nan: bool = False
    frac_bits: int = 0
    dtype: Optional[str] = None

    @property
    def ncodes(self) -> int:
        return 1 << self.bits

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        return self.name


# --- the paper's formats -------------------------------------------------
FP4 = FormatSpec("fp4", 4, "minifloat", ebits=2, mbits=1)
POSIT4 = FormatSpec("posit4_1", 4, "posit", es=1)
POSIT8 = FormatSpec("posit8_0", 8, "posit", es=0)
POSIT16 = FormatSpec("posit16_1", 16, "posit", es=1)
# --- comparison formats used by the paper's accuracy figures -------------
FP8_E4M3 = FormatSpec("fp8_e4m3", 8, "minifloat", ebits=4, mbits=3, has_nan=True)
FP8_E5M2 = FormatSpec("fp8_e5m2", 8, "minifloat", ebits=5, mbits=2, has_nan=True)
FXP4 = FormatSpec("fxp4", 4, "fixed", frac_bits=2)
FXP8 = FormatSpec("fxp8", 8, "fixed", frac_bits=4)
BF16 = FormatSpec("bf16", 16, "native", dtype="bfloat16")
FP16 = FormatSpec("fp16", 16, "native", dtype="float16")
FP32 = FormatSpec("fp32", 32, "native", dtype="float32")

FORMATS = {
    f.name: f
    for f in (FP4, POSIT4, POSIT8, POSIT16, FP8_E4M3, FP8_E5M2, FXP4, FXP8,
              BF16, FP16, FP32)
}


def format_by_name(name: str) -> FormatSpec:
    return FORMATS[name]


def storage_bits(spec: FormatSpec) -> int:
    return spec.bits


def simd_lanes(spec: FormatSpec) -> int:
    """How many operands of this format fit one 16-bit XR-NPE SIMD lane."""
    return max(1, 16 // spec.bits)


def nar_code(spec: FormatSpec) -> int:
    if spec.kind == "posit":
        return 1 << (spec.bits - 1)
    if spec.kind == "minifloat" and spec.has_nan:
        return (1 << (spec.bits - 1)) - 1  # positive all-ones
    return 0


# ---------------------------------------------------------------------------
# Exact scalar decoders (numpy, run once per spec to build tables)
# ---------------------------------------------------------------------------

def _posit_value(code: int, n: int, es: int) -> float:
    mask = (1 << n) - 1
    code &= mask
    if code == 0:
        return 0.0
    if code == 1 << (n - 1):
        return float("nan")  # NaR
    sign = -1.0 if code >> (n - 1) else 1.0
    if sign < 0:
        code = (-code) & mask
    body = code & ((1 << (n - 1)) - 1)
    B = n - 1
    r0 = (body >> (B - 1)) & 1
    # run length of leading bits equal to r0
    m = 0
    for i in range(B - 1, -1, -1):
        if ((body >> i) & 1) == r0:
            m += 1
        else:
            break
    k = (m - 1) if r0 else -m
    consumed = min(m + 1, B)  # regime + terminating bit
    rem = B - consumed
    eb = min(es, rem)
    e = ((body >> (rem - eb)) & ((1 << eb) - 1)) << (es - eb) if eb else 0
    fbits = rem - eb
    frac = body & ((1 << fbits) - 1) if fbits else 0
    scale = k * (1 << es) + e
    return sign * (1.0 + frac / (1 << fbits if fbits else 1)) * (2.0 ** scale)


def _minifloat_value(code: int, ebits: int, mbits: int, has_nan: bool) -> float:
    bias = (1 << (ebits - 1)) - 1
    sign = -1.0 if (code >> (ebits + mbits)) & 1 else 1.0
    e = (code >> mbits) & ((1 << ebits) - 1)
    m = code & ((1 << mbits) - 1)
    if has_nan and e == (1 << ebits) - 1 and m == (1 << mbits) - 1:
        return float("nan")
    if e == 0:
        return sign * (m / (1 << mbits)) * (2.0 ** (1 - bias))
    return sign * (1.0 + m / (1 << mbits)) * (2.0 ** (e - bias))


def _fixed_value(code: int, bits: int, frac_bits: int) -> float:
    if code >= 1 << (bits - 1):
        code -= 1 << bits
    return code / (1 << frac_bits)


@functools.lru_cache(maxsize=None)
def code_values(spec: FormatSpec) -> np.ndarray:
    """float32 value of every raw code, indexed by code. NaN marks NaR."""
    if spec.kind == "native":
        raise ValueError("native formats have no code table")
    vals = np.empty(spec.ncodes, np.float64)
    for c in range(spec.ncodes):
        if spec.kind == "posit":
            vals[c] = _posit_value(c, spec.bits, spec.es)
        elif spec.kind == "minifloat":
            vals[c] = _minifloat_value(c, spec.ebits, spec.mbits, spec.has_nan)
        elif spec.kind == "fixed":
            vals[c] = _fixed_value(c, spec.bits, spec.frac_bits)
        else:  # pragma: no cover
            raise ValueError(spec.kind)
    return vals.astype(np.float32)


@functools.lru_cache(maxsize=None)
def _encode_tables(spec: FormatSpec):
    """(sorted_values f64, sorted_codes i32, boundaries f64) for encode.

    Sorted values are strictly increasing finite values (NaR dropped,
    -0/+0 deduplicated keeping the +0 code).  Boundary semantics follow
    the posit standard (softposit-compatible): the rounding boundary
    between two adjacent posits is the value of the *midpoint bit
    pattern* -- the (n+1)-bit posit ``(c << 1) | 1`` -- which equals the
    arithmetic midpoint within a regime but the geometric one across
    regime changes.  For minifloats IEEE RNE boundaries *are* arithmetic
    midpoints.  Ties resolve to the even (LSB=0) code.
    """
    vals = code_values(spec).astype(np.float64)
    codes = np.arange(spec.ncodes, dtype=np.int32)
    finite = np.isfinite(vals)
    vals, codes = vals[finite], codes[finite]
    order = np.argsort(vals, kind="stable")
    vals, codes = vals[order], codes[order]
    # dedup equal values (e.g. +-0): keep first occurrence, prefer code 0 for 0
    keep = np.ones(len(vals), bool)
    keep[1:] = vals[1:] != vals[:-1]
    zmask = vals == 0.0
    if zmask.any():
        codes[np.argmax(zmask)] = 0
    vals, codes = vals[keep], codes[keep]
    if spec.kind == "posit":
        n, es = spec.bits, spec.es
        # signed interpretation of each code, ascending with value
        signed = np.where(codes >= (1 << (n - 1)), codes - (1 << n),
                          codes).astype(np.int64)
        mids = (signed[:-1] << 1) + 1          # (n+1)-bit midpoint patterns
        bnds = np.array([_posit_value(int(m) & ((1 << (n + 1)) - 1),
                                      n + 1, es) for m in mids])
    else:
        bnds = (vals[:-1] + vals[1:]) / 2.0
    return vals, codes, bnds


# ---------------------------------------------------------------------------
# JAX encode / decode
# ---------------------------------------------------------------------------

def decode(spec: FormatSpec, codes: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Raw codes -> float values (NaR -> NaN)."""
    if spec.kind == "native":
        return codes.astype(dtype)
    table = jnp.asarray(code_values(spec))
    return table[codes.astype(jnp.int32) & (spec.ncodes - 1)].astype(dtype)


def encode(spec: FormatSpec, x: jax.Array) -> jax.Array:
    """float -> nearest raw code (int32). RNE-on-code; NaN -> NaR; saturating."""
    if spec.kind == "native":
        return x.astype(spec.dtype)
    svals, scodes, bnds = _encode_tables(spec)
    svals_j = jnp.asarray(svals)
    scodes_j = jnp.asarray(scodes)
    bnds_j = jnp.asarray(bnds)
    xf = x.astype(jnp.float64) if jax.config.x64_enabled else x.astype(jnp.float32)
    bnds_c = bnds_j if jax.config.x64_enabled else bnds_j.astype(jnp.float32)
    idx = jnp.searchsorted(bnds_c, xf, side="right").astype(jnp.int32)
    # tie: x exactly on boundary idx-1 -> lands on upper; move down if the
    # lower code is even (RNE on final code bit, per posit standard).
    lower = jnp.clip(idx - 1, 0, len(svals) - 1)
    on_tie = (idx > 0) & (xf == bnds_c[lower])
    lower_even = (scodes_j[lower] & 1) == 0
    idx = jnp.where(on_tie & lower_even, lower, idx)
    out = scodes_j[idx]
    if spec.kind == "posit":
        # posits never round a nonzero value to zero: clamp to +-minpos
        nonzero = (x != 0) & (out == 0)
        minpos_code = jnp.int32(1)
        maxneg_code = jnp.int32(spec.ncodes - 1)
        out = jnp.where(nonzero & (x > 0), minpos_code, out)
        out = jnp.where(nonzero & (x < 0), maxneg_code, out)
    nan_in = jnp.isnan(x)
    out = jnp.where(nan_in, jnp.int32(nar_code(spec)), out)
    return out


def quantize(spec: FormatSpec, x: jax.Array) -> jax.Array:
    """Round-trip x through the format's value grid (same dtype out)."""
    if spec.kind == "native":
        return x.astype(spec.dtype).astype(x.dtype)
    return decode(spec, encode(spec, x), dtype=x.dtype)


def bits_per_value(spec: FormatSpec) -> float:
    return float(spec.bits)


# ---------------------------------------------------------------------------
# Algorithmic (branch-free) decoders -- the in-kernel RMMEC datapath
# ---------------------------------------------------------------------------

def _clz_fixed(x: jax.Array, width: int) -> jax.Array:
    """Count leading zeros of ``x`` seen as a ``width``-bit integer."""
    return jnp.clip(jax.lax.clz(x.astype(jnp.int32)) - (32 - width), 0, width)


def decode_posit_bits(codes: jax.Array, n: int, es: int,
                      dtype=jnp.float32) -> jax.Array:
    """Vectorized posit decode with integer ops only (no table gather).

    The posit's fields become an IEEE-754 float32 word directly -- the
    sign in bit 31, the scale plus 127 in the exponent field, the
    fraction left-aligned in the mantissa -- which is bitcast and cast
    once to ``dtype``: the exact value rounded once, as the codec's table
    path gives it, in a few integer ops where two ``ldexp`` expansions
    took ~150 instructions.  Every posit(n, es) whose widest fraction
    (``n - 3 - es`` bits) and scale range (+-(n - 2) * 2^es) fit a
    float32 normal is accepted; posit4, posit8 and posit16 do.

    Safe inside Pallas kernel bodies.  NaR decodes to +0.0 -- the hardware
    exception path of the paper's input-processing stage feeds zero to the
    accumulator, and weights produced by ``encode`` never contain NaR.
    """
    if n - 3 - es > 23 or (n - 2) << es > 126:
        raise ValueError(f"posit({n}, {es}) values do not fit a float32 "
                         "word")
    c = codes.astype(jnp.int32) & ((1 << n) - 1)
    B = n - 1
    neg = (c >> B) & 1
    is_zero = c == 0
    is_nar = c == (1 << B)
    mag = jnp.where(neg == 1, (1 << n) - c, c)
    body = mag & ((1 << B) - 1)
    r0 = (body >> (B - 1)) & 1
    t = jnp.where(r0 == 1, ~body, body) & ((1 << B) - 1)
    m = _clz_fixed(t, B)
    k = jnp.where(r0 == 1, m - 1, -m)
    consumed = jnp.minimum(m + 1, B)
    rem = B - consumed
    eb = jnp.minimum(es, rem)
    e = jnp.where(
        eb > 0,
        ((body >> jnp.maximum(rem - eb, 0)) & ((1 << es) - 1)) << (es - eb),
        0,
    ) if es > 0 else jnp.zeros_like(body)
    fbits = rem - eb
    frac = body & ((1 << jnp.maximum(fbits, 0)) - 1)
    scale = k * (1 << es) + e
    word = (neg << 31) | ((scale + 127) << 23) | (frac << (23 - fbits))
    word = jnp.where(is_zero | is_nar, 0, word)
    return jax.lax.bitcast_convert_type(word, jnp.float32).astype(dtype)


def decode_minifloat_bits(codes: jax.Array, ebits: int, mbits: int,
                          dtype=jnp.float32, has_nan: bool = False) -> jax.Array:
    """Vectorized minifloat decode (subnormal-aware), kernel-safe.

    NaN codes decode to 0 -- the hardware exception path feeds zero to the
    accumulator (weights produced by ``encode`` never contain NaN codes).
    """
    n = 1 + ebits + mbits
    c = codes.astype(jnp.int32) & ((1 << n) - 1)
    bias = (1 << (ebits - 1)) - 1
    sign = jnp.where((c >> (ebits + mbits)) & 1, -1.0, 1.0).astype(dtype)
    e = (c >> mbits) & ((1 << ebits) - 1)
    m = (c & ((1 << mbits) - 1)).astype(dtype)
    sub = e == 0
    mant = jnp.where(sub, m / (1 << mbits), 1.0 + m / (1 << mbits))
    scale = jnp.where(sub, 1 - bias, e - bias)
    val = sign * jnp.ldexp(mant.astype(dtype), scale)
    if has_nan:
        is_nan = (e == (1 << ebits) - 1) & ((c & ((1 << mbits) - 1)) == (1 << mbits) - 1)
        val = jnp.where(is_nan, jnp.zeros_like(val), val)
    return val


def encode_posit_bits(x: jax.Array, n: int, es: int) -> jax.Array:
    """Branch-free posit encode, exact RNE (validated against the table
    encoder on every code + random sweeps).  No table gathers / wide
    broadcasts -- safe for giant tensors (QAT, 8-bit Adam) and kernels.

    Bit algebra (int32-safe): build regime|exponent|13-bit-mantissa in one
    integer, round once at the final width with guard/sticky (sticky
    carries the truncated low 10 mantissa bits).  Rounding carries
    propagate into the regime, which is exactly posit RNE; saturation
    clamps to +-maxpos and nonzero underflow to +-minpos (posits never
    round to zero or NaR).
    """
    B = n - 1
    xf = x.astype(jnp.float32)
    neg = xf < 0
    a = jnp.abs(xf)
    is_zero = a == 0
    is_nan = jnp.isnan(xf)
    m, E = jnp.frexp(jnp.where(is_zero | is_nan, 1.0, a))  # a = m*2^E
    scale = E - 1                                          # a = (2m)*2^scale
    maxscale = (n - 2) << es
    lo_clamp = scale < -maxscale
    hi_clamp = scale > maxscale
    scale = jnp.clip(scale, -maxscale, maxscale)
    k = scale >> es
    e = scale - (k << es)
    R = jnp.where(k >= 0, k + 2, 1 - k)
    pattern = jnp.where(k >= 0,
                        ((jnp.left_shift(1, jnp.clip(k + 1, 0, 30)) - 1) << 1),
                        1)
    m23 = jnp.round((2.0 * m - 1.0) * (1 << 23)).astype(jnp.int32)
    m13 = m23 >> 10
    st0 = (m23 & 1023) != 0
    V = (pattern << (es + 13)) | (e << 13) | m13
    drop = R + es + 13 - B                                # always >= 1
    keep = jnp.right_shift(V, drop)
    guard = jnp.right_shift(V, drop - 1) & 1
    low_mask = jnp.left_shift(1, jnp.clip(drop - 1, 0, 30)) - 1
    sticky = ((V & low_mask) != 0) | st0
    up = guard & (sticky | (keep & 1)).astype(jnp.int32)
    body = keep + up
    body = jnp.clip(body, 1, (1 << B) - 1)
    body = jnp.where(lo_clamp, 1, body)
    body = jnp.where(hi_clamp, (1 << B) - 1, body)
    code = jnp.where(neg, ((1 << n) - body) & ((1 << n) - 1), body)
    code = jnp.where(is_zero, 0, code)
    code = jnp.where(is_nan, 1 << B, code)
    return code.astype(jnp.int32)


def encode_minifloat_bits(x: jax.Array, ebits: int, mbits: int,
                          has_nan: bool = False) -> jax.Array:
    """Branch-free minifloat encode with subnormals + RNE + saturation."""
    xf = x.astype(jnp.float32)
    neg = xf < 0
    a = jnp.abs(xf)
    is_nan = jnp.isnan(xf)
    bias = (1 << (ebits - 1)) - 1
    emax = (1 << ebits) - 1
    # largest finite magnitude
    top_m = (1 << mbits) - (2 if has_nan else 1)
    max_fin = (1.0 + top_m / (1 << mbits)) * (2.0 ** (emax - bias))
    a = jnp.minimum(a, max_fin)
    _, E0 = jnp.frexp(jnp.where(a == 0, 1.0, a))
    E = jnp.clip(E0 - 1, 1 - bias, emax - bias)            # unbiased exp
    q = jnp.round(jnp.ldexp(a, mbits - E)).astype(jnp.int32)  # RNE, exact
    # mantissa overflow from rounding: 1.111.. -> 10.00 (exponent bump)
    bump = q >= (1 << (mbits + 1))
    E = jnp.where(bump, E + 1, E)
    q = jnp.where(bump, 1 << mbits, q)
    over = E > emax - bias
    E = jnp.minimum(E, emax - bias)
    sub = q < (1 << mbits)                                 # subnormal
    e_field = jnp.where(sub, 0, E + bias)
    m_field = jnp.where(sub, q, q - (1 << mbits))
    m_field = jnp.where(over, top_m, m_field)
    e_field = jnp.where(over, emax, e_field)
    code = (neg.astype(jnp.int32) << (ebits + mbits)) | \
        (e_field << mbits) | m_field
    if has_nan:
        nan_code = ((1 << (ebits + mbits)) - 1)
        code = jnp.where(is_nan, nan_code, code)
    return code.astype(jnp.int32)


def encode_bits(spec: FormatSpec, x: jax.Array) -> jax.Array:
    """Algorithmic encode dispatch (no tables; giant-tensor safe)."""
    if spec.kind == "posit":
        return encode_posit_bits(x, spec.bits, spec.es)
    if spec.kind == "minifloat":
        return encode_minifloat_bits(x, spec.ebits, spec.mbits, spec.has_nan)
    if spec.kind == "fixed":
        q = jnp.clip(jnp.round(x.astype(jnp.float32) * (1 << spec.frac_bits)),
                     -(spec.ncodes // 2), spec.ncodes // 2 - 1)
        return (q.astype(jnp.int32)) & (spec.ncodes - 1)
    raise ValueError(f"no bit encoder for {spec.kind}")


def quantize_bits(spec: FormatSpec, x: jax.Array) -> jax.Array:
    """Algorithmic round-trip (value-identical to ``quantize``; used on
    hot paths -- QAT forward, 8-bit optimizer state, grad compression)."""
    return decode_bits(spec, encode_bits(spec, x), dtype=jnp.float32) \
        .astype(x.dtype)


def decode_bits(spec: FormatSpec, codes: jax.Array, dtype=jnp.float32):
    """Dispatch to the kernel-safe algorithmic decoder for ``spec``."""
    if spec.kind == "posit":
        return decode_posit_bits(codes, spec.bits, spec.es, dtype)
    if spec.kind == "minifloat":
        return decode_minifloat_bits(codes, spec.ebits, spec.mbits, dtype,
                                     spec.has_nan)
    if spec.kind == "fixed":
        c = codes.astype(jnp.int32) & (spec.ncodes - 1)
        c = jnp.where(c >= spec.ncodes // 2, c - spec.ncodes, c)
        return c.astype(dtype) / (1 << spec.frac_bits)
    raise ValueError(f"no bit decoder for {spec.kind}")
