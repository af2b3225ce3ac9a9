"""Quire-exact Posit(8,0) batched dot product on the VPU.

The XR-NPE accumulates posit products in a quire (wide fixed point), so a
dot product rounds exactly once.  f32 MXU accumulation is *almost* that --
each product is exact, but long sums can round.  This kernel reproduces
true quire semantics with integer accumulators:

  * a Posit(8,0) value is M/32 * 2^k, M in [32,63], k in [-6,6]; products
    are exact in f32 (<= 12 significant bits each, 22 < 24 total);
  * each product p is split into hi = round(p) and lo = round((p-hi)*2^22),
    both int32-exact;
  * hi and lo accumulate in two int32 lanes -- the quire limbs -- with a
    carry fold every K step so ``lo`` stays bounded;
  * the single final rounding happens outside the kernel when the limbs
    are combined (ops.quire_combine).

Layout: each row is one MAC lane of the SIMD array; grid is
(B/bb, K/bk) with K innermost, outputs revisited as accumulators.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import codec as codec_mod
from ..core import formats as fmt

__all__ = ["quire_dot_kernel", "quire_dot_pallas", "QUIRE_FRAC_BITS"]

QUIRE_FRAC_BITS = 22  # lsb of the lo limb = 2^-22 (posit8 product lsb)


def quire_dot_kernel(a_ref, b_ref, hi_ref, lo_ref, *, k_steps: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        hi_ref[...] = jnp.zeros_like(hi_ref)
        lo_ref[...] = jnp.zeros_like(lo_ref)

    a = codec_mod.decode(fmt.POSIT8, a_ref[...], dtype=jnp.float32)
    b = codec_mod.decode(fmt.POSIT8, b_ref[...], dtype=jnp.float32)
    p = a * b                                     # exact: <=22 sig bits
    hi = jnp.round(p)                             # integer part, exact
    lo = jnp.round((p - hi) * (2.0 ** QUIRE_FRAC_BITS))  # fractional limb
    hi_ref[...] += jnp.sum(hi, axis=-1, keepdims=True).astype(jnp.int32)
    lo_sum = lo_ref[...] + jnp.sum(lo, axis=-1, keepdims=True).astype(jnp.int32)
    # carry fold: keep |lo| < 2^22 so the next block's partial sums
    # (<= bk * 2^21) never overflow int32.
    carry = lo_sum >> QUIRE_FRAC_BITS            # arithmetic shift
    hi_ref[...] += carry
    lo_ref[...] = lo_sum - (carry << QUIRE_FRAC_BITS)


@functools.partial(jax.jit, static_argnames=("bb", "bk", "interpret"))
def quire_dot_pallas(a_codes: jax.Array, b_codes: jax.Array, *,
                     bb: int = 8, bk: int = 512,
                     interpret: bool = False):
    """a,b: (B, K) int32 posit8 codes -> (hi, lo) int32 quire limbs (B, 1).

    Exact value of row i = hi[i] + lo[i] * 2^-22 (combine in ops.py).
    B, K must be padded to (bb, bk) multiples; zero codes pad harmlessly.
    """
    bsz, kdim = a_codes.shape
    assert a_codes.shape == b_codes.shape
    assert bsz % bb == 0 and kdim % bk == 0
    grid = (bsz // bb, kdim // bk)
    kernel = functools.partial(quire_dot_kernel, k_steps=grid[1])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bk), lambda i, k: (i, k)),
            pl.BlockSpec((bb, bk), lambda i, k: (i, k)),
        ],
        out_specs=[
            pl.BlockSpec((bb, 1), lambda i, k: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
            jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a_codes, b_codes)
