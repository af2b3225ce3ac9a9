"""RMMEC packed mixed-precision GEMM -- the XR-NPE MAC array on TPU.

The ASIC datapath: packed low-bit operands stream in, the RMMEC block
decodes mantissa/exponent per ``prec_sel``, zero operands power-gate their
multiplier, and a quire accumulates.  The TPU port keeps the same stages,
re-cut for the HBM->VMEM->MXU hierarchy:

  HBM traffic   : weights live PACKED in HBM (uint32 words holding 8x4b /
                  4x8b / 2x16b codes) -- this is the bandwidth saving.
  VMEM decode   : each weight block is unpacked + decoded *in VMEM* by the
                  codec registry (``core.codec``), which under tracing
                  always picks the branch-free integer datapath (the RMMEC
                  analogue; one static mode per compiled kernel, mirroring
                  the hardware ``prec_sel`` register).
  power gating  : a per-(K-block, N-block) nonzero mask lets ``pl.when``
                  skip the MXU work of all-zero weight blocks entirely --
                  the dark-silicon reduction, as compute-cycle gating.
  quire         : f32 MXU accumulation; products of <=12-bit mantissas
                  accumulate exactly per step (bit-exact quire semantics for
                  the Posit(8,0) path is provided by the separate
                  ``quire_dot`` kernel).
  morphable tile: block shapes are chosen per precision mode so the packed
                  working set fills VMEM and MXU dims stay 128-aligned --
                  the 8x8/16x16 morphable-array analogue.

Grid is (M/bm, N/bn, K/bk) with the K axis innermost ('arbitrary'); the
output block is revisited across K steps and used as the accumulator.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import codec as codec_mod
from ..core.formats import FormatSpec
from ..core.packing import lanes_per_word

__all__ = ["rmmec_matmul_kernel", "rmmec_matmul_pallas", "default_blocks",
           "code_planes", "planar", "interleave"]


def default_blocks(spec: FormatSpec) -> Tuple[int, int, int]:
    """Morphable tiling: (bm, bk, bn) per precision mode.

    Lower-precision modes pack more codes per HBM word, so a larger K block
    keeps the MXU fed from the same packed VMEM budget.  ``bn`` is 128
    words wide in every mode (``bn // per == 128``): the packed-word block
    is the lane dim the TPU compiler tiles, and it must be a multiple of
    128.
    """
    per = lanes_per_word(spec.bits)
    if spec.bits <= 4:
        return (128, 1024, 128 * per)
    return (128, 512, 128 * per)


def _compute_dtype(spec: FormatSpec, x_dtype):
    # Follow the activation dtype: bf16 activations get the 2x-rate MXU
    # path (<=8-bit formats decode *exactly* into bf16 -- <=6 mantissa
    # bits); f32 activations keep full precision.  Posit16 always decodes
    # to f32 (12 fraction bits exceed bf16's 8).
    if x_dtype == jnp.bfloat16 and spec.bits <= 8:
        return jnp.bfloat16
    return jnp.float32


def code_planes(words: jax.Array, bits: int):
    """(..., W) uint32 words -> ``per`` code planes of shape (..., W);
    plane j holds the codes of logical columns ``w * per + j``.  Peeling
    planes keeps every array 2-D in the word layout -- no lane interleave
    inside the kernel; callers re-interleave outside (``planar``)."""
    mask = jnp.uint32((1 << bits) - 1)
    return [(words >> jnp.uint32(j * bits)) & mask
            for j in range(lanes_per_word(bits))]


def planar(a: jax.Array, per: int) -> jax.Array:
    """(G, N) column-interleaved -> (per, G, N // per) planar layout."""
    g, n = a.shape
    return a.reshape(g, n // per, per).transpose(2, 0, 1)


def interleave(a: jax.Array) -> jax.Array:
    """(per, M, W) planar -> (M, W * per): the inverse of ``planar``."""
    per, m, w = a.shape
    return a.transpose(1, 2, 0).reshape(m, w * per)


def rmmec_matmul_kernel(mask_ref, x_ref, w_ref, s_ref, o_ref, *,
                        spec: FormatSpec, k_steps: int,
                        group: Optional[int]):
    """One (bm, bn) output block, held planar as (per, bm, bn // per);
    K-step accumulation with block gating.

    ``group`` None: per-channel scales, applied once at output (the seed
    path).  ``group`` set: the scale block holds bk/group rows and is
    applied to the decoded weights *inside* the quire accumulation --
    each K-block's contribution enters the accumulator already on its
    own group grid (the scale-accumulate stage of the paper's datapath,
    at K-group granularity).
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    j = pl.program_id(1)
    gate = mask_ref[k, j]

    @pl.when(gate != 0)
    def _mac():
        cdt = _compute_dtype(spec, x_ref.dtype)
        # the MXU's default precision rounds f32 operands to bf16: an f32
        # product (posit16's 12 fraction bits) needs the full passes
        precision = jax.lax.Precision.HIGHEST if cdt == jnp.float32 \
            else None
        x = x_ref[...].astype(cdt)
        for p, codes in enumerate(code_planes(w_ref[...], spec.bits)):
            # RMMEC decode, in VMEM -- codec picks the branch-free path.
            # Decode and scale in f32 (the v5e VPU has no bf16 compare),
            # then narrow: a <=8-bit code times a po2 scale is exact in
            # bf16, so the fast path keeps its 2x MXU rate bit for bit.
            w = codec_mod.decode(spec, codes, jnp.float32)  # (bk, bn//per)
            if group is not None:
                # per-group scale inside the accumulation
                s = s_ref[p]                                # (bk//group, .)
                bk, nw = w.shape
                w = (w.reshape(bk // group, group, nw)
                     * s[:, None, :]).reshape(bk, nw)
            o_ref[p] += jnp.dot(x, w.astype(cdt), precision=precision,
                                preferred_element_type=jnp.float32)

    if group is None:
        @pl.when(k == k_steps - 1)
        def _scale():
            # output processing stage: apply the per-column
            # (exponent-shift) scale once, after quire accumulation.
            o_ref[...] = o_ref[...] * s_ref[...].astype(jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "bm", "bk", "bn", "group", "interpret"),
)
def rmmec_matmul_pallas(x: jax.Array, w_words: jax.Array, scales: jax.Array,
                        mask: jax.Array, *, spec: FormatSpec,
                        bm: int, bk: int, bn: int,
                        group: Optional[int] = None,
                        interpret: bool = False) -> jax.Array:
    """x:(M,K) float  @  packed w:(K, N/per) uint32  -> (M, N) f32.

    scales: (G, N) f32 dequant scales -- G=1 per-output-channel (applied
            once at output), G=K/group per-(K-group, channel) (applied
            per K-block inside the accumulation).
    mask:   (K/bk, N/bn) int32 nonzero-block map (0 -> power-gated).
    All dims must already be padded to block multiples (see ops.py).
    The kernel works on code planes (``code_planes``): scales enter and
    the result leaves planar, and are re-laid out here in XLA.
    """
    m, kdim = x.shape
    per = lanes_per_word(spec.bits)
    n = w_words.shape[1] * per
    nw = bn // per
    assert m % bm == 0 and kdim % bk == 0 and n % bn == 0, (m, kdim, n)
    if group is not None:
        assert bk % group == 0 and scales.shape[0] == kdim // group, \
            (bk, group, scales.shape)
    grid = (m // bm, n // bn, kdim // bk)
    kernel = functools.partial(rmmec_matmul_kernel, spec=spec,
                               k_steps=grid[2], group=group)
    if group is None:
        s_spec = pl.BlockSpec((per, 1, nw), lambda i, j, k: (0, 0, j))
    else:
        s_spec = pl.BlockSpec((per, bk // group, nw),
                              lambda i, j, k: (0, k, j))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),               # gate map
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),      # x
            pl.BlockSpec((bk, nw), lambda i, j, k: (k, j)),      # packed w
            s_spec,                                              # scales
        ],
        out_specs=pl.BlockSpec((per, bm, nw), lambda i, j, k: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((per, m, n // per), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(mask, x, w_words, planar(scales, per))
    return interleave(out)
