"""Fused quantized-KV flash-decode kernel -- online softmax over posit8
KV blocks, dequantized in VMEM.

The decode roofline is KV + weight bytes.  PR 1 stopped paying bf16 for
the weights; this kernel stops paying it for the KV cache: the posit8
codes (+ po2 group scales) stream from HBM and are decoded per block
*inside* the kernel by the codec registry's branch-free path -- the same
VMEM-decode stage ``rmmec_matmul`` uses for weights, applied to the KV
plane.  The bf16 cache never exists in HBM.

Grid is (B, T/blk) with the KV-block axis innermost ('arbitrary'): one
grid cell holds ALL Kh heads of one request, and the (Kh, G, Dh) output
block is revisited across T steps while the online-softmax state (f32
accumulator, running max m, normalizer l, one slab per head) lives in
VMEM scratch, carried across grid steps exactly like the K-step
accumulator of ``rmmec_matmul``.

Block layout: the KV operands are viewed as (..., T, Kh*Dh) rows -- a
free reshape of the (..., T, Kh, Dh) cache -- and a block is
``(blk, Kh*Dh)``: whole token rows, every head at once.  Mosaic tiles
the last two block dims and needs each to be (8, 128)-aligned or the
full array dim; a per-head block ``(blk, 1, Dh)`` over (T, Kh, Dh) has a
second-minor dim of 1, which the TPU compiler refuses, while the row
block is full-width in its last dim and page-sized (a multiple of 8) in
its second-minor dim.  Heads are split off by static lane slices of the
decoded block inside the kernel, so the per-head math is unchanged.

Length-aware block skipping: ``pos`` arrives as a scalar-prefetch
operand, so the KV BlockSpec index maps clamp the T-block index to
``pos // blk``.  Every grid step past the live prefix maps to the SAME
HBM block -- Pallas sees an unchanged block index between consecutive
steps and issues no new DMA -- and ``pl.when`` gates its compute off.  A
step at position ``pos`` therefore moves ceil((pos+1)/blk) KV blocks
instead of ``max_len/blk``, so short sequences in a long cache no
longer pay for ``max_len``.

``attn_decode`` (models/attention.py) carries the pure-XLA analogue (a
``fori_loop`` over the same blocks) for targets where a Pallas call is
not portable -- the dry-run's host-compile path and sharded caches --
mirroring the ``PACKED_USE_KERNEL`` split of the weight plane.

Paged variant (``paged_flash_decode_pallas``): the KV operands are a
POOL of fixed-size pages (page size == the KV block) shared by all
requests, and each request owns a row of a page table.  The
scalar-prefetch clamp generalizes exactly as the PR 2 design predicted:
the block-index clamp ``min(t, pos // blk)`` becomes a GATHER through
the prefetched page-table row, ``page_table[i, min(t, pos[i] // blk)]``
-- dead grid steps still map to the request's last live page, so Pallas
re-uses the resident block and issues no DMA.  ``pos`` is per-request
(a second scalar-prefetch operand): requests at different positions
decode in one batched grid, which is what continuous batching needs.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import codec as codec_mod
from ..core import formats as fmt

__all__ = ["flash_decode_kernel", "flash_decode_pallas", "default_kv_block",
           "paged_flash_decode_kernel", "paged_flash_decode_pallas",
           "paged_flash_prefill_kernel", "paged_flash_prefill_pallas"]

_NEG_INF = -1e30

_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def default_kv_block(max_len: int) -> int:
    """Largest KV block size <= 128 that divides ``max_len`` (128 keeps
    MXU dims aligned while staying fine-grained enough that the live
    prefix ceil((pos+1)/blk) tracks ``pos``, not ``max_len``)."""
    for blk in (128, 64, 32, 16, 8, 4, 2):
        if max_len % blk == 0:
            return blk
    return 1


def _rows(x: jax.Array) -> jax.Array:
    """(..., T, Kh, X) -> (..., T, Kh*X): the row view the kernels block."""
    return x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])


def _dequant_head(x_all, s_all, h: int, dh: int, gs: int) -> jax.Array:
    """Head ``h`` of a decoded (blk, Kh*Dh) f32 block, times its group
    scales from the (blk, Kh*Gs) scale block.  The scale columns are
    expanded by exact selects (Gs = Dh/group, Gs=1 broadcasts), so every
    element is ``decode(code) * scale`` as in the oracle."""
    x = x_all[:, h * dh:(h + 1) * dh]
    s = s_all[:, h * gs:(h + 1) * gs]
    if gs == 1:
        return x * s
    grp = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) // (dh // gs)
    full = jnp.broadcast_to(s[:, :1], x.shape)
    for j in range(1, gs):
        full = jnp.where(grp == j, s[:, j:j + 1], full)
    return x * full


def _online_softmax_step(pos_last, visible: Callable, q_ref, kc_ref, ks_ref,
                         vc_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref, *,
                         blk: int, softcap: float, scale: float,
                         pad_lo=None):
    """One grid step of the online-softmax accumulation: init scratch at
    t=0, accumulate the current KV block while any query row is live for
    it, emit the normalized output at the last step.  ONE copy of the
    math for the contiguous-decode, paged-decode and paged-prefill
    kernels (the bitwise-parity tests rest on it) -- they differ only in
    how the BlockSpec index maps pick the HBM block and in the query
    geometry:

      q_ref    : (Kh, R, Dh) query block (decode: R = G; prefill:
                 R = C*G, row = qi*G + gi); o_ref has the same shape.
      visible  : ``visible(kpos)`` -> bool (R, blk) key-visibility mask
                 for the absolute key positions ``kpos`` (R, blk).
      pos_last : scalar last live query position -- gates dead grid
                 steps off.
      pad_lo   : optional low key-visibility bound (ragged LEFT-padded
                 batches: slots below the request's pad width are dead).
                 ``None`` (the paged kernels, where rows have no pad)
                 compiles the exact pre-pad mask -- bitwise unchanged.

    With ``pad_lo`` set, blocks that sit ENTIRELY below the pad are
    gated off like blocks past the live horizon (their index map clamps
    them onto the first live block, so they are never fetched either).
    Skipping them is bitwise-identical to masking them: an all-masked
    block leaves m at -1e30 and contributes p-rows that the first live
    block's rescale ``alpha = exp(-1e30 - m_new)`` underflows to +0.0,
    annihilating acc and l exactly -- the gated path just starts from
    the same (acc=0, m=-1e30, l=0) scratch state at that block.
    """
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    live = t * blk <= pos_last
    if pad_lo is not None:
        # block t covers slots [t*blk, (t+1)*blk): it holds a live slot
        # iff its last slot reaches the pad
        live &= (t + 1) * blk > pad_lo

    @pl.when(live)
    def _block():
        kh, r, dh = acc_ref.shape
        gs = ks_ref.shape[-1] // kh
        kx = codec_mod.decode(fmt.POSIT8, kc_ref[...].astype(jnp.int32),
                              jnp.float32)                 # (blk, Kh*Dh)
        vx = codec_mod.decode(fmt.POSIT8, vc_ref[...].astype(jnp.int32),
                              jnp.float32)
        ks = ks_ref[...].astype(jnp.float32)               # (blk, Kh*Gs)
        vs = vs_ref[...].astype(jnp.float32)
        kpos = t * blk + jax.lax.broadcasted_iota(jnp.int32, (r, blk), 1)
        mask = visible(kpos)
        if pad_lo is not None:
            mask &= kpos >= pad_lo
        for h in range(kh):
            q = q_ref[h].astype(jnp.float32)                # (R, Dh)
            k = _dequant_head(kx, ks, h, dh, gs)            # (blk, Dh)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (R, blk)
            if softcap > 0.0:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_ref[h] = m_new
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            v = _dequant_head(vx, vs, h, dh, gs)            # (blk, Dh)
            pv = jnp.dot(p, v, preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * alpha + pv

    @pl.when(t == nt - 1)
    def _finalize():
        o_ref[...] = acc_ref[...] / l_ref[...]


def _scratch(kh: int, r: int, dh: int):
    return [pltpu.VMEM((kh, r, dh), jnp.float32),   # acc
            pltpu.VMEM((kh, r, 1), jnp.float32),    # running max m
            pltpu.VMEM((kh, r, 1), jnp.float32)]    # normalizer l


def flash_decode_kernel(pos_ref, pad_ref, q_ref, kc_ref, ks_ref, vc_ref,
                        vs_ref, o_ref, acc_ref, m_ref, l_ref, *,
                        blk: int, softcap: float, scale: float):
    """One request; online-softmax accumulation over live KV blocks.
    ``pad_ref`` holds per-request left-pad widths ((B,), zeros for a
    non-ragged batch): slots below ``pad_ref[i]`` are masked dead, the
    left-padded twin of the causal mask."""
    pos = pos_ref[0]
    _online_softmax_step(pos, lambda kpos: kpos <= pos, q_ref, kc_ref,
                         ks_ref, vc_ref, vs_ref, o_ref, acc_ref, m_ref,
                         l_ref, blk=blk, softcap=softcap, scale=scale,
                         pad_lo=pad_ref[pl.program_id(0)])


def paged_flash_decode_kernel(pt_ref, pos_ref, q_ref, kc_ref, ks_ref,
                              vc_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref,
                              *, blk: int, softcap: float, scale: float):
    """Paged cell: identical math, but ``pos`` is per-request and the KV
    blocks were gathered through the page table by the index maps (the
    kernel body never sees physical page ids)."""
    pos = pos_ref[pl.program_id(0)]
    _online_softmax_step(pos, lambda kpos: kpos <= pos, q_ref, kc_ref,
                         ks_ref, vc_ref, vs_ref, o_ref, acc_ref, m_ref,
                         l_ref, blk=blk, softcap=softcap, scale=scale)


@functools.partial(jax.jit,
                   static_argnames=("blk", "softcap", "interpret"))
def flash_decode_pallas(q: jax.Array, k_codes: jax.Array, k_scale: jax.Array,
                        v_codes: jax.Array, v_scale: jax.Array,
                        pos: jax.Array, *, pad: Optional[jax.Array] = None,
                        blk: Optional[int] = None,
                        softcap: float = 0.0,
                        interpret: bool = False) -> jax.Array:
    """GQA decode attention straight from posit8 KV codes.

    q                : (B, Kh, G, Dh) float -- one new token's queries,
                       grouped per KV head.
    k_codes/v_codes  : (B, T, Kh, Dh) uint8 posit8 codes (T = max_len).
    k_scale/v_scale  : (B, T, Kh, Gs) po2 dequant scales in the unified
                       ``quant.group_scales`` layout: Gs = Dh/group
                       (Gs = 1 is per-(token, head), the group=Dh case).
    pos              : scalar int32 -- attends to cache slots [0, pos].
    pad              : optional (B,) int32 left-pad widths of a ragged
                       batch -- request i additionally masks slots below
                       ``pad[i]`` (None == an all-zeros pad: the dense
                       static-batch case).  Blocks fully below the pad
                       are never fetched: the index map clamps them onto
                       the first live block (``pad[i] // blk``) exactly
                       like dead blocks past the horizon clamp onto the
                       last live one, so the block index stops changing
                       and Pallas issues no DMA; ``pl.when`` gates their
                       compute off.  A step for row i therefore moves
                       only its ``ceil((pos+1)/blk) - pad[i] // blk``
                       live blocks -- and the output is bitwise the old
                       mask-everything path's (see
                       ``_online_softmax_step``).

    Returns (B, Kh, G, Dh) f32 attention output.
    """
    b, kh, g, dh = q.shape
    t = k_codes.shape[1]
    gs = k_scale.shape[-1]
    if blk is None:
        blk = default_kv_block(t)
    assert t % blk == 0, (t, blk)
    nt = t // blk

    def q_im(i, tt, pos_ref, pad_ref):
        return (i, 0, 0, 0)

    def kv_im(i, tt, pos_ref, pad_ref):
        # clamp dead blocks onto the live window: blocks past the
        # horizon re-map to the last live block and blocks fully below
        # the left pad to the first live one -- either side, the block
        # index stops changing, so Pallas re-uses the resident copy
        # (no DMA).  pad <= pos for any valid row, so lo <= hi.
        return (i, jnp.clip(tt, pad_ref[i] // blk, pos_ref[0] // blk), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nt),
        in_specs=[
            pl.BlockSpec((None, kh, g, dh), q_im),
            pl.BlockSpec((None, blk, kh * dh), kv_im),
            pl.BlockSpec((None, blk, kh * gs), kv_im),
            pl.BlockSpec((None, blk, kh * dh), kv_im),
            pl.BlockSpec((None, blk, kh * gs), kv_im),
        ],
        out_specs=pl.BlockSpec((None, kh, g, dh), q_im),
        scratch_shapes=_scratch(kh, g, dh),
    )
    kernel = functools.partial(flash_decode_kernel, blk=blk,
                               softcap=float(softcap),
                               scale=1.0 / math.sqrt(dh))
    pos_arr = jnp.asarray(pos, jnp.int32).reshape((1,))
    pad_arr = jnp.zeros((b,), jnp.int32) if pad is None \
        else jnp.asarray(pad, jnp.int32).reshape((b,))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, dh), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(pos_arr, pad_arr, q, _rows(k_codes), _rows(k_scale), _rows(v_codes),
      _rows(v_scale))


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_flash_decode_pallas(q: jax.Array, k_codes: jax.Array,
                              k_scale: jax.Array, v_codes: jax.Array,
                              v_scale: jax.Array, page_table: jax.Array,
                              positions: jax.Array, *,
                              softcap: float = 0.0,
                              interpret: bool = False) -> jax.Array:
    """GQA decode attention over a PAGED posit8 KV pool.

    q                : (B, Kh, G, Dh) float -- one new token per request.
    k_codes/v_codes  : (P, page, Kh, Dh) uint8 pool pages (page = KV blk).
    k_scale/v_scale  : (P, page, Kh, Gs) po2 scales, unified layout.
    page_table       : (B, NP) int32 -- request i's logical block t lives
                       in pool page ``page_table[i, t]``; rows are padded
                       with a parking page id past the live prefix.
    positions        : (B,) int32 -- request i attends to logical slots
                       [0, positions[i]].

    The whole page indirection lives in the KV BlockSpec index map: the
    contiguous kernel's clamp ``min(t, pos // blk)`` becomes the gather
    ``page_table[i, min(t, pos[i] // blk)]`` through the two prefetched
    scalar operands.  Past a request's live prefix the gathered page id
    stops changing, so Pallas sees an unchanged block index and issues no
    DMA -- a step still moves only ceil((pos+1)/page) pages per request.

    Returns (B, Kh, G, Dh) f32 attention output.
    """
    b, kh, g, dh = q.shape
    blk = k_codes.shape[1]
    gs = k_scale.shape[-1]
    npp = page_table.shape[1]

    def q_im(i, tt, pt_ref, pos_ref):
        return (i, 0, 0, 0)

    def kv_im(i, tt, pt_ref, pos_ref):
        # the PR 2 clamp, now a gather: dead steps re-read the request's
        # last live page (same block index -> no DMA)
        tc = jnp.minimum(tt, pos_ref[i] // blk)
        return (pt_ref[i * npp + tc], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, npp),
        in_specs=[
            pl.BlockSpec((None, kh, g, dh), q_im),
            pl.BlockSpec((None, blk, kh * dh), kv_im),
            pl.BlockSpec((None, blk, kh * gs), kv_im),
            pl.BlockSpec((None, blk, kh * dh), kv_im),
            pl.BlockSpec((None, blk, kh * gs), kv_im),
        ],
        out_specs=pl.BlockSpec((None, kh, g, dh), q_im),
        scratch_shapes=_scratch(kh, g, dh),
    )
    kernel = functools.partial(paged_flash_decode_kernel, blk=blk,
                               softcap=float(softcap),
                               scale=1.0 / math.sqrt(dh))
    pt_flat = page_table.reshape(-1).astype(jnp.int32)
    pos_arr = jnp.asarray(positions, jnp.int32)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, dh), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(pt_flat, pos_arr, q, _rows(k_codes), _rows(k_scale), _rows(v_codes),
      _rows(v_scale))


def paged_flash_prefill_kernel(pt_ref, start_ref, q_ref, kc_ref, ks_ref,
                               vc_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref,
                               *, blk: int, c: int, g: int, softcap: float,
                               scale: float):
    """One request of the paged chunk-PREFILL kernel: the SAME
    online-softmax body as the decode kernels, widened to a (C*G, Dh)
    query block per head (row ``qi*G + gi``).  Row ``r`` sees key ``k``
    iff ``k <= start + r // G``, evaluated division-free as
    ``(k - start) * G <= r`` (the same set for integers, r >= 0)."""
    start = start_ref[pl.program_id(0)]
    row = jax.lax.broadcasted_iota(jnp.int32, (c * g, blk), 0)
    _online_softmax_step(start + c - 1,
                         lambda kpos: (kpos - start) * g <= row,
                         q_ref, kc_ref, ks_ref, vc_ref, vs_ref, o_ref,
                         acc_ref, m_ref, l_ref, blk=blk, softcap=softcap,
                         scale=scale)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_flash_prefill_pallas(q: jax.Array, k_codes: jax.Array,
                               k_scale: jax.Array, v_codes: jax.Array,
                               v_scale: jax.Array, page_table: jax.Array,
                               start: jax.Array, *,
                               softcap: float = 0.0,
                               interpret: bool = False) -> jax.Array:
    """Paged chunk-PREFILL attention over a posit8 KV pool: the prefill
    twin of :func:`paged_flash_decode_pallas`.

    q                : (B, C, Kh, G, Dh) float -- one CHUNK of C queries
                       per request, at absolute positions
                       ``start[i] .. start[i] + C - 1``.
    k_codes/v_codes  : (P, page, Kh, Dh) uint8 pool pages (page = KV blk).
    k_scale/v_scale  : (P, page, Kh, Gs) po2 scales, unified layout.
    page_table       : (B, NP) int32 -- the request's previously written
                       pages plus its own (just-written) chunk pages;
                       rows padded with a parking page id.
    start            : (B,) int32 -- query row j of request i attends to
                       logical slots [0, start[i] + j] causally.

    Identical page indirection to the decode kernel: the KV index map
    gathers ``page_table[i, min(t, (start[i]+C-1) // blk)]``, so grid
    steps past the chunk's last live page re-read the resident block
    (no DMA) and ``pl.when`` gates their compute off.  A chunk step
    moves ceil((start+C)/page) pages -- the chunk's causal prefix --
    regardless of NP.  The queries enter head-major, (B, Kh, C*G, Dh),
    so each head's rows are one contiguous VMEM slab.

    Returns (B, C, Kh, G, Dh) f32 attention output.
    """
    b, c, kh, g, dh = q.shape
    blk = k_codes.shape[1]
    gs = k_scale.shape[-1]
    npp = page_table.shape[1]
    r = c * g

    def q_im(i, tt, pt_ref, start_ref):
        return (i, 0, 0, 0)

    def kv_im(i, tt, pt_ref, start_ref):
        tc = jnp.minimum(tt, (start_ref[i] + c - 1) // blk)
        return (pt_ref[i * npp + tc], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, npp),
        in_specs=[
            pl.BlockSpec((None, kh, r, dh), q_im),
            pl.BlockSpec((None, blk, kh * dh), kv_im),
            pl.BlockSpec((None, blk, kh * gs), kv_im),
            pl.BlockSpec((None, blk, kh * dh), kv_im),
            pl.BlockSpec((None, blk, kh * gs), kv_im),
        ],
        out_specs=pl.BlockSpec((None, kh, r, dh), q_im),
        scratch_shapes=_scratch(kh, r, dh),
    )
    kernel = functools.partial(paged_flash_prefill_kernel, blk=blk, c=c,
                               g=g, softcap=float(softcap),
                               scale=1.0 / math.sqrt(dh))
    pt_flat = page_table.reshape(-1).astype(jnp.int32)
    start_arr = jnp.asarray(start, jnp.int32)
    qh = q.transpose(0, 2, 1, 3, 4).reshape(b, kh, r, dh)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, r, dh), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(pt_flat, start_arr, qh, _rows(k_codes), _rows(k_scale),
      _rows(v_codes), _rows(v_scale))
    return out.reshape(b, kh, c, g, dh).transpose(0, 2, 1, 3, 4)
