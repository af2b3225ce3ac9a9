"""Public ops over the XR-NPE kernels -- the packed-weight data plane.

``prec_sel`` from the paper is the ``spec`` argument here: each format
compiles its own kernel instance (the datapath is statically morphed),
and this module is the mode multiplexer.  On CPU (this container)
kernels run in ``interpret=True``; on TPU they compile to Mosaic.

All format logic goes through the codec registry (``core.codec``): this
module never picks between the table and algorithmic en/decode paths --
the codec does.  The physical weight representation is ``PackedTensor``
v2:

  * rank-generic -- one pack path for 2-D kernel-ready matrices and N-D
    (scan/expert-stacked) weights; leading dims are sliceable by
    ``lax.scan`` exactly like the dense tree;
  * per-group (block-wise) scales along K -- ``group_size`` codes share
    one dequant scale (32/64/128 typical; ``None`` = per-channel, the
    ``group=K`` special case and the bitwise-seed-compatible layout).
    Fine groups track local dynamic range, which is what makes the
    4-bit formats (FP4 / Posit(4,1)) usable on real weights;
  * versioned aux metadata (``version``) so checkpoints round-trip the
    layout across format evolution.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import codec as codec_mod
from ..core import quant
from ..core.formats import FormatSpec
from ..core.packing import lanes_per_word, pack, unpack
from . import ref
from .codec import dequant_pallas
from .quire_dot import QUIRE_FRAC_BITS, quire_dot_pallas
from .rmmec_matmul import default_blocks, rmmec_matmul_pallas

__all__ = [
    "PackedTensor", "pack_tensor", "unpack_tensor", "packed_matmul",
    "quire_dot", "dequant", "should_interpret", "to_dense",
    "PACKED_TENSOR_VERSION",
]

PACKED_TENSOR_VERSION = 2


def should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedTensor:
    """A weight tensor stored as packed low-bit codes + dequant scales.

    words  : (L..., Kp, ceil(Np/per)) uint32 -- the HBM-resident codes
    scales : (L..., G, Np) f32 -- per-(K-group, out-channel) scales;
             G = 1 is per-channel (group=K), G = Kp/group otherwise
    mask   : (L..., Kp/bk, Np/bn) int32 nonzero-block map (power gating)
    shape  : logical (K, N) of one 2-D slice (leading dims untouched)
    spec   : the format (static / aux data)
    group  : K-group size of ``scales`` (None = per-channel)
    version: layout version (checkpoint round-trip compatibility)
    """

    words: jax.Array
    scales: jax.Array
    mask: jax.Array
    shape: Tuple[int, int]
    spec: FormatSpec
    group: Optional[int] = None
    version: int = PACKED_TENSOR_VERSION

    def tree_flatten(self):
        return ((self.words, self.scales, self.mask),
                (self.shape, self.spec, self.group, self.version))

    @classmethod
    def tree_unflatten(cls, aux, children):
        words, scales, mask = children
        return cls(words, scales, mask, *aux)

    @property
    def nbytes_packed(self) -> int:
        return self.words.size * 4 + self.scales.size * 4 + self.mask.size * 4


def pack_tensor(spec: FormatSpec, w: jax.Array,
                scale_method: str = "auto",
                per_channel: bool = True,
                blocks: Optional[Tuple[int, int, int]] = None,
                group_size: Optional[int] = None) -> PackedTensor:
    """Quantize + pack a weight tensor for the serving plane.

    One rank-generic path: the trailing two dims are the logical (K, N)
    matrix; any leading dims are stacked (scan-over-layers / experts)
    and stay sliceable.  2-D inputs additionally get kernel block
    padding + the gating mask (they feed ``rmmec_matmul_pallas``); N-D
    inputs pad only to word/group boundaries (they feed the portable
    ref path / dequant).

    ``group_size``: codes per dequant scale along K (None/0 =
    per-channel).  Kernel block K (``bk``) must be a multiple of it.
    """
    assert w.ndim >= 2, "pack_tensor needs a trailing (K, N) matrix"
    lead, (k, n) = w.shape[:-2], w.shape[-2:]
    per = lanes_per_word(spec.bits)
    g = int(group_size) if group_size else None
    if g is not None and g >= k:
        g = None  # group=K special case: per-channel
    if w.ndim == 2:
        bm, bk, bn = blocks or default_blocks(spec)
        if g is not None and bk % g:
            raise ValueError(f"K block {bk} not a multiple of group {g}")
    else:
        bk, bn = (g or 1), per
    kp, np_ = _round_up(k, bk), _round_up(n, bn)

    # scales on the *logical* tensor (padding never skews a statistic)
    if not per_channel and g is None:
        s = quant.format_scale(spec, w, scale_method)
        scales = jnp.broadcast_to(jnp.asarray(s).reshape(
            (1,) * (w.ndim - 2) + (1, 1)), lead + (1, n))
    else:
        scales = quant.group_scales(spec, w, g, scale_method)
    codes = codec_mod.encode(
        spec, w / quant.expand_group_scales(scales, g, k))

    pad = [(0, 0)] * len(lead) + [(0, kp - k), (0, np_ - n)]
    codes = jnp.pad(codes, pad)
    words = pack(codes, spec.bits)
    # pad scales to the padded layout: extra groups/columns dequant the
    # zero padding codes, so 1.0 is harmless
    g_tot = kp // g if g is not None else 1
    spad = [(0, 0)] * len(lead) + [(0, g_tot - scales.shape[-2]),
                                   (0, np_ - n)]
    scales_p = jnp.pad(scales, spad, constant_values=1.0)

    if w.ndim == 2:
        # nonzero-block map: gate blocks whose codes are all zero
        # (max, not sum: a sum of 16-bit codes overflows int32 per block)
        blk = codes.reshape(kp // bk, bk, np_ // bn, bn)
        mask = (jnp.max(jnp.abs(blk), axis=(1, 3)) > 0).astype(jnp.int32)
    else:
        mask = (jnp.max(jnp.abs(codes), axis=(-2, -1),
                        keepdims=True) > 0).astype(jnp.int32)
    return PackedTensor(words, scales_p, mask, (k, n), spec, g)


def _expand_scales(t: PackedTensor, dtype=jnp.float32) -> jax.Array:
    """Per-row dequant multiplier matching the padded K of ``t.words``."""
    kp = t.words.shape[-2]
    return quant.expand_group_scales(t.scales.astype(dtype),
                                     kp // t.scales.shape[-2], kp)


def to_dense(t: PackedTensor, dtype=jnp.float32) -> jax.Array:
    """Decode a PackedTensor of any rank back to dense float."""
    n_padded = t.words.shape[-1] * lanes_per_word(t.spec.bits)
    codes = unpack(t.words, t.spec.bits, n_padded)
    w = codec_mod.decode(t.spec, codes, dtype=dtype)
    w = w[..., : t.scales.shape[-1]] * _expand_scales(t, dtype)
    return w[..., : t.shape[0], : t.shape[1]]


def unpack_tensor(t: PackedTensor) -> jax.Array:
    """2-D convenience alias of :func:`to_dense` (kept for callers that
    predate the rank-generic path)."""
    return to_dense(t)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret", "use_ref"))
def packed_matmul(x: jax.Array, t: PackedTensor,
                  blocks: Optional[Tuple[int, int, int]] = None,
                  interpret: Optional[bool] = None,
                  use_ref: bool = False) -> jax.Array:
    """x @ W for packed W; x: (..., K) -> (..., N) f32.

    Group scales are applied per K-block inside the kernel's quire
    accumulation (per-channel scales once at output, as before).

    ``use_ref`` selects the pure-jnp oracle path (used by the serving
    plane when lowering for the XLA-only dry-run, where a Pallas call
    would not be portable to the CPU compile target).
    """
    if interpret is None:
        interpret = should_interpret()
    k, n = t.shape
    lead = x.shape[:-1]
    m = int(np.prod(lead)) if lead else 1
    x2 = x.reshape(m, x.shape[-1])  # keep caller dtype: bf16 x => bf16 MXU path
    if use_ref:
        out = ref.rmmec_matmul_ref(x2, t.words, t.scales, t.spec,
                                   t.scales.shape[1])[:, :n]
        return out.reshape(*lead, n)
    bm, bk, bn = blocks or default_blocks(t.spec)
    if t.group is not None and bk % t.group:
        raise ValueError(f"K block {bk} not a multiple of group {t.group}")
    mp = _round_up(m, bm)
    x2 = jnp.pad(x2, ((0, mp - m), (0, t.words.shape[0] - k)))
    out = rmmec_matmul_pallas(x2, t.words, t.scales, t.mask, spec=t.spec,
                              bm=bm, bk=bk, bn=bn, group=t.group,
                              interpret=interpret)
    return out[:m, :n].reshape(*lead, n)


def quire_dot(a_codes: jax.Array, b_codes: jax.Array,
              interpret: Optional[bool] = None) -> jax.Array:
    """Bit-exact Posit(8,0) row-wise dot: (B, K) codes x2 -> (B,) f32."""
    if interpret is None:
        interpret = should_interpret()
    b, k = a_codes.shape
    bb, bk = 8, 512
    bp, kp = _round_up(b, bb), _round_up(k, bk)
    ap = jnp.pad(a_codes, ((0, bp - b), (0, kp - k)))
    bpc = jnp.pad(b_codes, ((0, bp - b), (0, kp - k)))
    hi, lo = quire_dot_pallas(ap.astype(jnp.int32), bpc.astype(jnp.int32),
                              bb=bb, bk=bk, interpret=interpret)
    return quire_combine(hi, lo)[:b]


def quire_combine(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """Fold the two int32 quire limbs into f32 (the single final rounding)."""
    return (hi[:, 0].astype(jnp.float32)
            + lo[:, 0].astype(jnp.float32) * (2.0 ** -QUIRE_FRAC_BITS))


def dequant(t: PackedTensor, interpret: Optional[bool] = None) -> jax.Array:
    """Materialize a 2-D PackedTensor to f32 via the decode kernel."""
    if interpret is None:
        interpret = should_interpret()
    kp = t.words.shape[0]
    npad = t.scales.shape[1]
    per = lanes_per_word(t.spec.bits)
    g = t.group
    kcands = (256, 128, 64, 32, 16, 8, 4, 2, 1) if g is None else \
        tuple(c for c in (256, 128, 64, 32) if c % g == 0) + (g,)
    bk = 256 if (kp % 256 == 0 and (g is None or 256 % g == 0)) \
        else _first_divisor(kp, kcands)
    # 128 packed words per block (the TPU lane tile); the default 2-D
    # packing pads N to it, any other layout takes its whole width
    bn = 128 * per if npad % (128 * per) == 0 else npad
    out = dequant_pallas(t.words, t.scales, spec=t.spec, bk=bk, bn=bn,
                         group=g, interpret=interpret)
    return out[: t.shape[0], : t.shape[1]]


def _first_divisor(n: int, cands) -> int:
    for c in cands:
        if n % c == 0:
            return c
    return 1
