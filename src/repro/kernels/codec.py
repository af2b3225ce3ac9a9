"""Standalone SIMD decode (dequantization) kernel.

Streams packed uint32 words from HBM and writes decoded floats -- the
input-processing stage of the NPE in isolation.  Used when a consumer
needs materialized weights (e.g. one-time decode at model load, or
debugging), and as the unit-bench for decode throughput.  Format decode
goes through the codec registry (``core.codec``), which under tracing
always picks the kernel-safe branch-free path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import codec as codec_mod
from ..core.formats import FormatSpec
from ..core.packing import lanes_per_word
from .rmmec_matmul import code_planes, interleave, planar

__all__ = ["dequant_kernel", "dequant_pallas"]


def dequant_kernel(w_ref, s_ref, o_ref, *, spec: FormatSpec,
                   group: Optional[int]):
    """One (bk, bn) block, written planar as (per, bk, bn // per)."""
    for p, codes in enumerate(code_planes(w_ref[...], spec.bits)):
        w = codec_mod.decode(spec, codes, jnp.float32)
        s = s_ref[p]
        if group is not None:
            bk, nw = w.shape
            s = jnp.broadcast_to(s[:, None, :], (bk // group, group, nw)) \
                .reshape(bk, nw)
        o_ref[p] = w * s


@functools.partial(jax.jit, static_argnames=("spec", "bk", "bn", "group",
                                             "interpret"))
def dequant_pallas(w_words: jax.Array, scales: jax.Array, *,
                   spec: FormatSpec, bk: int = 256, bn: int = 512,
                   group: Optional[int] = None,
                   interpret: bool = False) -> jax.Array:
    """(K, N/per) uint32 + (G, N) scales -> (K, N) f32.

    G = 1 is per-channel; G = K/group gives each K-group its own scale
    row (``bk`` must be a multiple of ``group``).  On the chip ``bn //
    per`` must be a multiple of 128 (or the whole packed width).
    """
    per = lanes_per_word(spec.bits)
    k, nw = w_words.shape
    n = nw * per
    bw = bn // per
    assert k % bk == 0 and n % bn == 0
    if group is None:
        s_spec = pl.BlockSpec((per, 1, bw), lambda i, j: (0, 0, j))
    else:
        assert bk % group == 0 and scales.shape[0] == k // group, \
            (bk, group, scales.shape)
        s_spec = pl.BlockSpec((per, bk // group, bw), lambda i, j: (0, i, j))
    out = pl.pallas_call(
        functools.partial(dequant_kernel, spec=spec, group=group),
        grid=(k // bk, n // bn),
        in_specs=[
            pl.BlockSpec((bk, bw), lambda i, j: (i, j)),
            s_spec,
        ],
        out_specs=pl.BlockSpec((per, bk, bw), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((per, k, nw), jnp.float32),
        interpret=interpret,
    )(w_words, planar(scales, per))
    return interleave(out)
