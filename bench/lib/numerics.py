"""Number formats for the plain references, written from their
definitions and independent of the program's codecs.

A configuration states its precision: which weights are stored as
FP4 (e2m1) or posit(n, es) codes with a per-output-channel power-of-two
scale, and which caches are posit8 with a power-of-two scale per token
and head.  The reference rebuilds those values from the master weights
with the functions here and then computes in float32.

Rounding is to nearest.  For posits the boundary between two adjacent
values is the value of the (n+1)-bit pattern between their codes (the
posit standard's rule); for FP4 it is the arithmetic midpoint.  Ties go
to the even code.  Nonzero values never round to zero (posit) and
magnitudes saturate at the largest finite value.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["posit_value", "posit_grid", "fp4_grid", "round_to_grid",
           "weight_values", "kv_values", "act_round", "FP8"]

FP8 = jnp.float8_e4m3fn


def posit_value(code: int, n: int, es: int) -> float:
    """Value of a positive posit(n, es) code, 0 < code < 2**(n-1)."""
    bits = format(code, f"0{n}b")[1:]              # drop the sign bit
    lead = bits[0]
    run = len(bits) - len(bits.lstrip(lead))
    k = run - 1 if lead == "1" else -run
    rest = bits[run + 1:]                          # past the terminator
    e_bits = (rest[:es] + "0" * es)[:es]
    e = int(e_bits, 2) if es else 0
    f_bits = rest[es:]
    frac = int(f_bits, 2) / 2 ** len(f_bits) if f_bits else 0.0
    return float(2.0 ** (k * 2 ** es + e) * (1.0 + frac))


@functools.lru_cache(maxsize=None)
def posit_grid(n: int, es: int) -> Tuple[np.ndarray, np.ndarray,
                                         np.ndarray]:
    """(positive values ascending, rounding boundaries, code parity)."""
    codes = np.arange(1, 2 ** (n - 1))
    vals = np.array([posit_value(int(c), n, es) for c in codes])
    bounds = np.array([posit_value(int(2 * c + 1), n + 1, es)
                       for c in codes[:-1]])
    return vals, bounds, codes % 2


@functools.lru_cache(maxsize=None)
def fp4_grid() -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """FP4 e2m1 (subnormals, no inf/NaN): 0, 0.5, ..., 6."""
    vals = np.array([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0])
    return vals, (vals[1:] + vals[:-1]) / 2, np.arange(8) % 2


def round_to_grid(x: jax.Array, grid, keep_nonzero: bool) -> jax.Array:
    """Round |x| to the nearest grid value (ties to the even code),
    saturating at the top; the sign is restored."""
    vals, bounds, parity = (jnp.asarray(g, jnp.float32) for g in grid)
    a = jnp.abs(x.astype(jnp.float32))
    idx = jnp.searchsorted(bounds, a, side="left")
    on_tie = (idx < bounds.shape[0]) & \
        (a == bounds[jnp.minimum(idx, bounds.shape[0] - 1)])
    idx = jnp.where(on_tie & (parity[jnp.minimum(idx + 1, vals.shape[0] - 1)]
                              == 0), idx + 1, idx)
    q = vals[idx]
    if keep_nonzero:                   # posit grids start at minpos
        q = jnp.where(a == 0, 0.0, q)
    return jnp.sign(x).astype(jnp.float32) * q


def _grid(fmt: str):
    if fmt == "fp4":
        return fp4_grid(), False
    if fmt.startswith("posit"):
        n, es = (int(v) for v in fmt[len("posit"):].split("_"))
        return posit_grid(n, es), True
    raise ValueError(f"no grid for format {fmt!r}")


def _po2(x: jax.Array, ceil: bool) -> jax.Array:
    lg = jnp.log2(jnp.maximum(x, 1e-30))
    return jnp.exp2(jnp.ceil(lg) if ceil else jnp.round(lg))


def weight_values(w: jax.Array, fmt: str) -> jax.Array:
    """The stored values of weight ``w`` (..., K, N) in ``fmt`` with one
    power-of-two scale per output channel: absmax onto the largest
    finite value for FP4, RMS onto 1 for posits."""
    w = w.astype(jnp.float32)
    grid, nonzero = _grid(fmt)
    if fmt == "fp4":
        scale = _po2(jnp.max(jnp.abs(w), axis=-2, keepdims=True)
                     / float(grid[0][-1]), ceil=True)
    else:
        scale = _po2(jnp.sqrt(jnp.mean(jnp.square(w), axis=-2,
                                       keepdims=True)), ceil=False)
    scale = jnp.maximum(scale, 1e-30)
    return round_to_grid(w / scale, grid, nonzero) * scale


def kv_values(x: jax.Array) -> jax.Array:
    """posit8 (es 0) cache values with one power-of-two scale per
    (token, head): absmax onto maxpos (64), the scale kept in bf16."""
    x = x.astype(jnp.float32)
    grid, _ = _grid("posit8_0")
    top = float(grid[0][-1])
    scale = _po2(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / top,
                 ceil=True)
    scale = jnp.maximum(scale, 1e-30).astype(jnp.bfloat16).astype(
        jnp.float32)
    return round_to_grid(x / scale, grid, True) * scale


def act_round(x: jax.Array, dtype) -> jax.Array:
    """Round activations to ``dtype`` and back (identity for None)."""
    if dtype is None:
        return x
    return x.astype(dtype).astype(jnp.float32)
