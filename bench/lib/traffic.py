"""The one traffic generator: a mix's parameters in, an open-loop
schedule out.

Every seed of a mix gets the same work in the same arrangement: the
same due times, prompt lengths and output lengths (stratified quantiles
of the mix's distributions, put in an order drawn once from the mix's
own ``arrangement_seed``).  The run's seed draws the token ids (and the
configuration's weights).  At a few dozen requests a window, the order
of long and short requests moves the tails far more than two runs of
one seed differ (26-57% against under 12% at 0.5 req/s on the chat
mix), so the arrangement is part of the mix and not of the seed.

Arrivals are placed in operational time (unit rate) and mapped to real
time through the inverse of the cumulative intensity, so a Poisson mix
and an on/off-modulated one share one code path.

A mix file::

  {"arrivals": {"kind": "poisson", "rate_per_s": 12.0}
            or {"kind": "onoff", "rate_per_s": 10, "period_s": 10,
                "on_s": 2, "on_factor": 4},
   "prompt_tokens": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                     "min": 64, "max": 3072}
                 or {"dist": "uniform", "min": 8, "max": 48},
   "output_tokens": {...same forms...},
   "arrangement_seed": 0,
   "drain_s": 60,
   "engine": {...ContinuousEngine settings...},
   "check": {"min_served_tokens": 400, "max_requests": 8}}
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Dict, List, Optional

import numpy as np

__all__ = ["Request", "intensity", "schedule", "lengths", "n_requests"]


@dataclasses.dataclass
class Request:
    due: float                 # seconds after the window opens
    prompt: np.ndarray         # int32 token ids
    out_len: int               # tokens to generate (greedy, no EOS)


def _rate(arr: Dict[str, Any], override: Optional[float]) -> float:
    return float(override if override is not None else arr["rate_per_s"])


def intensity(arr: Dict[str, Any], t: np.ndarray,
              rate: Optional[float] = None) -> np.ndarray:
    """Arrival rate at times ``t`` (requests per second)."""
    mean = _rate(arr, rate)
    if arr["kind"] == "poisson":
        return np.full(np.shape(t), mean)
    if arr["kind"] == "onoff":
        period, on = float(arr["period_s"]), float(arr["on_s"])
        factor = float(arr["on_factor"])
        off_rate = mean * period / (factor * on + (period - on))
        phase = np.mod(t, period)
        return np.where(phase < on, factor * off_rate, off_rate)
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def _cumulative(arr, seconds: float, rate) -> tuple:
    grid = np.linspace(0.0, seconds, int(seconds * 1000) + 1)
    lam = intensity(arr, grid, rate)
    cum = np.concatenate([[0.0], np.cumsum(
        0.5 * (lam[1:] + lam[:-1]) * np.diff(grid))])
    return grid, cum


def n_requests(arr: Dict[str, Any], seconds: float,
               rate: Optional[float] = None) -> int:
    _, cum = _cumulative(arr, seconds, rate)
    return max(1, int(round(cum[-1])))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` stratified draws of a length distribution, ascending."""
    q = _quantiles(n)
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf(float(p)) for p in q])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif dist["dist"] == "uniform":
        x = lo + q * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def schedule(mix: Dict[str, Any], seed: int, seconds: float, vocab: int,
             rate: Optional[float] = None) -> List[Request]:
    """The requests due in a window of ``seconds``, sorted by due time."""
    arr = mix["arrivals"]
    grid, cum = _cumulative(arr, seconds, rate)
    n = max(1, int(round(cum[-1])))
    order = np.random.default_rng(int(mix.get("arrangement_seed", 0)))
    gaps = -np.log1p(-_quantiles(n))            # unit-mean exponential
    gaps = order.permutation(gaps)
    op = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    op *= cum[-1] / gaps.sum()                  # n arrivals in [0, total)
    due = np.interp(op, cum, grid)
    plen = order.permutation(lengths(mix["prompt_tokens"], n))
    olen = order.permutation(lengths(mix["output_tokens"], n))
    rng = np.random.default_rng(seed)
    return [Request(float(d), rng.integers(0, vocab, int(p)).astype(np.int32),
                    int(o)) for d, p, o in zip(due, plen, olen)]
