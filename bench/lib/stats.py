"""Percentiles over all requests, failed ones included."""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

__all__ = ["percentile_with_failures", "p95_with_failures"]


def percentile_with_failures(values: Sequence[Optional[float]], q: float,
                             worst: float) -> float:
    """The ``q``-th percentile (linear interpolation) of ``values``,
    where None marks a failed request and ranks as ``worst``, a value
    above every finished one."""
    if not values:
        raise ValueError("no requests")
    x = np.array([worst if v is None else float(v) for v in values])
    return float(np.percentile(x, q))


def p95_with_failures(values: Sequence[Optional[float]],
                      worst: float) -> float:
    return percentile_with_failures(values, 95.0, worst)
