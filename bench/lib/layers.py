"""What a per-layer metric reader is given, and the sums several of
them share.

All times are on the harness's clock: seconds after the window opened.
``spans`` and ``events`` are the program's own ``obs.TraceRecorder``
records (span kind -> [(start, duration)], event kind -> [(time, rid,
args)]); ``steps`` are the harness's record of every engine step, with
the decode rows each step dispatched; ``device`` is the reduced
profiler trace of the traced stretch (``devtrace.Reduced``), or None.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["Context", "traced_decode_cost", "traced_prefill_cost",
           "roofline_share"]


@dataclasses.dataclass
class Context:
    model: Any                     # the configuration's cost model
    peak: Dict[str, Any]           # the chip's published peaks
    engine: Dict[str, Any]         # the mix's engine settings
    window_s: float
    requests: List[Any]            # run.Rec
    steps: List[Any]               # run.Step
    spans: Dict[str, List[Tuple[float, float]]]
    events: Dict[str, List[Tuple[float, Any, Dict[str, Any]]]]
    prompt_len: Dict[int, int]     # rid -> prompt tokens
    device: Optional[Any]
    compiles_in_window: int

    @property
    def traced(self) -> Optional[Tuple[float, float]]:
        """(first traced step start, last traced step end)."""
        t = [s for s in self.steps if s.traced]
        return (t[0].t0, t[-1].t1) if t else None


def roofline_seconds(ctx: Context, flops: float, nbytes: float) -> float:
    return max(flops / ctx.peak["bf16_flops_per_s"],
               nbytes / ctx.peak["hbm_bytes_per_s"])


def traced_decode_cost(ctx: Context):
    """(FLOPs, least seconds, dispatches) of the decode dispatches of the
    traced steps."""
    page = ctx.engine["page_size"]
    flops = least = 0.0
    n = 0
    for s in ctx.steps:
        if not s.traced or not s.decode:
            continue
        pos = [p for p, _ in s.decode]
        k = [m for _, m in s.decode]
        f = ctx.model.decode_dispatch_flops(pos, k)
        b = ctx.model.decode_dispatch_bytes(pos, k, page)
        flops += f
        least += roofline_seconds(ctx, f, b)
        n += 1
    return flops, least, n


def _chunks_in(ctx: Context, lo: float, hi: float):
    for t, rid, args in ctx.events.get("PREFILL_CHUNK", []):
        if lo <= t <= hi:
            start, real = int(args["start"]), int(args["real"])
            done = start + real >= ctx.prompt_len.get(rid, 0)
            yield start, real, done


def traced_prefill_cost(ctx: Context):
    """(FLOPs, least seconds, chunks) of the prefill chunks of the traced
    steps."""
    win = ctx.traced
    if win is None:
        return 0.0, 0.0, 0
    flops = least = 0.0
    n = 0
    for start, real, done in _chunks_in(ctx, *win):
        f = ctx.model.prefill_flops(start, real, done)
        flops += f
        least += roofline_seconds(ctx, f, ctx.model.prefill_bytes(start,
                                                                  real))
        n += 1
    return flops, least, n


def window_prefill_flops(ctx: Context, lo: float, hi: float) -> float:
    return sum(ctx.model.prefill_flops(s, r, d)
               for s, r, d in _chunks_in(ctx, lo, hi))


def roofline_share(least: float, device_s: Optional[List[float]],
                   count: int) -> Optional[float]:
    """Least time over device time, in percent; None where the trace
    has no complete run of the program or the runs and the dispatches
    the harness counted disagree."""
    if not device_s or count == 0 or len(device_s) != count:
        return None
    return 100.0 * least / sum(device_s)
