"""The engine's own spans on the device trace's clock.

With its ``profile`` switch on, the program's span recorder
(``repro.obs.TraceRecorder``) enters a profiler annotation named by each
span's kind -- ``step``, ``prefill_chunk``, ``prefill_sync``,
``decode_dispatch``, ``decode_sync``, ... -- at the instants it records
the span, so the traced window's host annotations say which engine
phase the host was in while the device waited.

``reduce`` takes the events ``devtrace.load_events`` reads and keeps,
for the window ``devtrace.WINDOW`` marks:

- the host annotations of the harness's thread (the line that holds
  the window) that overlap it;
- the device's idle intervals: the gaps in the union of its operations;
- the complete runs of each program inside it.

``of(ctx)`` reads the run's own trace from the harness's trace
directory (once per file), where the harness reduced one; None where
it did not.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import os
from typing import Dict, List, Optional, Sequence, Tuple

from bench.lib import devtrace

__all__ = ["HostTrace", "reduce", "of", "exposed_host_s", "idle_by_span",
           "SYNC_SPANS"]

#: spans in which the host waits for the device on purpose
SYNC_SPANS = ("decode_sync", "prefill_sync")

Interval = Tuple[float, float]


@dataclasses.dataclass
class HostTrace:
    """One traced window; every time in seconds on the trace's clock."""

    lo: float
    hi: float
    spans: List[Tuple[str, float, float]]   # (name, start, end), by start
    idle: List[Interval]                    # device idle, by start
    runs: Dict[str, List[Interval]]         # module -> complete runs

    def named(self, name: str) -> List[Interval]:
        """The complete ``name`` annotations inside the window."""
        return [(a, b) for n, a, b in self.spans
                if n == name and a >= self.lo and b <= self.hi]

    def program_runs(self, name: str) -> List[Interval]:
        """The complete runs of jitted ``name`` inside the window."""
        return self.runs.get("jit_" + name, [])

    def idle_in(self, a: float, b: float) -> float:
        """Device idle seconds inside [a, b]."""
        i = max(bisect.bisect_right(self._starts, a) - 1, 0)
        total = 0.0
        for g0, g1 in self.idle[i:]:
            if g0 >= b:
                break
            total += max(0.0, min(g1, b) - max(g0, a))
        return total

    @functools.cached_property
    def _starts(self) -> List[float]:
        return [g0 for g0, _ in self.idle]


def reduce(events: List[dict]) -> Optional[HostTrace]:
    """None where the trace holds no window or no device operation."""
    wins = [e for e in events if e["plane"] == devtrace.HOST_PLANE
            and e["name"] == devtrace.WINDOW]
    if not wins:
        return None
    w = max(wins, key=lambda e: e["dur_ns"])
    lo, hi = w["start_ns"], w["start_ns"] + w["dur_ns"]
    ops: List[Interval] = []
    runs: Dict[str, List[Interval]] = {}
    spans = []
    for e in events:
        a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
        if e["plane"] == devtrace.HOST_PLANE:
            if e["line"] == w["line"] and e is not w and a < hi and b > lo:
                spans.append((e["name"], a * 1e-9, b * 1e-9))
        elif e["line"] == devtrace.MODULES_LINE:
            if a >= lo and b <= hi:
                runs.setdefault(devtrace._module_name(e["name"]), []).append(
                    (a * 1e-9, b * 1e-9))
        elif e["line"] == devtrace.OPS_LINE and b > lo and a < hi:
            ops.append((max(a, lo), min(b, hi)))
    if not ops:
        return None
    idle = [(a * 1e-9, b * 1e-9) for a, b in devtrace._gaps(ops, lo, hi)]
    spans.sort(key=lambda s: s[1])
    return HostTrace(lo=lo * 1e-9, hi=hi * 1e-9, spans=spans, idle=idle,
                     runs=runs)


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime_ns: int) -> Optional[HostTrace]:
    return reduce(devtrace.load_events(path))


def of(ctx) -> Optional[HostTrace]:
    """The reduced trace of the run ``ctx`` describes, or None."""
    if ctx.device is None:
        return None
    from bench.run import TRACE_DIR
    try:
        path = devtrace.find_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None
    return _read(path, os.stat(path).st_mtime_ns)


def exposed_host_s(trace: HostTrace) -> List[float]:
    """Per complete ``step`` annotation: the device's idle seconds
    inside the step that no sync span covers -- host work the chip
    waited on."""
    syncs = sorted(iv for name in SYNC_SPANS for iv in trace.named(name))
    out = []
    for a, b in trace.named("step"):
        waited = sum(trace.idle_in(s0, s1) for s0, s1 in syncs
                     if s0 >= a and s1 <= b)
        out.append(trace.idle_in(a, b) - waited)
    return out


def idle_by_span(trace: HostTrace,
                 names: Optional[Sequence[str]] = None) -> Dict[str, float]:
    """The window's device idle seconds by the innermost host
    annotation over each part of them (``names``, where given, limits
    which annotations count); ``host`` where none covers it."""
    spans = [s for s in trace.spans if names is None or s[0] in names]
    cuts = sorted({trace.lo, trace.hi} | {t for _, a, b in spans
                                          for t in (a, b)
                                          if trace.lo < t < trace.hi})
    out: Dict[str, float] = {}
    for x0, x1 in zip(cuts, cuts[1:]):
        idle = trace.idle_in(x0, x1)
        if idle <= 0:
            continue
        mid = (x0 + x1) / 2
        inner = min((s for s in spans if s[1] <= mid <= s[2]),
                    key=lambda s: s[2] - s[1], default=None)
        name = inner[0] if inner is not None else "host"
        out[name] = out.get(name, 0.0) + idle
    return out
