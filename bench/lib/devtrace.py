"""Reduce a JAX profiler trace to device busy time, program times, the
heaviest device operations and the longest idle gaps.

Two stages, so the second can be checked on a small recorded trace:

``load_events``   the ``.xplane.pb`` -> a flat list of events
                  ``{"plane", "line", "name", "start_ns", "dur_ns"}``;
``reduce``        that list -> ``Reduced`` for one traced window.

The window is the host annotation ``WINDOW`` that the harness opens
around the traced steps; device events are clipped to it.  Device
planes are ``/device:<KIND>:<n>``; their ``XLA Ops`` line holds one
event per operation and their ``XLA Modules`` line one per program
execution (a jitted function ``f`` runs as module ``jit_f``).  Idle
gaps are named by the innermost host event of the harness's own thread
(the line that holds ``WINDOW``: its annotations and the engine's)
that covers their midpoint, or ``host`` where none does.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["WINDOW", "Reduced", "load_events", "find_xplane", "reduce",
           "union_length"]

WINDOW = "bench.traced_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_events(path: str) -> List[dict]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        if not (device or plane.name == HOST_PLANE):
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if not device and e.duration_ns <= 0:
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": _short(e.name),
                            "start_ns": float(e.start_ns),
                            "dur_ns": float(e.duration_ns)})
    return out


def _short(name: str) -> str:
    """An XLA op's event name is its whole HLO instruction; keep the
    instruction's name (``%fusion.12 = f32[..] fusion(..)`` ->
    ``%fusion.12``)."""
    return name.split(" = ", 1)[0]


def union_length(intervals: Sequence[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def _gaps(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    gaps, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        gaps.append((cur, hi))
    return gaps


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # union of op intervals, per chip
    n_devices: int
    modules: Dict[str, List[float]]     # program -> complete run seconds
    device_ops: List[Tuple[str, float]]  # heaviest leaf ops, seconds
    idle_gaps: List[Tuple[str, float]]   # longest gaps, seconds

    def program_seconds(self, name: str) -> Optional[List[float]]:
        """Durations of the complete executions of jitted ``name``."""
        return self.modules.get(name) or self.modules.get("jit_" + name)


def reduce(events: List[dict], top: int = 10) -> Optional[Reduced]:
    """Reduce one traced window; None where the trace holds no window
    or no device operation in it."""
    wins = [e for e in events if e["plane"] == HOST_PLANE
            and e["name"] == WINDOW]
    if not wins:
        return None
    w = max(wins, key=lambda e: e["dur_ns"])
    lo, hi = w["start_ns"], w["start_ns"] + w["dur_ns"]
    devices = sorted({e["plane"] for e in events
                      if e["plane"].startswith("/device:")})
    busy, ops, modules, all_ops = 0.0, {}, {}, []
    parents = _parents([e for e in events if e["line"] == OPS_LINE])
    for dev in devices:
        iv = []
        for e in events:
            if e["plane"] != dev:
                continue
            a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
            if e["line"] == MODULES_LINE:
                if a >= lo and b <= hi:
                    modules.setdefault(_module_name(e["name"]), []).append(
                        e["dur_ns"] * 1e-9)
                continue
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            iv.append((a, b))
            ops[e["name"]] = ops.get(e["name"], 0.0) + (b - a) * 1e-9
        busy += union_length(iv)
        all_ops.extend(iv)
    if not all_ops:
        return None
    n = len(devices)
    host = [e for e in events if e["plane"] == HOST_PLANE
            and e["line"] == w["line"] and e["name"] != WINDOW
            and e["start_ns"] < hi and e["start_ns"] + e["dur_ns"] > lo]
    gaps = sorted(_gaps(all_ops, lo, hi), key=lambda g: g[0] - g[1])[:top]
    named = [(_host_at(host, (a + b) / 2), (b - a) * 1e-9) for a, b in gaps]
    heavy = sorted(((k, v) for k, v in ops.items() if k not in parents),
                   key=lambda kv: -kv[1])[:top]
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n,
                   n_devices=n, modules=modules,
                   device_ops=[(k, v / n) for k, v in heavy],
                   idle_gaps=named)


def _parents(ops: List[dict]) -> set:
    """Names of ops that enclose other ops (a while loop around its
    body's fusions): the heaviest-op list shows the leaves."""
    out, stack = set(), []
    for e in sorted(ops, key=lambda e: (e["plane"], e["start_ns"],
                                        -e["dur_ns"])):
        end = e["start_ns"] + e["dur_ns"]
        while stack and (stack[-1][0] != e["plane"]
                         or stack[-1][1] <= e["start_ns"]):
            stack.pop()
        if stack and end <= stack[-1][1]:
            out.add(stack[-1][2])
        stack.append((e["plane"], end, e["name"]))
    return out


def _module_name(name: str) -> str:
    """``jit_loop(12345)`` -> ``jit_loop``."""
    return name.split("(")[0]


def _host_at(host: List[dict], t: float) -> str:
    best = None
    for e in host:
        if e["start_ns"] <= t <= e["start_ns"] + e["dur_ns"]:
            if best is None or e["dur_ns"] < best["dur_ns"]:
                best = e
    return best["name"] if best is not None else "host"
