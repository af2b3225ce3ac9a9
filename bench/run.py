#!/usr/bin/env python3
"""One benchmark run: one cell, one seed, one measured window.

  python3 bench/run.py --workload qwen2-0.5b.chat --seed 7 --seconds 30 \
      --trace 0

The cell (``BENCHMARK.json``) names a configuration and a traffic mix.
Set-up makes the configuration's weights on the device from ``--seed``
and packs them with the program (``zoo.pack_params`` under
``PrecisionPolicy.paper_mixed()``) in one jitted call, builds the
program's ``ContinuousEngine`` with the mix's engine settings, and
serves one request of every prompt shape the mix can produce, so that
every program the window runs is compiled (or loaded from the
persistent cache) before it opens.

The window is an open loop: every request of the seeded schedule is
submitted when it is due, the engine steps while it has work, and the
harness sleeps to the next due time when it has none.  Each request is
timed from when it was due.  After ``--seconds`` nothing new arrives;
the requests due in the window drain under the mix's ``drain_s`` and
any still unfinished then counts as failed.

``--trace 0`` prints the cell's end-to-end metrics.  ``--trace 1`` is
a run of its own with the program's span recorder and profiler
annotations on and the window's last seconds under the JAX profiler;
it prints the per-layer metrics, the device's busy and window seconds
and a breakdown of device time and idle gaps.

Every run then checks what the window served against the
configuration's plain reference (``bench/lib/check.py``) and prints the
numbers compared beside their limits, last on stderr and last in the
result line.  The last line of stdout is one JSON object.

Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero before any work and prints no result.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".bench_cache", "trace")
TRACE_SECONDS = 4.0       # profiled stretch at the end of the window
WINDOW_EVENT = "BENCH_WINDOW_OPEN"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# compile accounting and the persistent cache
# ---------------------------------------------------------------------------

class CompileClock:
    """Counts programs compiled and programs loaded from the persistent
    cache, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.compiled = 0
        self.loaded = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1
            self.compile_s += secs


def use_compile_cache() -> None:
    """Persist every program, whatever it took to compile, in a fixed
    directory inside the checkout."""
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def program_config(cell):
    """The program's ModelConfig for the cell: the registered
    architecture the configuration file names, with the file's sizes,
    RoPE and biases put in."""
    from repro.configs import get_config
    base = get_config(cell.config["program_arch"])
    cfg = cell.reference.program_config(cell.config, base)
    eng = cell.traffic["engine"]
    return dataclasses.replace(cfg, decode_impl=eng["decode_impl"])


def weight_maker(cell):
    """jit: seed key -> the packed weights the engine serves (master
    weights made and packed in one program; never on the host)."""
    import jax
    from repro.core.policy import PrecisionPolicy
    from repro.models import zoo
    policy = getattr(PrecisionPolicy, cell.config["precision"].get(
        "policy", "paper_mixed"))()
    ref, conf = cell.reference, cell.config
    return jax.jit(lambda key: zoo.pack_params(ref.init_weights(key, conf),
                                               policy))


def build_engine(cell, cfg, packed, trace: bool):
    from repro.obs import TraceRecorder
    from repro.serve.engine import ContinuousEngine
    e = cell.traffic["engine"]
    rec = TraceRecorder(capacity=1 << 21) if trace else None
    return ContinuousEngine(
        cfg, packed, n_pages=e["n_pages"], page_size=e["page_size"],
        max_batch=e["max_batch"], max_len=e["max_len"], policy=None,
        prefill_chunk_tokens=e["prefill_chunk_tokens"],
        prefill_context=e["prefill_context"],
        decode_steps=e["decode_steps"],
        n_state_slabs=e.get("n_state_slabs"),
        trace=rec, profile_annotations=trace)


def warm_lengths(family: str, e: Dict[str, Any], lo: int,
                 hi: int) -> List[int]:
    """One prompt length per program shape the mix's range can make.

    Attention families prefill against a carry preallocated at the
    prompt's page-rounded width (one chunk program per width, the first
    chunk at width 0) and scatter 1-4 pages per final chunk; recurrent
    families run unpadded chunks, one program per remainder width."""
    page, chunk = e["page_size"], e["prefill_chunk_tokens"]
    keys: Dict[Any, int] = {}
    for ln in range(lo, hi + 1):
        last = ln - chunk * ((ln - 1) // chunk)
        if family in ("dense", "moe"):
            width = 0 if ln <= chunk else -(-ln // page) * page
            key = (width, -(-last // page))
        else:
            key = (min(ln, chunk), last)
        keys.setdefault(key, ln)
    return sorted(keys.values())


def warm_up(engine, cell, vocab: int) -> int:
    """Serve one request of every shape, one at a time (a lone request
    prefills in steps that dispatch no decode); returns how many."""
    import numpy as np
    t = cell.traffic
    lens = warm_lengths(cell.config["family"], t["engine"],
                        int(t["prompt_tokens"]["min"]),
                        int(t["prompt_tokens"]["max"]))
    rng = np.random.default_rng(0)
    out = t["engine"]["decode_steps"] + 1
    for n in lens:
        engine.submit(rng.integers(0, vocab, n).astype(np.int32), out)
        engine.run()
    engine.reset_counters()
    if engine.trace is not None:
        engine.trace.clear()          # the window's records only
    return len(lens)


# ---------------------------------------------------------------------------
# the open-loop window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Rec:
    """One request of the schedule, on the harness's clock (seconds
    after the window opened)."""

    due: float
    prompt: Any
    out_len: int
    req: Any = None          # the engine's Request
    submit: Optional[float] = None
    first: Optional[float] = None
    last: Optional[float] = None
    n: int = 0

    @property
    def finished(self) -> bool:
        return self.last is not None


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    decode: List[Any]        # (position at dispatch, tokens decoded)
    traced: bool = False


class Profiler:
    """Starts and stops the JAX profiler at step boundaries around
    ``[at, at + length)`` of the window, inside a host annotation that
    marks the traced window for the reduction."""

    def __init__(self, at: float, length: float):
        self.at, self.length = at, length
        self.span = None
        self.t_start = self.t_stop = None

    @property
    def active(self) -> bool:
        return self.span is not None

    def tick(self, t: float) -> None:
        import jax
        from bench.lib.devtrace import WINDOW
        if self.t_start is None and t >= self.at:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # annotations, not every call
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation(WINDOW)
            self.span.__enter__()
            self.t_start = t
        elif self.active and t >= self.at + self.length:
            self.stop(t)

    def stop(self, t: float) -> None:
        import jax
        if self.active:
            self.span.__exit__(None, None, None)
            self.span = None
            jax.profiler.stop_trace()
            self.t_stop = t


def drive(engine, schedule, seconds: float, drain_s: float,
          profiler: Optional[Profiler] = None):
    """Serve ``schedule`` open-loop.  Returns (records, steps, tokens
    delivered inside the window, perf_counter of the window's start)."""
    import jax
    recs = [Rec(r.due, r.prompt, r.out_len) for r in schedule]
    sched = engine.scheduler
    tracing = profiler is not None
    ann = (lambda name: jax.profiler.TraceAnnotation(name)) if tracing \
        else (lambda name: contextlib.nullcontext())
    steps: List[Step] = []
    live: List[Rec] = []
    delivered, nxt, end = 0, 0, seconds + drain_s
    t0 = time.perf_counter()
    if engine.trace is not None:       # the recorder's clock at t0
        engine.trace.event(WINDOW_EVENT)

    def now() -> float:
        return time.perf_counter() - t0

    while True:
        t = now()
        while nxt < len(recs) and recs[nxt].due <= t:
            r = recs[nxt]
            engine.submit(r.prompt, r.out_len)
            r.req = sched.waiting[-1]
            r.submit = now()
            live.append(r)
            nxt += 1
        if tracing:
            profiler.tick(t)
        if not sched.has_work:
            if nxt >= len(recs):
                break
            with ann("bench.idle"):
                time.sleep(max(0.0, recs[nxt].due - now()))
            continue
        if t > end:
            break
        before = [len(r.req.generated) for r in live] if tracing else None
        with ann("bench.step"):
            engine.step()
        t1 = now()
        if tracing:
            rows = []
            for r, g0 in zip(live, before):
                g1 = len(r.req.generated)
                first = 1 if (g0 == 0 and g1 > 0) else 0
                if g1 - g0 - first > 0:
                    rows.append((len(r.prompt) + g0 + first - 1,
                                 g1 - g0 - first))
            steps.append(Step(t, t1, rows, profiler.active))
        still = []
        for r in live:
            n = len(r.req.generated)
            if n > r.n:
                if r.first is None:
                    r.first = t1
                if t1 <= seconds:
                    delivered += n - r.n
                r.n = n
            if n >= r.out_len:
                r.last = t1
            else:
                still.append(r)
        live = still
    if tracing:
        profiler.stop(now())
    return recs, steps, delivered, t0


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def end_to_end(recs: List[Rec], delivered: int, seconds: float,
               drain_s: float, setup_s: float) -> Dict[str, float]:
    from bench.lib.stats import p95_with_failures
    worst = seconds + drain_s          # ranks after every finished one
    ttft = [(r.first - r.due) if r.finished else None for r in recs]
    tpot = [((r.last - r.first) / (r.out_len - 1)) if r.finished else None
            for r in recs if r.out_len >= 2]
    return {"output_tok_s": delivered / seconds,
            "ttft_p95_ms": 1e3 * p95_with_failures(ttft, worst),
            "tpot_p95_ms": 1e3 * p95_with_failures(tpot, worst),
            "setup_s": setup_s}


def layer_context(cell, cfg, engine, recs, steps, seconds, reduced, peak,
                  trace_growth):
    from bench.lib.layers import Context
    rec = engine.trace
    offset = -rec.events(WINDOW_EVENT)[0]["ts"]   # recorder -> window clock
    spans: Dict[str, List] = {}
    events: Dict[str, List] = {}
    for e in rec.events():
        t = e["ts"] + offset
        if e["ph"] == "X":
            spans.setdefault(e["kind"], []).append((t, e["dur"]))
        else:
            events.setdefault(e["kind"], []).append((t, e["rid"], e["args"]))
    prompts = {r.req.rid: len(r.prompt) for r in recs if r.req is not None}
    return Context(model=cell.model, peak=peak,
                   engine=dict(cell.traffic["engine"]), window_s=seconds,
                   requests=recs, steps=steps, spans=spans, events=events,
                   prompt_len=prompts, device=reduced,
                   compiles_in_window=trace_growth)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info(devices) -> Dict[str, Any]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def correctness(cell, seed: int, recs: List[Rec], control: bool = False):
    """Reference over a seeded sample of finished requests."""
    import jax
    import numpy as np
    from bench.lib import check
    t = cell.traffic
    finished = [(np.asarray(r.prompt), np.asarray(r.req.generated[:r.out_len],
                                                  np.int32))
                for r in recs if r.finished]
    pick = check.pick_sample(finished, seed, t["check"]["min_served_tokens"],
                             t["check"]["max_requests"])
    if not pick:
        return {"token_gap_max": None, "token_gap_mean": None, "tokens": 0,
                "argmax_share": None}
    ref, conf = cell.reference, cell.config
    t0 = time.perf_counter()
    master = jax.jit(lambda k: ref.init_weights(k, conf))(
        check.seed_key(seed))
    weights = jax.jit(lambda m: ref.stored_weights(m, conf))(master)
    del master
    res = check.token_gaps(ref, conf, weights, [finished[i] for i in pick],
                           seq_len=t["engine"]["max_len"],
                           max_out=int(t["output_tokens"]["max"]),
                           control=control)
    res.update(requests=len(pick), seconds=time.perf_counter() - t0)
    log(f"reference: {len(pick)} requests, {res['tokens']} served tokens, "
        f"{res['seconds']:.1f} s")
    return res


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             control: bool = False,
             peak: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """One run.  ``control``: the control's numbers take the program's
    place in the comparison (a test of the comparison, never a run of
    the benchmark)."""
    import jax
    from bench.lib import check, traffic
    from bench.lib.costs import peaks
    clock = CompileClock()
    cfg = program_config(cell)
    key = check.seed_key(seed)
    packed = weight_maker(cell)(key)
    jax.block_until_ready(packed)
    engine = build_engine(cell, cfg, packed, trace)
    n_warm = warm_up(engine, cell, cfg.vocab)
    t = cell.traffic
    drain_s = float(t["drain_s"])
    sched = traffic.schedule(t, seed, seconds, cfg.vocab)
    log(f"set-up: {clock.compiled} programs compiled "
        f"({clock.compile_s:.1f} s), {clock.loaded} loaded from the cache; "
        f"{n_warm} warm-up requests; {len(sched)} requests due in "
        f"{seconds:g} s")
    gc.collect()
    gc.freeze()      # set-up's objects stay out of the window's collections
    counts0 = dict(engine.trace_counts)
    compiled0 = clock.compiled + clock.loaded
    profiler = None
    if trace:
        # the last seconds of the arrivals: stopping the profiler blocks
        # the loop while it writes the trace (seconds), which must not
        # hold back requests that are still to arrive
        length = min(TRACE_SECONDS, seconds)
        profiler = Profiler(seconds - length, length)
    recs, steps, delivered, t0 = drive(engine, sched, seconds, drain_s,
                                       profiler)
    setup_s = t0 - PROCESS_START
    in_window = clock.compiled + clock.loaded - compiled0
    growth = sum(engine.trace_counts.values()) - sum(counts0.values())
    late = sorted(r.submit - r.due for r in recs if r.submit is not None)
    failed = sum(1 for r in recs if not r.finished)
    log(f"window: {len(recs)} requests, {failed} failed, {delivered} tokens "
        f"in {seconds:g} s; {in_window} programs compiled or loaded inside "
        f"it; generator late p95 {1e3 * late[int(0.95 * (len(late) - 1))]:.2f}"
        f" ms, max {1e3 * late[-1]:.2f} ms")
    dev = device_info(devices)
    dev["memory_peak_bytes"] = memory_peak(devices)
    out: Dict[str, Any] = {"attempted": len(recs), "failed": failed}
    if trace:
        from bench.lib import devtrace
        reduced = None
        try:
            reduced = devtrace.reduce(devtrace.load_events(
                devtrace.find_xplane(TRACE_DIR)))
        except FileNotFoundError as e:
            log(f"trace: {e}")
        ctx = layer_context(cell, cfg, engine, recs, steps, seconds, reduced,
                            peak or peaks(dev["kind"]), growth)
        metrics = {}
        for m in cell.per_layer:
            v = m.read(ctx)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.entry["unit"]}
        out["metrics"] = metrics
        if reduced is not None:
            dev["busy_s"] = reduced.busy_s
            dev["window_s"] = reduced.window_s
            out["breakdown"] = {
                "device_ops": [[k, v] for k, v in reduced.device_ops],
                "idle_gaps": [[k, v] for k, v in reduced.idle_gaps]}
    else:
        e2e = end_to_end(recs, delivered, seconds, drain_s, setup_s)
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    out["device"] = dev
    out["load"] = {"requests": len(recs), "compiled_in_window": in_window,
                   "late_p95_ms": 1e3 * late[int(0.95 * (len(late) - 1))],
                   "late_max_ms": 1e3 * late[-1]}
    # the program's state goes before the reference runs
    del engine, packed
    gc.unfreeze()
    gc.collect()
    res = correctness(cell, seed, recs, control)
    compared = res.pop("control", {}) if control else res
    out["checks"], out["correct"] = check.verdict(compared,
                                                  cell.config["check"])
    out["reference"] = {k: v for k, v in res.items()
                        if k not in out["checks"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    log(f"device: platform {devices[0].platform}, kind "
        f"{devices[0].device_kind}, count {len(devices)}")
    from bench.lib.registry import cell as find_cell
    c = find_cell(args.workload)
    if devices[0].platform != "tpu":
        log("bench/run.py measures a TPU; JAX found none")
        return 2
    if len(devices) < c.chips:
        log(f"{args.workload} needs {c.chips} chips, found {len(devices)}")
        return 2
    devices = devices[:c.chips]
    use_compile_cache()
    out = run_cell(c, args.seed, args.seconds, bool(args.trace), devices)
    for name, chk in out["checks"].items():
        log(f"check {name}: {chk['value']} (limit {chk['limit']})")
    print(json.dumps(result_line(out)), flush=True)
    return 0


def result_line(out: Dict[str, Any]) -> Dict[str, Any]:
    """The last line's object: the driver's keys first, the numbers
    compared (``checks``) last."""
    line = {k: out[k] for k in ("correct", "attempted", "failed", "metrics",
                                "device")}
    for k in ("breakdown", "load", "reference", "checks"):
        if k in out:
            line[k] = out[k]
    return line


if __name__ == "__main__":
    sys.exit(main())
