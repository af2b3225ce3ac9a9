"""The engine's spans on the device trace: the reduction and the three
readers that use it, by hand on a made-up trace and on a slice recorded
on a TPU v5e, and the program's decode rows against the harness's."""

import gzip
import json
import os
import types

import pytest

from bench.tests.tiny import CPU_PEAK, ROOT, run_module, tiny_cell
from bench.lib import devtrace, hostspans
from bench.lib.registry import cell as find_cell

RECORDED = os.path.join(ROOT, "bench", "tests", "data",
                        "trace_slice_spans.json.gz")
DEV = "/device:TPU:0"
HOST = devtrace.HOST_PLANE
ENGINE_SPANS = ("step", "capacity", "admit", "prefill", "prefill_chunk",
                "prefill_sync", "decode_prepare", "decode_dispatch",
                "decode_sync", "decode_apply", "bench.idle")


def _ev(plane, line, name, start_ms, end_ms):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start_ms * 1e6, "dur_ns": (end_ms - start_ms) * 1e6}


def _host(name, a, b):
    return _ev(HOST, "python3", name, a, b)


def _events():
    return [
        _host(devtrace.WINDOW, 100, 200),
        _host("step", 100, 150),
        _host("prefill_chunk", 102, 110),
        _host("prefill_sync", 110, 120),
        _host("decode_dispatch", 121, 125),
        _host("decode_sync", 125, 148),
        _host("step", 150, 190),
        _host("decode_sync", 160, 185),
        _host("bench.idle", 190, 200),
        _ev(HOST, "pjrt-worker", "decode_sync", 100, 200),  # other thread
        # device: the chunk, two decode loops; one op cut by the window
        _ev(DEV, "XLA Ops", "%fusion.1", 90, 95),
        _ev(DEV, "XLA Ops", "%fusion.2", 105, 118),
        _ev(DEV, "XLA Ops", "%fusion.3", 127, 147),
        _ev(DEV, "XLA Ops", "%fusion.3", 155, 183),
        _ev(DEV, "XLA Modules", "jit_chunk_step(1)", 105, 115),
        _ev(DEV, "XLA Modules", "jit__ctx_write(2)", 115, 116),
        _ev(DEV, "XLA Modules", "jit__scatter_pages(3)", 116, 118),
        _ev(DEV, "XLA Modules", "jit_loop(4)", 127, 147),
        _ev(DEV, "XLA Modules", "jit_loop(4)", 155, 183),
    ]


def _readers():
    c = find_cell("qwen2-0.5b.chat")
    return {m.name: m.module for m in c.per_layer}


def test_reduce_and_split_idle_by_hand():
    t = hostspans.reduce(_events())
    assert (t.lo, t.hi) == (pytest.approx(0.100), pytest.approx(0.200))
    # idle: 100..105, 118..127, 147..155, 183..200
    assert [(pytest.approx(a), pytest.approx(b)) for a, b in t.idle] == \
        [(0.100, 0.105), (0.118, 0.127), (0.147, 0.155), (0.183, 0.200)]
    assert len(t.named("step")) == 2 and len(t.named("decode_sync")) == 2
    assert t.program_runs("loop") == [(pytest.approx(0.127),
                                       pytest.approx(0.147)),
                                      (pytest.approx(0.155),
                                       pytest.approx(0.183))]
    # step 1: 17 ms idle, 2 of it in prefill_sync, 3 in decode_sync;
    # step 2: 12 ms idle, 2 of it in decode_sync
    assert hostspans.exposed_host_s(t) == [pytest.approx(0.012),
                                           pytest.approx(0.010)]
    split = hostspans.idle_by_span(t, ENGINE_SPANS)
    assert split == {"step": pytest.approx(0.015),
                     "prefill_chunk": pytest.approx(0.003),
                     "prefill_sync": pytest.approx(0.002),
                     "decode_dispatch": pytest.approx(0.004),
                     "decode_sync": pytest.approx(0.005),
                     "bench.idle": pytest.approx(0.010)}
    assert sum(split.values()) == pytest.approx(
        sum(b - a for a, b in t.idle))


def test_device_trace_readers_by_hand():
    read = _readers()
    t = hostspans.reduce(_events())
    assert read["engine.exposed_host_ms_per_step"].value(t) == \
        pytest.approx(11.0)
    # chunk step 10 ms + carry write 1 ms + page scatter 2 ms, one chunk
    assert read["model.prefill_ms_per_chunk"].value(t) == pytest.approx(13.0)
    # a chunk-step run without its annotation: the count disagrees
    extra = _events() + [_ev(DEV, "XLA Modules", "jit_chunk_step(1)",
                             150, 152)]
    assert read["model.prefill_ms_per_chunk"].value(
        hostspans.reduce(extra)) is None
    # a trace of the program before it wrote its spans: silent
    bare = [e for e in _events() if e["plane"] != HOST
            or e["name"] == devtrace.WINDOW]
    t = hostspans.reduce(bare)
    assert read["engine.exposed_host_ms_per_step"].value(t) is None
    assert read["model.prefill_ms_per_chunk"].value(t) is None
    assert hostspans.reduce([_host(devtrace.WINDOW, 0, 1)]) is None


def test_recorded_tpu_slice_with_engine_spans():
    """Half a second of a qwen2-0.5b chat window on one TPU v5e (16 rows
    a dispatch, chunks of 512), traced with the engine's annotations:
    two steps, each with one prefill chunk that completes its prompt
    and one decode-loop run."""
    with gzip.open(RECORDED, "rt") as f:
        events = json.load(f)
    t = hostspans.reduce(events)
    assert t.hi - t.lo == pytest.approx(0.5121, abs=1e-3)
    steps = t.named("step")
    assert len(steps) == 2
    for name in ("capacity", "admit", "prefill", "prefill_chunk",
                 "prefill_sync", "decode_prepare", "decode_dispatch",
                 "decode_sync", "decode_apply"):
        inside = [(s0, s1) for s0, s1 in t.named(name)
                  if any(a <= s0 and s1 <= b for a, b in steps)]
        assert len(inside) == 2, name
    assert len(t.program_runs("chunk_step")) == 2
    assert len(t.program_runs("loop")) == 2
    # the engine's spans name all but a few percent of the idle time
    idle = sum(b - a for a, b in t.idle)
    split = hostspans.idle_by_span(t, ENGINE_SPANS)
    assert sum(split.values()) == pytest.approx(idle)
    assert split.get("host", 0.0) + split.get("step", 0.0) < 0.05 * idle
    read = _readers()
    assert read["engine.exposed_host_ms_per_step"].value(t) == \
        pytest.approx(5.860, abs=0.01)
    assert read["model.prefill_ms_per_chunk"].value(t) == \
        pytest.approx(7.959, abs=0.01)
    # the existing reduction reads the same slice as before
    r = devtrace.reduce(events)
    assert r.program_seconds("loop") == [pytest.approx(0.2376, abs=1e-3)] * 2


def _ctx(spans, events, rids, device=None):
    from bench.lib.layers import Context
    c = find_cell("qwen2-0.5b.chat")
    recs = [types.SimpleNamespace(req=types.SimpleNamespace(rid=r))
            for r in rids]
    return Context(model=c.model, peak=CPU_PEAK,
                   engine=c.traffic["engine"], window_s=1.0, requests=recs,
                   steps=[], spans=spans, events=events, prompt_len={},
                   device=device, compiles_in_window=0)


def test_prefill_stall_by_hand():
    read = _readers()["scheduler.prefill_stall_p95_ms"].read
    spans = {"prefill_chunk": [(1.0, 0.1), (2.0, 0.1), (3.0, 0.2),
                               (4.0, 0.1)]}
    events = {"ADMIT": [(0.5, 1, {}), (2.5, 2, {}), (3.9, 3, {})],
              "PREFILL_CHUNK": [(1.05, 1, {}), (2.05, 1, {}), (3.1, 2, {}),
                                (4.05, 3, {})],
              "PREFILL_COMPLETE": [(2.2, 1, {}), (3.3, 2, {})]}
    # rid 1: 0.5 -> 2.1; rid 2: 2.5 -> 3.2; rid 3 never completed
    stalls = [1.6, 0.7]
    want = 1e3 * (min(stalls) + 0.95 * (max(stalls) - min(stalls)))
    assert read(_ctx(spans, events, [1, 2, 3])) == pytest.approx(want)
    # requests outside the window's schedule do not count
    assert read(_ctx(spans, events, [2])) == pytest.approx(700.0)
    # a program that writes no prefill_chunk span: silent
    assert read(_ctx({"step": [(0.0, 1.0)]}, events, [1, 2])) is None


def test_readers_are_silent_without_a_trace():
    read = _readers()
    ctx = _ctx({}, {}, [], device=None)
    for name in ("engine.exposed_host_ms_per_step",
                 "model.prefill_ms_per_chunk",
                 "scheduler.prefill_stall_p95_ms"):
        assert read[name].read(ctx) is None


def test_readers_name_the_programs_the_engine_names():
    from repro.serve.engine import PROGRAM_NAMES
    read = _readers()
    assert read["model.prefill_ms_per_chunk"].PROGRAMS == tuple(
        PROGRAM_NAMES[r] for r in ("chunk_step", "carry_write",
                                   "page_scatter"))
    assert read["decode_loop_roofline"].PROGRAM == \
        read["model.decode_mfu"].PROGRAM == PROGRAM_NAMES["decode_loop"]
    assert read["chunk_step_roofline"].PROGRAM == PROGRAM_NAMES["chunk_step"]


def test_program_decode_rows_match_the_harness():
    """``DECODE_DISPATCH positions`` with ``DECODE_SYNC tokens`` give,
    dispatch by dispatch, the (position, tokens) rows the harness finds
    by diffing each request's tokens around ``engine.step``."""
    from bench.lib import check, traffic
    run = run_module()
    cell = tiny_cell()
    cfg = run.program_config(cell)
    seed = 2200000777
    engine = run.build_engine(cell, cfg,
                              run.weight_maker(cell)(check.seed_key(seed)),
                              trace=True)
    sched = traffic.schedule(cell.traffic, seed, 1.0, cfg.vocab)
    never = run.Profiler(at=float("inf"), length=0.0)
    _, steps, _, _ = run.drive(engine, sched, 1.0, 60.0, never)
    rec = engine.trace
    disp = rec.events(kind="DECODE_DISPATCH")
    sync = rec.events(kind="DECODE_SYNC")
    program = [sorted(zip(d["args"]["positions"], s["args"]["tokens"]))
               for d, s in zip(disp, sync)]
    harness = [sorted(s.decode) for s in steps if s.decode]
    assert len(disp) == len(sync) > 0
    assert program == harness
