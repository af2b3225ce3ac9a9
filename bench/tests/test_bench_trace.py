"""The trace reduction: hand-computed on a made-up trace, checked on a
slice recorded on a TPU v5e, and the loader on a CPU trace."""

import gzip
import json
import os

import pytest

from bench.tests.tiny import ROOT
from bench.lib import devtrace

RECORDED = os.path.join(ROOT, "bench", "tests", "data", "trace_slice.json.gz")
DEV = "/device:TPU:0"


def _ev(plane, line, name, start_ms, dur_ms):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start_ms * 1e6, "dur_ns": dur_ms * 1e6}


def test_reduce_by_hand():
    host = devtrace.HOST_PLANE
    events = [
        _ev(host, "python3", devtrace.WINDOW, 100, 100),      # 100..200
        _ev(host, "python3", "bench.step", 100, 60),
        _ev(host, "python3", "decode_dispatch", 110, 5),
        _ev(host, "python3", "bench.idle", 170, 30),
        _ev(host, "pjrt-worker", "tpu::Execute", 125, 20),    # other thread
        # ops: 90..120 (clipped to 100..120), 115..130 overlaps, 140..160
        _ev(DEV, "XLA Ops", "%fusion.1", 90, 30),
        _ev(DEV, "XLA Ops", "%fusion.2", 115, 15),
        _ev(DEV, "XLA Ops", "%fusion.1", 140, 20),
        # one module run inside the window, one cut by its start
        _ev(DEV, "XLA Modules", "jit_loop(7)", 140, 20),
        _ev(DEV, "XLA Modules", "jit_chunk_step(3)", 90, 40),
    ]
    r = devtrace.reduce(events)
    assert r.window_s == pytest.approx(0.100)
    # busy: 100..130 and 140..160 = 50 ms
    assert r.busy_s == pytest.approx(0.050)
    assert r.program_seconds("loop") == [pytest.approx(0.020)]
    assert r.program_seconds("chunk_step") is None
    assert r.device_ops[0] == ("%fusion.1", pytest.approx(0.040))
    assert r.device_ops[1] == ("%fusion.2", pytest.approx(0.015))
    # gaps: 160..200 (40 ms, idle sleep) and 130..140 (10 ms, in the
    # step; the other thread's event covers it too but does not name it)
    assert r.idle_gaps == [("bench.idle", pytest.approx(0.040)),
                           ("bench.step", pytest.approx(0.010))]


def test_reduce_without_window_or_device_is_none():
    host = devtrace.HOST_PLANE
    assert devtrace.reduce([_ev(DEV, "XLA Ops", "f", 0, 1)]) is None
    assert devtrace.reduce([_ev(host, "p", devtrace.WINDOW, 0, 1)]) is None


def test_recorded_tpu_slice():
    """Half a second of a qwen2-0.5b chat window on one TPU v5e (64 rows
    a dispatch): one whole decode-loop run and two chunk steps."""
    with gzip.open(RECORDED, "rt") as f:
        events = json.load(f)
    r = devtrace.reduce(events)
    assert r is not None and r.n_devices == 1
    assert r.window_s == pytest.approx(0.5036, abs=1e-3)
    assert 0.9 * r.window_s < r.busy_s <= r.window_s
    loop = r.program_seconds("loop")
    assert len(loop) == 1 and loop[0] == pytest.approx(0.4636, abs=1e-3)
    assert len(r.program_seconds("chunk_step")) == 2
    # the heaviest leaf ops are the KV-block dequantizations inside the
    # paged decode attention, not the while loops around them
    assert r.device_ops[0][0].startswith("%fusion")
    assert not any(k.startswith("%while") for k, _ in r.device_ops)
    assert len(r.idle_gaps) <= 10 and len(r.device_ops) <= 10


class _Device:
    """A reduced trace that holds ``runs`` of the decode-loop program."""

    def __init__(self, runs):
        self.runs = runs

    def program_seconds(self, name):
        return self.runs if name == "loop" else None


def test_decode_mfu_reads_the_loop_program_time():
    """Decode FLOPs over the decode-loop program's device time: below
    the loop's roofline share, and silent where the trace's runs and the
    traced dispatches disagree in number or there is no trace."""
    from bench.lib.layers import Context
    from bench.lib.registry import cell as find_cell
    from bench.tests.tiny import CPU_PEAK, run_module
    c = find_cell("qwen2-0.5b.chat")
    read = {m.name: m.read for m in c.per_layer}
    Step = run_module().Step
    steps = [Step(0.0, 0.1, [(600, 4), (900, 4)], traced=True),
             Step(0.1, 0.2, [], traced=True),
             Step(0.2, 0.3, [(700, 2)], traced=False)]
    model = c.model

    def ctx(device):
        return Context(model=model, peak=CPU_PEAK, engine=c.traffic["engine"],
                       window_s=1.0, requests=[], steps=steps, spans={},
                       events={}, prompt_len={}, device=device,
                       compiles_in_window=0)

    flops = model.decode_dispatch_flops([600, 900], [4, 4])
    mfu = read["model.decode_mfu"](ctx(_Device([0.05])))
    assert mfu == pytest.approx(
        100 * flops / (0.05 * CPU_PEAK["bf16_flops_per_s"]))
    assert 0 < mfu <= read["decode_loop_roofline"](ctx(_Device([0.05])))
    assert read["model.decode_mfu"](ctx(_Device([0.05, 0.05]))) is None
    assert read["model.decode_mfu"](ctx(None)) is None


def test_load_events_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = devtrace.load_events(devtrace.find_xplane(str(tmp_path)))
    names = {e["name"] for e in events}
    assert {devtrace.WINDOW, "bench.step"} <= names
    # the CPU has no device plane: nothing to reduce
    assert devtrace.reduce(events) is None
