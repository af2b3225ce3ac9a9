"""Serving-plane telemetry (PR 8): registry, descriptors, tracing, SLOs.

The pinned contracts:

- legacy ``_COUNTERS`` attributes ARE registry counters (bind_counters
  descriptors): attribute writes and registry reads agree always;
- the trace's per-kind counts / arg-sums are eviction-proof, so
  closed-form tie-outs hold regardless of ring pressure;
- a disabled recorder records NOTHING across a full traffic burst;
- tracing never touches device math: temperature-0 output is bitwise
  identical with the recorder on or off;
- the Chrome-trace export is schema-valid (Perfetto-loadable).
"""

import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.obs import (
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    NULL_RECORDER,
    SPAN_KINDS,
    TraceRecorder,
    bind_counters,
    pctl_ms,
    percentiles,
    summarize,
    validate_chrome_trace,
)
from repro.serve import ContinuousEngine

CFG = get_config("qwen2-0.5b").reduced()
RNG = np.random.default_rng(21)
PARAMS = T.lm_init(jax.random.PRNGKey(0), CFG)


def _reqs(spec):
    return [(RNG.integers(0, CFG.vocab, (ln,)).astype(np.int32), gn)
            for ln, gn in spec]


# ---------------------------------------------------------------------------
# metric primitives
# ---------------------------------------------------------------------------

def test_counter_and_gauge_basics():
    reg = MetricRegistry()
    c = reg.counter("a/b")
    c.inc()
    c.inc(3)
    assert reg.value("a/b") == 4
    c.reset()
    assert c.value == 0

    g = reg.gauge("a/g")
    g.set(2.5)
    assert reg.value("a/g") == 2.5
    g.reset()
    assert g.value == 0

    live = reg.gauge("a/live", fn=lambda: 7)
    assert live.value == 7
    with pytest.raises(ValueError):
        live.set(1)
    reg.reset()                       # fn-gauges survive reset (live)
    assert reg.value("a/live") == 7


def test_registry_kind_mismatch_raises():
    reg = MetricRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    with pytest.raises(TypeError):
        reg.histogram("x")
    assert "x" in reg and reg.names() == ["x"]


def test_histogram_log_buckets():
    h = Histogram("h", lo=1e-3, hi=1e3, per_decade=8)
    vals = [0.5, 1.0, 2.0, 4.0, 8.0, 16.0]
    for v in vals:
        h.observe(v)
    # one log bucket spans 10^(1/8) ~ 1.33x: every percentile is within
    # one bucket width of the exact answer, and clamped to [min, max]
    for q in (50, 95, 99):
        exact = float(np.percentile(vals, q))
        assert h.percentile(q) <= exact * 10 ** (1 / 8) * 1.001
        assert min(vals) <= h.percentile(q) <= max(vals)
    snap = h.snapshot()
    assert snap["count"] == len(vals)
    assert snap["sum"] == pytest.approx(sum(vals))
    assert snap["min"] == 0.5 and snap["max"] == 16.0
    h.observe(1e-9)                   # underflow bucket, not a crash
    assert h.count == len(vals) + 1 and h.vmin == 1e-9
    h.reset()
    assert h.snapshot() == {"count": 0} and h.percentile(50) == 0.0


def test_prometheus_text_snapshot():
    reg = MetricRegistry()
    reg.counter("engine/steps_run").inc(5)
    reg.gauge("pool/utilization", fn=lambda: 0.25)
    reg.histogram("span/step").observe(1.5)
    text = reg.prometheus_text()
    assert "# TYPE repro_engine_steps_run counter" in text
    assert "repro_engine_steps_run 5" in text
    assert "repro_pool_utilization 0.25" in text
    assert "# TYPE repro_span_step summary" in text
    assert "repro_span_step_count 1" in text


def test_bind_counters_descriptor_roundtrip():
    class Legacy:
        _COUNTERS = ("hits", "bytes_moved")

        def __init__(self, reg):
            bind_counters(self, reg, "legacy")

    r1, r2 = MetricRegistry(), MetricRegistry()
    a, b = Legacy(r1), Legacy(r2)
    a.hits += 1
    a.hits += 1
    a.bytes_moved += 128
    b.hits += 5
    # attribute reads, registry reads and instances stay coherent
    assert a.hits == 2 and r1.value("legacy/hits") == 2
    assert a.bytes_moved == 128 and r1.value("legacy/bytes_moved") == 128
    assert b.hits == 5 and r2.value("legacy/hits") == 5
    # the legacy reset idiom writes through the descriptor too
    for c in Legacy._COUNTERS:
        setattr(a, c, 0)
    assert a.hits == 0 and r1.value("legacy/hits") == 0
    assert b.hits == 5                # other instance untouched
    # re-binding is idempotent and zeroes the counters
    bind_counters(b, r2, "legacy")
    assert b.hits == 0


def test_stats_helpers_match_numpy():
    vals = [0.004, 0.001, 0.010, 0.007]
    assert pctl_ms(vals, 50) == pytest.approx(
        float(np.percentile(vals, 50) * 1e3))
    p = percentiles(vals)
    assert set(p) == {"p50", "p95", "p99"}
    assert p["p99"] == pytest.approx(float(np.percentile(vals, 99)))
    s = summarize(vals)
    assert s["n"] == 4 and s["min"] == 0.001 and s["max"] == 0.010
    assert summarize([]) == {"n": 0}


# ---------------------------------------------------------------------------
# trace recorder
# ---------------------------------------------------------------------------

def test_ring_eviction_keeps_counts_exact():
    rec = TraceRecorder(capacity=4)
    for i in range(10):
        rec.event("HANDOFF", rid=i, pages=2, bytes=100)
    assert len(rec) == 4              # ring evicted under pressure...
    assert rec.dropped == 6
    assert rec.count("HANDOFF") == 10          # ...counts never do
    assert rec.arg_sum("HANDOFF", "pages") == 20
    assert rec.arg_sum("HANDOFF", "bytes") == 1000
    rec.clear()
    assert len(rec) == 0 and rec.count("HANDOFF") == 0


def test_disabled_recorder_records_nothing():
    rec = TraceRecorder(enabled=False)
    rec.event("SUBMIT", rid=0, prompt_tokens=4)
    with rec.span("step"):
        pass
    assert len(rec) == 0 and rec.count("SUBMIT") == 0
    assert rec._counts == {} and rec._sums == {}
    # one shared no-op span object: no per-call allocation when off
    assert rec.span("step") is rec.span("decode_sync")


def test_slo_derivation_from_lifecycle_timestamps():
    rec = TraceRecorder()
    t = {"v": 0.0}
    rec._now = lambda: t["v"]         # deterministic clock
    rec.event("SUBMIT", rid=1)
    t["v"] = 0.010
    rec.event("ADMIT", rid=1)
    t["v"] = 0.050
    rec.event("PREFILL_COMPLETE", rid=1)
    t["v"] = 0.150
    rec.event("RETIRE", rid=1, generated=6)
    slo = rec.request_slo()[1]
    assert slo["queue_wait_ms"] == pytest.approx(10.0)
    assert slo["ttft_ms"] == pytest.approx(50.0)
    assert slo["prefill_stall_ms"] == pytest.approx(40.0)
    assert slo["e2e_ms"] == pytest.approx(150.0)
    assert slo["tpot_ms"] == pytest.approx(100.0 / 5)  # 6 tokens -> 5 gaps
    summ = rec.slo_summary()
    assert summ["e2e_ms"]["n"] == 1
    assert summ["e2e_ms"]["p50"] == pytest.approx(150.0)


def test_chrome_trace_schema():
    rec = TraceRecorder()
    rec.event("SUBMIT", rid=0, prompt_tokens=4)
    with rec.span("step"):
        with rec.span("prefill", rid=0, width=4):
            pass
    obj = rec.chrome_trace()
    stats = validate_chrome_trace(obj)
    assert stats["spans"] == 2 and stats["instants"] == 1
    assert stats["total"] == len(obj["traceEvents"])
    with pytest.raises(ValueError):
        validate_chrome_trace({"events": []})
    with pytest.raises(ValueError):
        validate_chrome_trace(
            {"traceEvents": [{"ph": "Z", "name": "x", "pid": 0, "tid": 0,
                              "ts": 0}]})
    with pytest.raises(ValueError):
        validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "name": "x", "pid": 0, "tid": 0,
                              "ts": -1.0, "dur": 1.0}]})


def test_exporters_roundtrip(tmp_path):
    rec = TraceRecorder()
    rec.event("SUBMIT", rid=0)
    with rec.span("step"):
        pass
    ct = tmp_path / "trace.json"
    jl = tmp_path / "trace.jsonl"
    rec.write_chrome_trace(str(ct))
    rec.write_jsonl(str(jl))
    with open(ct) as f:
        validate_chrome_trace(json.load(f))
    lines = [json.loads(l) for l in open(jl)]
    assert len(lines) == len(rec)


# ---------------------------------------------------------------------------
# engine integration: parity, tie-outs, disabled path
# ---------------------------------------------------------------------------

def _drive(trace=None):
    eng = ContinuousEngine(CFG, PARAMS, n_pages=40, page_size=16,
                           max_batch=4, max_len=48,
                           prefill_chunk_tokens=16, decode_steps=2,
                           trace=trace)
    reqs = _reqs([(5, 6), (9, 4), (3, 5)])
    rids = [eng.submit(p, g) for p, g in reqs]
    out = eng.run()
    return eng, rids, [out[r] for r in rids]


def test_traffic_burst_leaves_null_recorder_untouched():
    """With tracing off (the default), the shared NULL_RECORDER's ring
    stays empty across a full serve burst -- telemetry-off costs one
    predicted branch, not hidden recording."""
    before = (len(NULL_RECORDER), dict(NULL_RECORDER._counts),
              dict(NULL_RECORDER._sums))
    _drive(trace=None)
    assert len(NULL_RECORDER) == before[0] == 0
    assert NULL_RECORDER._counts == before[1] == {}
    assert NULL_RECORDER._sums == before[2] == {}


def test_traced_engine_parity_and_tieouts(tmp_path):
    global RNG
    RNG = np.random.default_rng(21)   # same request stream both runs
    _, _, plain = _drive(trace=None)
    RNG = np.random.default_rng(21)
    rec = TraceRecorder()
    eng, rids, traced = _drive(trace=rec)
    # tracing never touches device math: bitwise-identical tokens
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a, b)
    # lifecycle counts tie to scheduler/engine counters exactly
    assert rec.count("SUBMIT") == rec.count("RETIRE") == len(rids)
    assert rec.count("DECODE_DISPATCH") == eng.decode_dispatches == \
        eng.metrics.value("engine/decode_dispatches")
    assert rec.count("PREFILL_CHUNK") > 0
    assert rec.arg_sum("PREFILL_CHUNK", "real") == \
        eng.prefill_tokens_computed
    # every request has a full SLO record
    slo = rec.request_slo()
    assert set(slo) == set(rids)
    for s in slo.values():
        assert {"queue_wait_ms", "ttft_ms", "e2e_ms"} <= set(s)
        assert s["e2e_ms"] >= s["ttft_ms"] >= 0.0
    # the export is Perfetto-loadable
    path = tmp_path / "t.json"
    rec.write_chrome_trace(str(path))
    with open(path) as f:
        stats = validate_chrome_trace(json.load(f))
    assert stats["spans"] > 0 and stats["instants"] > 0


# ---------------------------------------------------------------------------
# span ids, parents and profiler annotations
# ---------------------------------------------------------------------------

def _nested_spans(rec):
    with rec.span("step"):
        with rec.span("prefill"):
            for start in (0, 16):
                with rec.span("prefill_chunk", rid=3, start=start, real=16):
                    rec.event("PREFILL_CHUNK", rid=3, start=start)
            with rec.span("prefill_sync", rid=3):
                pass
        with rec.span("decode_dispatch"):
            pass
    rec.event("SUBMIT", rid=4)


def test_span_ids_and_parents():
    rec = TraceRecorder()
    _nested_spans(rec)
    by_id = {e["id"]: e for e in rec.events()}
    assert len(by_id) == len(rec)               # ids are unique
    kind_of = {i: e["kind"] for i, e in by_id.items()}
    links = sorted((e["kind"], kind_of.get(e["parent"]))
                   for e in rec.events())
    assert links == sorted([
        ("step", None), ("prefill", "step"),
        ("prefill_chunk", "prefill"), ("prefill_chunk", "prefill"),
        ("PREFILL_CHUNK", "prefill_chunk"), ("PREFILL_CHUNK", "prefill_chunk"),
        ("prefill_sync", "prefill"), ("decode_dispatch", "step"),
        ("SUBMIT", None)])
    # a child lies inside its parent
    for e in rec.events(kind="prefill_chunk"):
        p = by_id[e["parent"]]
        assert p["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= p["ts"] + p["dur"]
    # the Chrome export carries both links in its args
    ct = [e for e in rec.chrome_trace()["traceEvents"] if e["ph"] != "M"]
    assert sorted((e["args"]["id"], e["args"]["parent"]) for e in ct) == \
        sorted((e["id"], e["parent"]) for e in rec.events())
    assert set(SPAN_KINDS) >= {"prefill_chunk", "prefill_sync",
                               "decode_prepare", "decode_apply"}


def _host_annotations(trace_dir, names):
    """(name, start_ns, end_ns) of the host events named in ``names``
    in the profiler trace under ``trace_dir``."""
    import glob
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


@pytest.mark.parametrize("profile", [True, False])
def test_profile_switch_mirrors_spans_as_annotations(tmp_path, profile):
    """With ``profile`` on, every span is a profiler annotation of the
    same name, with the same count and nesting as the ring; off, the
    trace holds none of them."""
    rec = TraceRecorder(profile=profile)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _nested_spans(rec)
    finally:
        jax.profiler.stop_trace()
    spans = [e for e in rec.events() if e["ph"] == "X"]
    anns = _host_annotations(tmp_path, set(SPAN_KINDS))
    if not profile:
        assert anns == []
        return
    assert sorted(n for n, _, _ in anns) == sorted(e["kind"] for e in spans)

    def parent(a):
        outer = [b for b in anns if b is not a and b[1] <= a[1]
                 and a[2] <= b[2]]
        return min(outer, key=lambda b: b[2] - b[1])[0] if outer else None

    kind_of = {e["id"]: e["kind"] for e in spans}
    assert sorted((a[0], parent(a)) for a in anns) == sorted(
        (e["kind"], kind_of.get(e["parent"])) for e in spans)


def test_disabled_recorder_with_profile_records_nothing(tmp_path):
    rec = TraceRecorder(enabled=False, profile=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _nested_spans(rec)
    finally:
        jax.profiler.stop_trace()
    assert len(rec) == 0 and rec._counts == {}
    assert _host_annotations(tmp_path, set(SPAN_KINDS)) == []


def test_engine_step_children_cover_the_step():
    """Each phase of a step has a span of its own, so a step's self time
    (what its child spans leave uncovered) is a few percent at most."""
    rec = TraceRecorder()
    eng, _, _ = _drive(trace=rec)
    spans = [e for e in rec.events() if e["ph"] == "X"]
    steps = {e["id"]: e["dur"] for e in spans if e["kind"] == "step"}
    children = {}
    for e in spans:
        if e["parent"] in steps:
            children[e["parent"]] = children.get(e["parent"], 0.0) + e["dur"]
    kinds = {e["kind"] for e in spans if e["parent"] in steps}
    assert kinds == {"capacity", "admit", "prefill", "decode_prepare",
                     "decode_dispatch", "decode_sync", "decode_apply"}
    assert sum(children.values()) >= 0.95 * sum(steps.values())
    # prefill's children: one span per chunk and one per first token
    chunks = rec.events(kind="prefill_chunk")
    assert len(chunks) == rec.count("PREFILL_CHUNK")
    assert len(rec.events(kind="prefill_sync")) == \
        rec.count("PREFILL_COMPLETE")
    assert all({"start", "real"} <= set(e["args"]) and e["rid"] is not None
               for e in chunks)


def test_decode_events_carry_positions_and_tokens():
    rec = TraceRecorder()
    eng, rids, outs = _drive(trace=rec)
    disp = rec.events(kind="DECODE_DISPATCH")
    sync = rec.events(kind="DECODE_SYNC")
    assert len(disp) == len(sync) == eng.decode_dispatches
    for d, s in zip(disp, sync):
        assert len(d["args"]["positions"]) == d["args"]["batch"] == \
            len(s["args"]["tokens"])
        assert all(1 <= n <= eng.decode_steps for n in s["args"]["tokens"])
    # every generated token but the first (sampled at prefill) comes
    # from a decode dispatch
    generated = sum(len(o) for o in outs) - sum(
        rec.events(kind="SUBMIT", rid=r)[0]["args"]["prompt_tokens"]
        for r in rids)
    assert sum(sum(s["args"]["tokens"]) for s in sync) == \
        generated - len(rids)


def test_profile_annotations_switch_the_engine_recorder_on():
    from repro.serve import DisaggEngine
    rec = TraceRecorder()
    kw = dict(max_batch=4, max_len=48, page_size=16)
    ContinuousEngine(CFG, PARAMS, trace=rec, profile_annotations=True, **kw)
    assert rec.profile
    own = ContinuousEngine(CFG, PARAMS, profile_annotations=True, **kw)
    assert own._trace.enabled and own._trace.profile
    assert not NULL_RECORDER.profile
    plain = ContinuousEngine(CFG, PARAMS, **kw)
    assert plain._trace is NULL_RECORDER
    d = DisaggEngine(CFG, PARAMS, profile_annotations=True, **kw)
    assert d._trace.profile and d.decode._trace is d._trace


def test_served_programs_lower_under_their_table_names():
    """Each served program lowers as ``jit_<name>`` with the name the
    table gives its role: the device trace names its runs so."""
    from repro.models import zoo
    from repro.serve.engine import PROGRAM_NAMES, _ctx_write
    from repro.serve.paged_kv import _scatter_pages
    eng = ContinuousEngine(CFG, PARAMS, n_pages=8, page_size=16,
                           max_batch=2, max_len=48,
                           prefill_chunk_tokens=16, decode_steps=2)
    b, i32 = eng.max_batch, np.int32
    vec = np.zeros((b,), i32)
    loop = eng._decode_loop.lower(
        eng.params, np.zeros((b, 1), i32), vec, eng.pool.device_state(),
        np.zeros((b, eng.max_pages_per_req), i32), vec,
        np.zeros((b,), bool), vec, vec, vec, vec, eng._base_key)
    chunk = eng._chunk_step.lower(eng.params, np.zeros((1, 16), i32),
                                  eng._empty_ctx(), np.zeros((1,), i32))
    carry = zoo.init_cache(CFG, 1, 32)["k"]
    write = _ctx_write.lower(carry, carry[:, :, :16], np.int32(0))
    codes = eng.pool.device_state()["k_codes"]
    scatter = _scatter_pages.lower(codes, codes[:, :1], np.zeros((1,), i32))

    def module(lowered):
        return lowered.as_text().split("module @", 1)[1].split()[0]

    assert module(loop) == "jit_loop" == "jit_" + PROGRAM_NAMES["decode_loop"]
    assert module(chunk) == "jit_chunk_step" == \
        "jit_" + PROGRAM_NAMES["chunk_step"]
    assert module(write) == "jit_" + PROGRAM_NAMES["carry_write"]
    assert module(scatter) == "jit_" + PROGRAM_NAMES["page_scatter"]


def test_profiled_engine_outputs_match_untraced(tmp_path):
    """Annotations under a running profiler leave the device math alone:
    temperature-0 tokens are bitwise those of an untraced engine."""
    global RNG
    RNG = np.random.default_rng(21)
    _, _, plain = _drive(trace=None)
    RNG = np.random.default_rng(21)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, _, profiled = _drive(trace=TraceRecorder(profile=True))
    finally:
        jax.profiler.stop_trace()
    for a, b in zip(plain, profiled):
        np.testing.assert_array_equal(a, b)
