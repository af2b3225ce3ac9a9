"""BENCHMARK.json against the rules it is held to, and the last line a
run prints."""

import json
import os
import re

import pytest

from bench.tests.tiny import ROOT, run_tiny, tiny_cell
from bench.lib.registry import load_benchmark, load_module

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return load_benchmark()


def test_benchmark_file_keeps_its_rules(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["paths"] == ["bench"]
    assert all(not w.startswith("/") and ".." not in w
               for w in bench["command"])
    confs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("bench/")
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    pairs = set()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in confs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in bench["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))


def test_per_layer_metrics_declare_what_their_files_do(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        mod = load_module(os.path.join(ROOT, "bench", "metrics",
                                       m["name"] + ".py"),
                          "schema_" + m["name"].replace(".", "_"))
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == \
            (m["layer"], m["unit"], m["source"], m["moves"])
    # each roofline that moves a metric has a whole-step mfu beside it
    for m in bench["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       for o in bench["per_layer"])


@pytest.fixture(scope="module")
def tiny_runs():
    return {trace: run_tiny(tiny_cell(limit=1.0), trace=trace)
            for trace in (False, True)}


def test_last_line_schema(bench, tiny_runs):
    run, out = tiny_runs[False]
    line = run.result_line(out)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert isinstance(line["correct"], bool)
    assert line["attempted"] >= 1 and 0 <= line["failed"] <= \
        line["attempted"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    want = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    for chk in line["checks"].values():
        assert set(chk) == {"value", "limit"}
    json.loads(json.dumps(line))


def test_traced_line_carries_layer_metrics(bench, tiny_runs):
    run, out = tiny_runs[True]
    line = run.result_line(out)
    units = {m["name"]: m["unit"] for m in bench["per_layer"]}
    assert line["metrics"] and set(line["metrics"]) <= set(units)
    for k, v in line["metrics"].items():
        assert v["unit"] == units[k]
    # spans and counters are read on any backend; the device trace only
    # on a chip
    assert {"engine.host_ms_per_step", "scheduler.queue_wait_p95_ms",
            "scheduler.batch_occupancy",
            "device.compiles_in_window"} <= set(line["metrics"])
    assert line["metrics"]["device.compiles_in_window"]["value"] == 0
    assert list(line)[-1] == "checks"
