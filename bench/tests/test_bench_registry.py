"""A configuration, a traffic mix and a per-layer metric are added with
new files and new entries alone: no file that is there changes."""

import hashlib
import json
import os
import shutil

from bench.tests.tiny import ROOT
from bench.lib import registry, traffic


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_cell_from_new_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = _digests(root)

    # a configuration: its file of sizes and its reference beside it
    qwen = os.path.join(root, "bench", "configs", "qwen2-0.5b")
    with open(qwen + ".json") as f:
        conf = json.load(f)
    conf.update(name="toy-dense", num_hidden_layers=2, hidden_size=64)
    with open(os.path.join(root, "bench", "configs", "toy-dense.json"),
              "w") as f:
        json.dump(conf, f)
    shutil.copy(qwen + ".py",
                os.path.join(root, "bench", "configs", "toy-dense.py"))
    # a traffic mix: data only
    mix = {"arrivals": {"kind": "onoff", "rate_per_s": 3.0,
                        "period_s": 5.0, "on_s": 1.0, "on_factor": 4.0},
           "prompt_tokens": {"dist": "uniform", "min": 4, "max": 20},
           "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                             "min": 2, "max": 12},
           "drain_s": 5, "engine": {}, "check": {}}
    with open(os.path.join(root, "bench", "traffic", "toy-mix.json"),
              "w") as f:
        json.dump(mix, f)
    # a per-layer metric: its reader
    with open(os.path.join(root, "bench", "metrics", "toy.requests.py"),
              "w") as f:
        f.write('LAYER, UNIT, SOURCE, MOVES = "scheduler", "count", '
                '"program_counter", "output_tok_s"\n\n\n'
                'def read(ctx):\n    return float(len(ctx.requests))\n')
    bench["configs"].append({"name": "toy-dense", "source": "made up",
                             "file": "bench/configs/toy-dense.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy-dense.toy-mix",
                               "config": "toy-dense", "traffic": "toy-mix",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "toy.requests", "unit": "count",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "scheduler", "moves": "output_tok_s",
                               "workloads": ["toy-dense.toy-mix"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    c = registry.cell("toy-dense.toy-mix", root=root)
    assert c.config["hidden_size"] == 64
    assert c.reference.param_shapes(c.config)["embed"]["table"] == \
        (conf["vocab_size"], 64)
    assert [m.name for m in c.per_layer] == ["toy.requests"]
    assert [m["name"] for m in c.end_to_end] == \
        [m["name"] for m in bench["end_to_end"]]

    class Ctx:
        requests = traffic.schedule(c.traffic, 5, 10.0, vocab=100)
    assert c.per_layer[0].read(Ctx) == 30.0
    # the files that were there are byte for byte what they were
    after = _digests(root)
    assert {k: after[k] for k in before} == before
