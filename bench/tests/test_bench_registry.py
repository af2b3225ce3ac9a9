"""A configuration, a traffic mix and a per-layer metric are added with
new files and new entries alone: no file that is there changes.  Each
configuration brings its own cost model, and one without is refused."""

import hashlib
import json
import os
import re
import shutil

import pytest

from bench.tests.tiny import CPU_PEAK, ROOT, run_module
from bench.lib import costs, layers, registry, traffic

# A recurrent model with none of a dense transformer's attention keys:
# one state slab per live row, read and written once a step, no pages.
SLAB_MODULE = '''"""toy-slab: its cost model alone."""

import dataclasses


@dataclasses.dataclass(frozen=True)
class Slab:
    weight_bytes: int
    state_bytes: int
    token_flops: float

    def decode_dispatch_flops(self, positions, steps):
        return self.token_flops * sum(steps)

    def decode_dispatch_bytes(self, positions, steps, page):
        steps = list(steps)
        return float(max(steps, default=0) * self.weight_bytes
                     + 2 * self.state_bytes * sum(steps))

    def prefill_flops(self, start, real, sampled):
        return self.token_flops * real

    def prefill_bytes(self, start, real):
        return float(self.weight_bytes + 2 * self.state_bytes)


def cost_model(c):
    n = c["num_hidden_layers"] * 4 * c["hidden_size"] ** 2
    return Slab(weight_bytes=4 * n,
                state_bytes=4 * c["num_hidden_layers"] * c["hidden_size"],
                token_flops=2.0 * n)
'''


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


class _Engine:
    """An engine whose recorder holds only the window's opening event."""

    def __init__(self, window_event):
        self.trace = self
        self._events = [{"ts": 0.0, "ph": "i", "kind": window_event,
                         "rid": None, "args": {}}]

    def events(self, kind=None):
        return self._events


def _copy_bench(root):
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _add_config(root, bench, name, conf, module):
    """Write a configuration's two files and a cell of it under the mix
    ``toy-mix``; returns the configuration module's path."""
    base = os.path.join(root, "bench", "configs", name)
    with open(base + ".json", "w") as f:
        json.dump(conf, f)
    with open(base + ".py", "w") as f:
        f.write(module)
    bench["configs"].append({"name": name, "source": "made up",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": f"{name}.toy-mix", "config": name,
                               "traffic": "toy-mix", "chips": 1,
                               "why": "test"})
    return base + ".py"


def _toy_dense(root, bench):
    """A copy of qwen's files at other sizes: counted as dense."""
    qwen = os.path.join(root, "bench", "configs", "qwen2-0.5b")
    with open(qwen + ".json") as f:
        conf = json.load(f)
    conf.update(name="toy-dense", num_hidden_layers=2, hidden_size=64)
    with open(qwen + ".py") as f:
        _add_config(root, bench, "toy-dense", conf, f.read())

    def check(c):
        assert c.reference.param_shapes(c.config)["embed"]["table"] == \
            (conf["vocab_size"], 64)
        assert c.model == costs.Dense.from_config(conf)
        # rows at 600 and 900 decode 4 and 2 tokens: weights read 4 times
        m = c.model
        flops = sum(m.decode_token_flops(p) for p in (600, 601, 602, 603,
                                                       900, 901))
        nbytes = (4 * m.weight_bytes + 6 * m.kv_token_bytes
                  + (4 * 5 + 2 * 8) * m.kv_page_bytes(128))
        return flops, nbytes
    return "toy-dense", check


def _toy_slab(root, bench):
    """A configuration with none of Dense's attention and FFN keys."""
    conf = {"name": "toy-slab", "family": "ssm", "num_hidden_layers": 2,
            "hidden_size": 64, "vocab_size": 100,
            "precision": {"weights": {"all": "float32"}}}
    _add_config(root, bench, "toy-slab", conf, SLAB_MODULE)

    def check(c):
        assert type(c.model) is c.reference.Slab
        # 2 layers x 4 x 64^2 = 32,768 weights of 4 bytes, read once per
        # iteration (4); a 512-byte slab read and written per token (6)
        assert c.model.weight_bytes == 131_072
        return 6 * 65_536.0, 4 * 131_072 + 6 * 2 * 512
    return "toy-slab", check


@pytest.mark.parametrize("toy", [_toy_dense, _toy_slab],
                         ids=["dense", "recurrent"])
def test_new_cell_from_new_files_only(tmp_path, toy):
    root = str(tmp_path)
    bench = _copy_bench(root)
    before = _digests(root)

    # a configuration: its file of sizes and its module beside it
    name, check = toy(root, bench)
    # a traffic mix: data only
    mix = {"arrivals": {"kind": "onoff", "rate_per_s": 3.0,
                        "period_s": 5.0, "on_s": 1.0, "on_factor": 4.0},
           "prompt_tokens": {"dist": "uniform", "min": 4, "max": 20},
           "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                             "min": 2, "max": 12},
           "drain_s": 5, "engine": {"page_size": 128}, "check": {}}
    with open(os.path.join(root, "bench", "traffic", "toy-mix.json"),
              "w") as f:
        json.dump(mix, f)
    # a per-layer metric: its reader
    with open(os.path.join(root, "bench", "metrics", "toy.requests.py"),
              "w") as f:
        f.write('LAYER, UNIT, SOURCE, MOVES = "scheduler", "count", '
                '"program_counter", "output_tok_s"\n\n\n'
                'def read(ctx):\n    return float(len(ctx.requests))\n')
    cell_name = f"{name}.toy-mix"
    bench["per_layer"].append({"name": "toy.requests", "unit": "count",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "scheduler", "moves": "output_tok_s",
                               "workloads": [cell_name]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    c = registry.cell(cell_name, root=root)
    assert c.config["hidden_size"] == 64
    assert [m.name for m in c.per_layer] == ["toy.requests"]
    assert [m["name"] for m in c.end_to_end] == \
        [m["name"] for m in bench["end_to_end"]]

    class Ctx:
        requests = traffic.schedule(c.traffic, 5, 10.0, vocab=100)
    assert c.per_layer[0].read(Ctx) == 30.0

    # the harness's context takes the configuration's own cost model
    flops, nbytes = check(c)
    run = run_module()
    steps = [run.Step(0.0, 0.1, [(600, 4), (900, 2)], traced=True),
             run.Step(0.1, 0.2, [(700, 3)], traced=False)]
    ctx = run.layer_context(c, None, _Engine(run.WINDOW_EVENT), [], steps,
                            1.0, None, CPU_PEAK, 0)
    assert layers.traced_decode_cost(ctx) == pytest.approx(
        (flops, max(flops / CPU_PEAK["bf16_flops_per_s"],
                    nbytes / CPU_PEAK["hbm_bytes_per_s"]), 1))
    # the files that were there are byte for byte what they were
    after = _digests(root)
    assert {k: after[k] for k in before} == before


def test_qwen_is_counted_as_dense():
    """The qwen cell's readers see the same cost model as before the
    configurations brought their own."""
    with open(os.path.join(ROOT, "bench", "configs", "qwen2-0.5b.json")) as f:
        conf = json.load(f)
    assert registry.cell("qwen2-0.5b.chat").model == \
        costs.Dense.from_config(conf)


def test_a_configuration_without_a_cost_model_is_refused(tmp_path):
    root = str(tmp_path)
    bench = _copy_bench(root)
    conf = {"name": "no-model", "num_hidden_layers": 2, "hidden_size": 64}
    path = _add_config(root, bench, "no-model", conf,
                       '"""weights and a reference, but no cost model."""\n')
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    with pytest.raises(AttributeError, match=re.escape(path)):
        registry.cell("no-model.toy-mix", root=root)
