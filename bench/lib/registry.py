"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

A cell names a configuration and a traffic mix; the per-layer metrics
name their readers.  Each lives in a file of its own, so a later change
adds a configuration, a mix or a metric with new files and new entries
in ``BENCHMARK.json`` and edits nothing that is there:

  <config file>                   sizes, precision, program binding (JSON)
  <config file minus .json>.py    its plain reference, weight init and
                                  ``cost_model(config)``: the yardstick's
                                  count of the architecture's operations
                                  and bytes (``bench/lib/costs.py`` says
                                  what the shared readers call on it)
  bench/traffic/<traffic>.json    the mix's parameters and engine settings
  bench/metrics/<metric>.py       one per-layer metric's reader
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

__all__ = ["BENCH_DIR", "ROOT", "Cell", "MetricReader", "load_benchmark",
           "load_module", "cell"]


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a Python file by path (names may hold '.' and '-')."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class MetricReader:
    """One per-layer metric: its ``BENCHMARK.json`` entry and the module
    whose ``read(ctx)`` returns its number, or None where it finds
    nothing to read."""

    entry: Dict[str, Any]
    module: Any

    @property
    def name(self) -> str:
        return self.entry["name"]

    def read(self, ctx):
        return self.module.read(ctx)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]        # the configuration file (sizes etc.)
    reference: Any                # module beside it: weights + reference
    model: Any                    # its cost_model(config)
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[MetricReader]
    run_seconds: int


def _applies(entry: Dict[str, Any], cell_name: str) -> bool:
    return "workloads" not in entry or cell_name in entry["workloads"]


def cell(name: str, root: str = ROOT) -> Cell:
    """Resolve workload ``name`` into its configuration, reference, cost
    model, traffic and the metrics it reports.  A configuration module
    without ``cost_model`` is refused: no architecture is counted as
    another by default."""
    bench = load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    confs = {c["name"]: c for c in bench["configs"]}
    centry = confs[w["config"]]
    cfile = os.path.join(root, centry["file"])
    with open(cfile) as f:
        config = json.load(f)
    ref_path = os.path.splitext(cfile)[0] + ".py"
    reference = load_module(ref_path, "bench_ref_" + w["config"])
    cost_model = getattr(reference, "cost_model", None)
    if not callable(cost_model):
        raise AttributeError(f"{ref_path} defines no cost_model(config): "
                             "the yardstick has no count of its operations "
                             "and bytes")
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    readers = []
    for m in bench["per_layer"]:
        if not _applies(m, name):
            continue
        path = os.path.join(root, "bench", "metrics", m["name"] + ".py")
        readers.append(MetricReader(m, load_module(
            path, "bench_metric_" + m["name"].replace(".", "_"))))
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, reference=reference,
                model=cost_model(config),
                traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=readers,
                run_seconds=int(bench["run_seconds"]))
