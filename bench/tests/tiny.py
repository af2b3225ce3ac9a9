"""A cell of the benchmark cut to a size the CPU runs in seconds: the
qwen2-0.5b configuration file with small widths and the chat mix with a
small engine.  Only the tests use it; the benchmark's cells run at the
sizes ``BENCHMARK.json`` states."""

from __future__ import annotations

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.lib.registry import cell as find_cell, load_module  # noqa: E402

# stand-in peaks for the CPU (the table refuses an unknown device kind)
CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def run_module():
    return load_module(os.path.join(ROOT, "bench", "run.py"),
                       "bench_run_under_test")


def tiny_cell(limit: float = 0.05, rate: float = 4.0):
    c = find_cell("qwen2-0.5b.chat")
    c.config = dict(c.config, num_hidden_layers=2, hidden_size=128,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=32, intermediate_size=256, vocab_size=512,
                    check={"token_gap_max": limit, "token_gap_mean": limit})
    c.model = c.reference.cost_model(c.config)
    t = copy.deepcopy(c.traffic)
    t["engine"].update(max_batch=8, max_len=256, page_size=32, n_pages=64,
                       prefill_chunk_tokens=64)
    t["prompt_tokens"].update(median=40, min=8, max=120)
    t["output_tokens"].update(median=12, min=4, max=40)
    t["arrivals"]["rate_per_s"] = rate
    t["drain_s"] = 60
    t["check"].update(min_served_tokens=60, max_requests=4)
    c.traffic = t
    return c


def run_tiny(cell, seed: int = 1234567890123, seconds: float = 2.0,
             trace: bool = False, control: bool = False):
    import jax
    run = run_module()
    return run, run.run_cell(cell, seed, seconds, trace, jax.devices(),
                             control=control, peak=CPU_PEAK)
