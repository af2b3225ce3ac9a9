# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark driver: one harness per paper table/figure.

  Table II  -> bench_mac_engine     (SIMD MAC engine, packed GEMM + quire)
  Table III -> bench_coprocessor    (morphable 8x8/16x16 array)
  Table IV  -> bench_e2e            (end-to-end packed vs dense serving)
  Fig 5-8   -> bench_accuracy       (precision sweeps on the XR workloads)
  size tbl  -> bench_model_size     (13.5 -> 2.42 MB UL-VIO story)
  decode    -> bench_decode         (quantized-KV flash decode vs bf16
                                     cache: tokens/s + KV bytes/step)
  serve     -> bench_serve          (continuous batching over paged KV:
                                     throughput, p50/p99 latency, pool
                                     utilization vs static max_len waste)

Roofline terms for the assigned architectures come from the dry-run
(launch/dryrun.py), not from CPU wall-clock -- see EXPERIMENTS.md.
"""

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run a single bench (mac_engine|coprocessor|"
                         "e2e|accuracy|model_size|decode|serve)")
    args = ap.parse_args()
    from repro.launch.cache import setup_compile_cache
    setup_compile_cache()
    from . import (bench_accuracy, bench_coprocessor, bench_decode,
                   bench_e2e, bench_mac_engine, bench_model_size,
                   bench_serve)
    benches = {
        "mac_engine": bench_mac_engine.run,
        "coprocessor": bench_coprocessor.run,
        "e2e": bench_e2e.run,
        "model_size": bench_model_size.run,
        "accuracy": bench_accuracy.run,
        "decode": bench_decode.run,
        "serve": bench_serve.run,
    }
    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
