#!/usr/bin/env python3
"""Measurements that set a cell's fixed numbers, in one process each
(set-up is paid once; the engine is reused and only its weights change
from seed to seed).  The driver's runs never call this.

  python3 bench/tools.py sweep --workload qwen2-0.5b.chat --seed 3 \
      --seconds 20 --rates 6,9,12,15
      The knee: per offered rate, tokens/s, tails, failures and the
      backlog (requests due but without a first token) at a third, two
      thirds and the end of the window.  A backlog that grows through
      the window means the rate is above what the cell sustains.

  python3 bench/tools.py readings --workload qwen2-0.5b.chat \
      --seeds 11,12,13 --seconds 20
      The correctness readings: per seed, the widest and the mean token
      gap of the program's served tokens and of the control (the
      reference one precision lower) on the same sample, at the cell's
      own load, each judged against the configuration's limits.

  python3 bench/tools.py control --workload qwen2-0.5b.chat --seed 7 \
      --seconds 51
      One whole run of the cell (``run.run_cell``) with the control's
      numbers in the program's place: its result line has to read
      ``"correct": false``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

SWEEP_DRAIN_S = 10.0     # a rate's requests still unserved then count as failed

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (os.path.join(ROOT, "src"), ROOT)
                if p not in sys.path]


def _backlog(recs, t: float) -> int:
    return sum(1 for r in recs if r.due <= t
               and (r.first is None or r.first > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("sweep", "readings", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--rate", type=float, default=None,
                    help="readings: override the mix's arrival rate")
    args = ap.parse_args(argv)

    import gc
    import jax
    from bench.lib import check, traffic
    from bench.lib.registry import cell as find_cell, load_module
    run = load_module(os.path.join(ROOT, "bench", "run.py"), "bench_run")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        run.log("bench/tools.py measures a TPU; JAX found none")
        return 2
    c = find_cell(args.workload)
    devices = devices[:c.chips]
    run.use_compile_cache()
    if args.mode == "control":
        out = run.run_cell(c, args.seed, args.seconds, False, devices,
                           control=True)
        print(json.dumps(run.result_line(out)), flush=True)
        return 0
    cfg = run.program_config(c)
    maker = run.weight_maker(c)
    seeds = [int(s) for s in args.seeds.split(",") if s] or [args.seed]
    engine = run.build_engine(c, cfg, maker(check.seed_key(seeds[0])),
                              trace=False)
    run.warm_up(engine, c, cfg.vocab)
    drain = float(c.traffic["drain_s"])
    S = args.seconds
    if args.mode == "sweep":
        for rate in [float(r) for r in args.rates.split(",")]:
            sched = traffic.schedule(c.traffic, args.seed, S, cfg.vocab,
                                     rate)
            recs, _, tok, _ = run.drive(engine, sched, S, SWEEP_DRAIN_S)
            # an overloaded rate leaves a backlog: the next rate starts
            # on a fresh engine (its programs load from the cache)
            packed = engine.params
            del engine
            engine = run.build_engine(c, cfg, packed, trace=False)
            run.warm_up(engine, c, cfg.vocab)
            e2e = run.end_to_end(recs, tok, S, SWEEP_DRAIN_S, 0.0)
            print(json.dumps({
                "rate": rate, "requests": len(recs),
                "failed": sum(not r.finished for r in recs),
                "output_tok_s": e2e["output_tok_s"],
                "ttft_p95_ms": e2e["ttft_p95_ms"],
                "tpot_p95_ms": e2e["tpot_p95_ms"],
                "backlog": [_backlog(recs, S * f) for f in (1 / 3, 2 / 3, 1)],
            }), flush=True)
        return 0
    for seed in seeds:
        engine.params = maker(check.seed_key(seed))
        sched = traffic.schedule(c.traffic, seed, S, cfg.vocab, args.rate)
        recs, _, tok, _ = run.drive(engine, sched, S, drain)
        gc.collect()
        res = run.correctness(c, seed, recs, control=True)
        ctl = res.pop("control", {})
        line = {"seed": seed, "requests": len(recs),
                "failed": sum(not r.finished for r in recs), **res}
        for side, numbers in (("program", res), ("control", ctl)):
            checks, ok = check.verdict(numbers, c.config["check"])
            line[side] = {"correct": ok, "checks": checks}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
