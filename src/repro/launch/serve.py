"""Serving CLI: batched generation with the packed-weight plane.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --policy mixed --batch 4 --prompt-len 16 --steps 32 [--quantized-kv]

``--continuous`` serves the same request mix through the paged-KV
``ContinuousEngine`` instead: per-request prompt/generation lengths,
FIFO admission against a page pool, one batched decode step for all
live requests (see serve/__init__ for the page-table layout).
``--prefill-chunk N`` turns on chunked paged prefill: one step pays at
most N prefill tokens, so a long prompt no longer stalls the running
decode batch for a full prefill.  ``--prefix-cache`` shares the request
mix's common preamble through the pool's copy-on-write prefix cache:
every request after the first sharer skips re-prefilling the matched
whole pages.

``--decode-steps K`` makes the decode loop device-resident: one jitted
dispatch runs K fused decode+sample iterations (positions bump on
device, EOS/budget rows park mid-scan) and the host syncs one (B, K)
token buffer -- K host round trips become one, and the (B, vocab)
logits never leave the device.

  ... --continuous --batch 8 --n-pages 48 [--page-size 16]
      [--prefill-chunk 16] [--prefix-cache] [--decode-steps 4]

``--disagg`` serves the same mix through the disaggregated
``DisaggEngine`` instead: a prefill worker (admission + chunk budget)
and an uninterrupted decode worker over separate page pools, joined by
a double-buffered posit8 page-handoff channel; decode dispatches
overlap the prefill chunks.  With more than one device the workers'
programs are placed on distinct device slices
(``parallel.sharding.split_devices``).

  ... --disagg --batch 8 --n-pages 48 --prefill-chunk 16 --decode-steps 4

``--trace out.json`` records request-lifecycle events and per-step
spans on the paged engines and writes a Chrome-trace JSON (open in
Perfetto / chrome://tracing); ``--metrics`` prints a Prometheus-style
snapshot of the engine's metric registry.  See docs/observability.md.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..core.policy import PrecisionPolicy
from ..models import zoo
from ..serve.engine import ContinuousEngine, ServeEngine
from .cache import setup_compile_cache


def _static(args, cfg, params, policy) -> None:
    eng = ServeEngine(cfg, params,
                      max_len=args.prompt_len + args.steps + 8,
                      quantized_kv=args.quantized_kv, policy=policy)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)
    t0 = time.perf_counter()
    out = eng.generate(toks, steps=args.steps,
                       temperature=args.temperature)
    dt = time.perf_counter() - t0
    tps = args.batch * args.steps / dt
    print(f"generated {out.shape} in {dt:.2f}s ({tps:.1f} tok/s)")
    print(out[:, args.prompt_len:][:2])


def _continuous(args, cfg, params, policy) -> None:
    from ..obs import TraceRecorder
    rec = TraceRecorder() if (args.trace or args.metrics) else None
    rng = np.random.default_rng(0)
    max_len = args.prompt_len + args.steps + 8
    page_size = args.page_size
    if args.prefill_chunk and page_size is None:
        page_size = args.prefill_chunk       # chunk == k * page, k = 1
    if args.prefix_cache:
        # the shared preamble rides ON TOP of the nominal prompt
        # length: size the page table for it too, or the longest
        # requests would have no token budget left
        if page_size is None:
            from ..kernels.flash_decode import default_kv_block
            page_size = default_kv_block(max_len)
        max_len += page_size
    if args.prefill_chunk and max_len % args.prefill_chunk:
        # chunk | max_len is the page-table contract; round up
        max_len += args.prefill_chunk - max_len % args.prefill_chunk
    if page_size is not None and max_len % page_size:
        # the page table maps whole pages; round up exactly like the
        # chunk branch (an explicit --page-size used to crash the
        # engine's divisibility check here)
        max_len += page_size - max_len % page_size
    if args.disagg:
        from ..parallel.sharding import split_devices
        from ..serve.disagg import DisaggEngine
        pdev, ddev = split_devices()
        one = pdev is ddev or pdev[0] == ddev[0]
        eng = DisaggEngine(
            cfg, params, prefill_pages=args.n_pages,
            decode_pages=args.n_pages, page_size=page_size,
            max_batch=args.batch, max_len=max_len, policy=policy,
            temperature=args.temperature,
            prefill_chunk_tokens=args.prefill_chunk,
            prefix_cache=args.prefix_cache,
            decode_steps=args.decode_steps,
            prefill_device=None if one else pdev[0],
            decode_device=None if one else ddev[0],
            trace=rec)
    else:
        eng = ContinuousEngine(
            cfg, params, n_pages=args.n_pages, page_size=page_size,
            max_batch=args.batch, max_len=max_len, policy=policy,
            temperature=args.temperature,
            prefill_chunk_tokens=args.prefill_chunk,
            prefix_cache=args.prefix_cache,
            decode_steps=args.decode_steps,
            trace=rec)
    # ragged request mix around the CLI's nominal prompt/step counts;
    # under --prefix-cache every prompt opens with one shared page-sized
    # preamble (the XR scene/system prompt ahead of every query), so
    # request 2.. re-prefills only its unique tail
    preamble = rng.integers(0, cfg.vocab, (eng.page_size,)) \
        if args.prefix_cache else None
    n_req = 2 * args.batch
    rids = []
    for i in range(n_req):
        plen = max(1, args.prompt_len - int(rng.integers(0, 4)))
        steps = max(1, args.steps - int(rng.integers(0, args.steps // 2 + 1)))
        prompt = rng.integers(0, cfg.vocab, (plen,))
        if preamble is not None:
            prompt = np.concatenate([preamble, prompt])
            steps = max(1, min(steps, max_len - prompt.size))
        rids.append(eng.submit(prompt, steps))
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0
    finished = eng.finished if args.disagg else eng.scheduler.finished
    sched = eng.prefill.scheduler if args.disagg else eng.scheduler
    toks = sum(len(finished[r].generated) for r in rids)
    print(f"served {n_req} requests / {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s) over {eng.steps_run} engine steps")
    print(f"decode loop: K={eng.decode_steps}, {eng.decode_dispatches} "
          f"dispatches, {eng.page_table_uploads} page-table uploads, "
          f"{eng.token_host_bytes} token bytes to host "
          f"(logits bytes: {eng.logits_host_bytes})")
    if args.disagg:
        print(f"disagg: {eng.handoffs} handoffs / {eng.handoff_pages} "
              f"pages / {eng.handoff_bytes} posit8 bytes over the "
              f"channel (depth {eng.channel.depth}), "
              f"{eng.decode_bounces} decode-side bounces; pools "
              f"prefill {eng.prefill.pool.n_pages} (peak "
              f"{eng.prefill.pool.alloc_peak}) / decode "
              f"{eng.decode.pool.n_pages} (peak "
              f"{eng.decode.pool.alloc_peak}) x {eng.page_size} slots")
    else:
        print(f"pool: {eng.pool.n_pages} pages x {eng.pool.page_size} "
              f"slots, peak used {eng.pool.alloc_peak}, "
              f"preemptions {sched.preemption_count} "
              f"(mid-prefill {sched.prefill_preemptions}, "
              f"wasted prefill tokens {sched.wasted_prefill_tokens})")
    print(f"prefill: "
          f"{'chunked, %d tokens/step' % eng.prefill_chunk_tokens if eng.prefill_chunk_tokens else 'monolithic'}, "
          f"{eng.prefill_tokens_computed} tokens computed")
    if args.prefix_cache:
        px = sched.prefix
        print(f"prefix cache: {px.hits} hits, {px.hit_tokens} prefill "
              f"tokens served from shared pages, {len(px)} pages cached, "
              f"{px.evictions} evictions")
    for r in rids[:2]:
        print(f"  req {r}: {np.asarray(finished[r].generated)}")
    if rec is not None:
        print("slo (ms):")
        for name, s in rec.slo_summary().items():
            print(f"  {name:>17}: p50 {s['p50']:8.2f}  p95 {s['p95']:8.2f}  "
                  f"p99 {s['p99']:8.2f}  (n={s['n']})")
    if args.trace:
        rec.write_chrome_trace(args.trace)
        print(f"wrote Chrome trace ({len(rec)} events) to {args.trace} -- "
              f"open in Perfetto (ui.perfetto.dev) or chrome://tracing")
    if args.metrics:
        print(eng.metrics.prometheus_text(), end="")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--policy", default="mixed")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--quantized-kv", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--continuous", "--paged", action="store_true",
                    help="serve through the paged-KV ContinuousEngine")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode serving: split "
                         "the paged engine into a prefill worker and an "
                         "uninterrupted decode worker joined by a "
                         "posit8 page-handoff channel (implies paged "
                         "serving; each side gets its own --n-pages "
                         "pool)")
    ap.add_argument("--n-pages", type=int, default=48,
                    help="paged pool size (allocatable pages)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per page (default: the decode KV block, "
                         "or --prefill-chunk when that is set)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked paged prefill: max prefill tokens one "
                         "engine step may process (default: monolithic)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share whole common-preamble pages between "
                         "requests (copy-on-write prefix caching); the "
                         "demo mix gets a one-page shared preamble")
    ap.add_argument("--decode-steps", type=int, default=1,
                    help="decode iterations per jitted dispatch: one "
                         "host round trip drives K on-device "
                         "decode+sample steps (temperature-0 output is "
                         "identical for every K)")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="record request-lifecycle events + step spans "
                         "and write a Chrome-trace JSON (open in "
                         "Perfetto); paged engines only")
    ap.add_argument("--metrics", action="store_true",
                    help="print a Prometheus-style text snapshot of the "
                         "engine's metric registry after the run; paged "
                         "engines only")
    args = ap.parse_args()
    setup_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = zoo.init_model(jax.random.PRNGKey(0), cfg)
    policy = None
    if args.policy not in ("fp32", "none"):
        policy = (PrecisionPolicy.paper_mixed() if args.policy == "mixed"
                  else PrecisionPolicy.uniform(args.policy))
    if args.continuous or args.disagg:
        _continuous(args, cfg, params, policy)
    else:
        if args.trace or args.metrics:
            print("note: --trace/--metrics need the paged engines "
                  "(--continuous/--disagg); the static engine carries "
                  "no telemetry")
        _static(args, cfg, params, policy)


if __name__ == "__main__":
    main()
