#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU, through its normal entry points.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # the disaggregated path on four

One chip, in one process (no child ever touches the chip):

  (a) refuse anything but a TPU, before any work;
  (b) every Pallas kernel compiled (``interpret=False``) at qwen2-0.5b
      shapes against its ``kernels/ref.py`` oracle;
  (c) qwen2-0.5b at its published widths (random weights from
      ``--seed``, ``PrecisionPolicy.paper_mixed()`` packing) served
      through ``ContinuousEngine`` -- posit8 paged KV, one-page chunked
      prefill, ``decode_steps=4`` -- once per ``decode_impl``.  The
      flash run takes its prefill context from the pages, so all three
      attention kernels sit on its path.  Each served token is checked
      by teacher forcing against a bf16-KV prefill of the same packed
      model; token-for-token parity with the static
      ``ServeEngine.generate(quantized_kv=True)`` is printed.

``--four-chips`` runs only the disaggregated engine (prefill and decode
workers on their own device slices, ``parallel.sharding.split_devices``)
and the one-chip ``ContinuousEngine`` it must match token for token.

Any failed check or exception ends the run with a non-zero exit code.
Only a run where every phase passed prints its last line:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PAGE = 128            # page == prefill chunk == decode KV block
MAX_LEN = 768         # >= longest prompt + generation, a multiple of PAGE
N_REQUESTS = 8
DECODE_STEPS = 4
# kernel vs oracle: max |kernel - oracle| over max |oracle|.  The oracles
# run at "highest" matmul precision; a TPU matmul at default precision
# rounds f32 operands to bf16 (2^-8 relative), in a kernel as in XLA.
KERNEL_TOL = 1e-2


class _CompileClock:
    """Sums JAX's own trace + lower + backend-compile durations, so each
    phase can report the seconds it spent compiling."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, t0 = self.seconds, time.perf_counter()
        yield
        print(f"[{name}] compile {self.seconds - c0:.1f} s, "
              f"phase {time.perf_counter() - t0:.1f} s", flush=True)


def _rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _check(name: str, ok: bool, detail: str) -> None:
    print(f"  {name}: {detail} -> {'ok' if ok else 'FAIL'}", flush=True)
    if not ok:
        raise AssertionError(f"{name}: {detail}")


# ---------------------------------------------------------------------------
# (b) the Pallas kernels, compiled, against their oracles
# ---------------------------------------------------------------------------

def kernels_phase(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import formats as fmt
    from repro.kernels import ops, ref
    from repro.kernels.flash_decode import (flash_decode_pallas,
                                            paged_flash_decode_pallas,
                                            paged_flash_prefill_pallas)
    from repro.models.attention import quantize_kv

    rng = np.random.default_rng(seed)
    d, f = cfg.d_model, cfg.d_ff
    x = jnp.asarray(rng.normal(size=(8, d)), jnp.bfloat16)   # decode batch
    w = jnp.asarray(rng.normal(size=(d, f)).astype(np.float32))
    for spec in (fmt.FP4, fmt.POSIT8, fmt.POSIT16):
        t = ops.pack_tensor(spec, w, group_size=32)
        got = ops.packed_matmul(x, t, interpret=False)
        with jax.default_matmul_precision("highest"):
            want = ref.rmmec_matmul_ref(x, t.words, t.scales, spec,
                                        t.scales.shape[1])[:, :f]
        err = _rel_err(got, want)
        _check(f"rmmec_matmul_pallas {spec.name} ({d}x{f}, group 32)",
               err <= KERNEL_TOL, f"rel err {err:.2e} <= {KERNEL_TOL:g}")
        got = ops.dequant(t, interpret=False)
        want = ref.dequant_ref(t.words, t.scales, spec,
                               t.scales.shape[1])[:d, :f]
        err = _rel_err(got, want)
        _check(f"dequant_pallas {spec.name}", err == 0.0,
               f"rel err {err:.2e} == 0")

    a = rng.integers(0, 256, size=(8, d))
    b = rng.integers(0, 256, size=(8, d))
    got = np.asarray(ops.quire_dot(jnp.asarray(a), jnp.asarray(b),
                                   interpret=False))
    want = ref.quire_dot_ref(a, b).astype(np.float32)
    _check(f"quire_dot_pallas (8x{d})", np.array_equal(got, want),
           "bit-exact")

    kh, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    g = cfg.n_heads // kh
    bsz, npp = N_REQUESTS, MAX_LEN // PAGE
    n_pool = bsz * npp + 1
    pool = {}
    for name in ("k", "v"):
        codes, scale = quantize_kv(jnp.asarray(
            rng.normal(size=(n_pool, PAGE, kh, dh)).astype(np.float32)))
        pool[name + "_codes"], pool[name + "_scale"] = codes, scale
    kv = (pool["k_codes"], pool["k_scale"], pool["v_codes"], pool["v_scale"])
    pt = jnp.asarray(1 + rng.permutation(bsz * npp).reshape(bsz, npp),
                     jnp.int32)
    pos = jnp.asarray(rng.integers(0, MAX_LEN, bsz), jnp.int32)
    q = jnp.asarray(rng.normal(size=(bsz, kh, g, dh)).astype(np.float32))

    got = paged_flash_decode_pallas(q, *kv, pt, pos, interpret=False)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_flash_decode_ref(q, *kv, pt, pos)
    err = _rel_err(got, want)
    _check(f"paged_flash_decode_pallas (B={bsz}, {n_pool} pages)",
           err <= KERNEL_TOL, f"rel err {err:.2e} <= {KERNEL_TOL:g}")

    # the contiguous cache of the same requests: the gathered pages
    contig = [x[pt].reshape(bsz, MAX_LEN, *x.shape[2:]) for x in kv]
    p0 = int(pos[0])
    got = flash_decode_pallas(q, *contig, p0, blk=PAGE, interpret=False)
    with jax.default_matmul_precision("highest"):
        want = ref.flash_decode_ref(q, *contig, p0)
    err = _rel_err(got, want)
    _check(f"flash_decode_pallas (B={bsz}, T={MAX_LEN}, pos {p0})",
           err <= KERNEL_TOL, f"rel err {err:.2e} <= {KERNEL_TOL:g}")

    start = jnp.asarray([3 * PAGE], jnp.int32)
    qc = jnp.asarray(rng.normal(size=(1, PAGE, kh, g, dh)).astype(np.float32))
    got = paged_flash_prefill_pallas(qc, *kv, pt[:1], start, interpret=False)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_prefill_ref(qc, *kv, pt[:1], start)
    err = _rel_err(got, want)
    _check(f"paged_flash_prefill_pallas (chunk {PAGE} at {int(start[0])})",
           err <= KERNEL_TOL, f"rel err {err:.2e} <= {KERNEL_TOL:g}")


# ---------------------------------------------------------------------------
# (c) qwen2-0.5b served through ContinuousEngine
# ---------------------------------------------------------------------------

def traffic(cfg, seed: int):
    """N_REQUESTS prompts of 100-600 tokens, 32-64 tokens each to
    generate, all submitted together."""
    import numpy as np
    rng = np.random.default_rng(seed)
    lens = rng.integers(100, 601, N_REQUESTS)
    gens = rng.integers(32, 65, N_REQUESTS)
    return ([rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens],
            [int(n) for n in gens])


def serve(cfg, params, policy, prompts, gens, **kw):
    """Serve the traffic through ContinuousEngine; -> (engine, outputs)."""
    from repro.serve.engine import ContinuousEngine
    eng = ContinuousEngine(cfg, params, n_pages=N_REQUESTS * MAX_LEN // PAGE,
                           page_size=PAGE, max_batch=N_REQUESTS,
                           max_len=MAX_LEN, policy=policy,
                           prefill_chunk_tokens=PAGE,
                           decode_steps=DECODE_STEPS, **kw)
    rids = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    out = eng.run()
    return eng, [out[r] for r in rids]


def serve_phase(cfg, params, policy, prompts, gens, clock) -> int:
    """Both decode paths; returns the tokens served."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import layers
    from repro.serve.engine import (TOKEN_GAP_TOL, ServeEngine,
                                    build_token_gap_step)

    served = 0
    for impl, context in (("blocked", "carry"), ("flash", "pages")):
        c = dataclasses.replace(cfg, decode_impl=impl)
        with clock.phase(f"serve decode_impl={impl}"):
            eng, outs = serve(c, params, policy, prompts, gens,
                              prefill_context=context)
            n = sum(len(o) - len(p) for o, p in zip(outs, prompts))
            print(f"  {len(outs)} requests, {n} tokens served in "
                  f"{eng.steps_run} engine steps, {eng.decode_dispatches} "
                  f"decode dispatches; attention: decode_impl={impl}, "
                  f"prefill context={context}; packed matmul: "
                  f"{'kernel' if layers.PACKED_USE_KERNEL else 'jnp oracle'}",
                  flush=True)
            served += n
        static = ServeEngine(c, params, max_len=MAX_LEN, quantized_kv=True,
                             policy=policy)
        with clock.phase(f"static parity decode_impl={impl}"):
            wants = [static.generate(jnp.asarray(p)[None], steps=g)[0]
                     for p, g in zip(prompts, gens)]
        with clock.phase(f"teacher-forced check decode_impl={impl}"):
            gap_step = jax.jit(build_token_gap_step(c))

            def rel_gaps(seq, p):
                """Gap of each generated token, in row deviations."""
                toks = np.zeros((1, MAX_LEN), np.int32)
                toks[0, :len(seq)] = seq
                gap, spread = gap_step(static.params, jnp.asarray(toks))
                sl = slice(len(p) - 1, len(seq) - 1)
                return np.asarray(gap[0, sl] / spread[0, sl])

            worst, exact, total, first = 0.0, 0, 0, None
            for i, (o, p, want) in enumerate(zip(outs, prompts, wants)):
                rel = rel_gaps(o, p)
                worst = max(worst, float(rel.max()))
                exact += int(np.sum(rel == 0.0))
                total += rel.size
                diff = np.nonzero(want != o)[0]
                if diff.size and first is None:
                    j = int(diff[0]) - len(p)
                    first = (i, j, rel[j], rel_gaps(want, p)[j])
            print(f"  temp-0 continuous == static generate(quantized_kv="
                  f"True), token for token: "
                  + ("holds" if first is None else
                     "DOES NOT HOLD: request %d, generated token %d first "
                     "differs; served token %.3f, static token %.3f row "
                     "std below the reference max" % first)
                  + ("" if context == "carry" else
                     " [pages context: not a pinned invariant]"),
                  flush=True)
            print(f"  served token is the reference argmax at "
                  f"{exact}/{total} positions", flush=True)
            _check(f"teacher-forced logits decode_impl={impl}",
                   worst <= TOKEN_GAP_TOL,
                   f"worst gap {worst:.3f} row std <= {TOKEN_GAP_TOL}")
    return served


# ---------------------------------------------------------------------------
# --four-chips: disaggregated workers on their own device slices
# ---------------------------------------------------------------------------

def four_chip_phase(cfg, params, policy, prompts, gens, clock) -> int:
    import jax
    import numpy as np
    from repro.parallel.sharding import split_devices
    from repro.serve.disagg import DisaggEngine

    with clock.phase("one-chip ContinuousEngine"):
        _, want = serve(cfg, params, policy, prompts, gens)
    pdev, ddev = split_devices()
    print(f"  prefill slice {[d.id for d in pdev]}, decode slice "
          f"{[d.id for d in ddev]}; each worker runs on the first device "
          f"of its slice", flush=True)
    with clock.phase("DisaggEngine"):
        eng = DisaggEngine(cfg, params, prefill_pages=len(prompts) *
                           MAX_LEN // PAGE,
                           decode_pages=len(prompts) * MAX_LEN // PAGE,
                           page_size=PAGE, max_batch=N_REQUESTS,
                           max_len=MAX_LEN, policy=policy,
                           prefill_chunk_tokens=PAGE,
                           decode_steps=DECODE_STEPS,
                           prefill_device=pdev[0], decode_device=ddev[0])
        rids = [eng.submit(p, g) for p, g in zip(prompts, gens)]
        out = eng.run()
        got = [out[r] for r in rids]
    for side, dev, tree in (
            ("prefill params", pdev[0], eng.prefill.params),
            ("prefill pool", pdev[0], eng.prefill.pool.device_state()),
            ("decode params", ddev[0], eng.decode.params),
            ("decode pool", ddev[0], eng.decode.pool.device_state())):
        placed = {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}
        _check(f"{side} placement", placed == {dev},
               f"on {sorted(d.id for d in placed)}, want [{dev.id}]")
    n = sum(len(o) - len(p) for o, p in zip(got, prompts))
    print(f"  {eng.handoffs} handoffs / {eng.handoff_pages} pages over the "
          f"channel, {eng.decode_bounces} bounces, {n} tokens", flush=True)
    same = all(np.array_equal(a, b) for a, b in zip(got, want))
    _check("disaggregated == one-chip continuous, token for token", same,
           f"{len(got)} requests")
    return n


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the disaggregated path on four chips "
                         "and the one-chip engine it must match")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":                                 # (a)
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    want_count = 4 if args.four_chips else 1
    if len(devices) < want_count:
        print(f"chip_smoke: needs {want_count} chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.core.policy import PrecisionPolicy
    from repro.kernels.ops import should_interpret
    from repro.launch.cache import setup_compile_cache
    from repro.models import zoo

    print(f"device: {dev.platform} / {dev.device_kind}, count "
          f"{len(devices)}", flush=True)
    print(f"compile cache: {setup_compile_cache()}", flush=True)
    if should_interpret():
        raise AssertionError("Pallas kernels would run interpreted on TPU")
    print("Pallas kernels: compiled (interpret off)", flush=True)
    clock = _CompileClock()

    cfg = get_config("qwen2-0.5b")                # published widths
    policy = PrecisionPolicy.paper_mixed()
    with clock.phase("init"):
        params = zoo.init_model(jax.random.PRNGKey(args.seed), cfg)
        prompts, gens = traffic(cfg, args.seed)
        print(f"  {cfg.name}: {cfg.n_layers} layers, d_model "
              f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, "
              f"vocab {cfg.vocab}; prompts {sorted(map(len, prompts))}, "
              f"generating {sorted(gens)}", flush=True)
    if args.four_chips:
        served = four_chip_phase(cfg, params, policy, prompts, gens, clock)
    else:
        with clock.phase("kernels"):
            kernels_phase(cfg, args.seed)
        served = serve_phase(cfg, params, policy, prompts, gens, clock)
    print(f"tokens served: {served}; compile total {clock.seconds:.1f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
