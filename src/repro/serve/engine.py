"""Batched serving engine: prefill -> decode with the packed weight plane.

This is the runtime the decode_* and long_* dry-run shapes lower:
``serve_step`` is one new token against a seq_len KV cache (or SSM state).
Weights can be physically packed (PackedTensor leaves -- HBM holds the
low-bit codes, the paper's memory-bandwidth reduction) and the KV cache
can be Posit(8,0)-quantized end-to-end (``quantized_kv=True``): prefill
returns codes+scales (one-shot ``zoo.quantize_cache`` fused into the
prefill jit, before ``_pad_cache``), decode writes the quantized layout
incrementally and reads only the live prefix of it per step (the
length-aware paths in ``models/attention``) -- the bf16 cache never
exists in HBM.

The engine itself does simple static batching with per-request lengths
masked by position -- enough to serve real batched traffic in the
examples while keeping the step function identical to the dry-run cell.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core.policy import PrecisionPolicy
from ..models import ssm
from ..models import zoo
from ..obs import MetricRegistry, NULL_RECORDER, TraceRecorder, bind_counters
from .programs import PROGRAM_NAMES, program
from .scheduler import PREFILLING, RUNNING

__all__ = ["build_prefill_step", "build_token_gap_step",
           "build_prefill_chunk_step", "build_serve_step", "ServeEngine",
           "ContinuousEngine", "PROGRAM_NAMES"]


def build_prefill_step(cfg: ModelConfig, last_logit_only: bool = False,
                       quantized_kv: bool = False,
                       kv_group: Optional[int] = None,
                       quantized_state: bool = False):
    """(params, batch) -> (logits, cache): full-sequence forward that also
    materializes the KV cache / SSM state.

    ``last_logit_only``: return logits only for the final position -- the
    only one generation needs.  XLA pushes the slice up through the
    readout matmul, eliminating ~(S-1)/S of lm_head FLOPs and the
    (B, S, vocab) buffer (a §Perf hillclimb lever for prefill cells).

    ``quantized_kv``: quantize the returned KV cache to posit8 codes +
    ``kv_group``-grouped scales inside the same jit (XLA fuses the
    quantize into the cache write, so the bf16 cache is a transient,
    not an output).  ``quantized_state`` extends the same one-shot
    quantization to recurrent-state leaves (``ssm.quantize_state``);
    decode then round-trips the state through posit8 every step --
    the contiguous twin of the paged pool's state slabs."""

    def prefill(params, batch):
        logits, cache, _ = zoo.apply_model(params, batch, cfg, mode="prefill",
                                           cache=None)
        if last_logit_only:
            logits = logits[:, -1:]
        if quantized_kv:
            cache = zoo.quantize_cache(cache, kv_group,
                                       quantize_state=quantized_state)
        return logits, cache

    return prefill


# teacher-forced token check (``build_token_gap_step``): a served token
# passes when its logit is within this many row standard deviations of
# the row max.  posit8 KV and bf16 rounding move logits by a fraction
# of the spread (up to ~0.5 of it over hundreds of positions of the
# 2-layer smoke model), while a token from a broken path lands several
# deviations below the max: the max of a 512-token row sits ~3, of a
# 152k-token row ~4.5, deviations above the row mean.
TOKEN_GAP_TOL = 1.0


def build_token_gap_step(cfg: ModelConfig):
    """(params, tokens (B, S)) -> (gap (B, S-1), spread (B, S-1)) f32.

    Teacher forcing: one bf16-KV prefill over ``tokens`` (prompt +
    served tokens), then at each position ``s`` how far the logit of
    ``tokens[:, s+1]`` sits below the max of row ``s`` (0 where it is
    the argmax), and that row's standard deviation.  A served token is
    checked as right when its gap is within a tolerance of the spread:
    unlike comparing greedy tokens, this does not flip on argmax ties
    between near-equal logits."""
    prefill = build_prefill_step(cfg)

    def gap_step(params, tokens):
        logits, _ = prefill(params, {"tokens": tokens})
        lg = logits[:, :-1].astype(jnp.float32)
        picked = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
        return jnp.max(lg, axis=-1) - picked, jnp.std(lg, axis=-1)

    return gap_step


def build_prefill_chunk_step(cfg: ModelConfig,
                             kv_group: Optional[int] = None,
                             paged: bool = False):
    """(params, tokens (1, C), ctx, start (1,)) -> the chunk-prefill step
    of chunked paged prefill: forward one CHUNK of C tokens at absolute
    positions ``start .. start+C-1``, attending causally to ``ctx`` (the
    request's already-prefilled prefix) plus the chunk itself.

    ``paged=False`` (carry, the engine default): ``ctx`` is the bf16 KV
    carry ``{"k", "v"}`` stacked (L, 1, T, Kh, Dh) with T == start.
    Returns (logits (1, C, V), chunk_kv, chunk_q): ``chunk_kv`` extends
    the carry for the next chunk and ``chunk_q`` (posit8 codes+scales,
    quantized inside the jit) scatters into pages via
    ``PagedKVPool.write_chunk``.  Chunk logits agree BITWISE with a
    monolithic prefill of the same prefix.

    ``paged=True``: ``ctx`` carries the pool leaves + ``page_table``
    (leaves lead with the layer-scan axis, like the paged decode cache);
    the chunk is quantized and scattered in-jit, attention reads prefix
    + chunk back through the page table, and (logits, updated_ctx) is
    returned -- zero extra residency, posit8-accurate context.
    Attention-only: recurrent state never lands in pages, so it cannot
    be re-read through a page table -- stateful families chunk on the
    carry path, where ``ctx`` is the family's ``zoo.init_cache`` pytree
    (rwkv state stack / hybrid group caches) and the f32 state rides
    the carry chunk to chunk (sequential recurrences make the chunked
    state BITWISE the monolithic one).
    """
    if paged and cfg.family not in ("dense", "moe"):
        raise ValueError(
            f"prefill_context='pages' re-reads the prefix through the "
            f"page table, but family {cfg.family!r} carries recurrent "
            f"state that never lands in pages: chunk on the carry path")
    if cfg.rope_kind != "default":
        raise ValueError("chunked prefill serves 1-D token streams "
                         f"(rope_kind={cfg.rope_kind!r})")

    def chunk_step(params, tokens, ctx, start):
        c = tokens.shape[1]
        positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
        batch = {"tokens": tokens, "positions": positions}
        logits, new_cache, _ = zoo.apply_model(
            params, batch, cfg, mode="prefill_chunk", cache=ctx)
        if paged:
            return logits, new_cache
        return logits, new_cache, zoo.quantize_cache(new_cache, kv_group)

    return program(chunk_step, "chunk_step")


def build_serve_step(cfg: ModelConfig, ragged: bool = False,
                     sample: bool = False):
    """(params, tokens (B,1), cache, pos) -> (logits, new_cache).

    ``ragged=True`` adds a trailing ``pad`` operand ((B,) left-pad
    widths): RoPE positions shift per request and pad cache slots are
    masked, so a left-padded mixed-length batch decodes like its
    unpadded per-request selves.

    ``sample=True`` fuses sampling into the step: two more trailing
    operands ``(key, temperature)`` and the step returns
    ``(next_tokens (B, 1) int32, new_cache)`` instead of logits -- the
    ``(B, vocab)`` logits never leave the device.  ``temperature`` is a
    traced scalar (one compiled step serves both regimes;
    ``lax.cond`` picks greedy argmax vs seeded categorical at run
    time), and both branches keep the host sampler's exact semantics:
    first-occurrence argmax tie-breaking, categorical over
    ``logits / temperature`` in the logits' own dtype."""

    def _next(logits, key, temperature):
        lg = logits[:, -1]
        return jax.lax.cond(
            temperature > 0,
            lambda: jax.random.categorical(
                key, lg / temperature).astype(jnp.int32),
            lambda: jnp.argmax(lg, -1).astype(jnp.int32))[:, None]

    if ragged:
        if sample:
            def serve_step(params, tokens, cache, pos, pad, key, temperature):
                logits, cache = zoo.decode_model(params, tokens, cfg, cache,
                                                 pos, pad)
                return _next(logits, key, temperature), cache
        else:
            def serve_step(params, tokens, cache, pos, pad):
                return zoo.decode_model(params, tokens, cfg, cache, pos, pad)
    elif sample:
        def serve_step(params, tokens, cache, pos, key, temperature):
            logits, cache = zoo.decode_model(params, tokens, cfg, cache, pos)
            return _next(logits, key, temperature), cache
    else:
        def serve_step(params, tokens, cache, pos):
            return zoo.decode_model(params, tokens, cfg, cache, pos)

    return serve_step


@dataclasses.dataclass
class ServeEngine:
    """Static-batch serving with greedy/temperature sampling."""

    cfg: ModelConfig
    params: Any
    max_len: int = 2048
    # posit8 KV cache end-to-end: prefill returns codes+scales, decode
    # reads only the live prefix of them per step.  The scale grouping
    # follows ``policy.group_size`` (the weight plane's grid).
    quantized_kv: bool = False
    # posit8 recurrent state too (ssm/hybrid): prefill quantizes the
    # final state once, decode round-trips it through posit8 every step
    # -- the static oracle of the paged pool's state slabs
    quantized_state: bool = False
    policy: Optional[PrecisionPolicy] = None

    def __post_init__(self):
        if self.policy is not None:
            self.params = zoo.pack_params(self.params, self.policy)
        kv_group = self.policy.group_size if self.policy else None
        self._prefill = jax.jit(build_prefill_step(
            self.cfg, last_logit_only=True,
            quantized_kv=self.quantized_kv, kv_group=kv_group,
            quantized_state=self.quantized_state))
        self._step = jax.jit(build_serve_step(self.cfg))
        self._step_ragged = jax.jit(build_serve_step(self.cfg, ragged=True))
        # generate() runs on the fused-sampling variants: tokens come
        # back (B, 1) int32 and accumulate on device; the (B, vocab)
        # logits never cross to host
        self._gen_step = jax.jit(build_serve_step(self.cfg, sample=True))
        self._gen_step_ragged = jax.jit(
            build_serve_step(self.cfg, ragged=True, sample=True))

    def generate(self, tokens: jax.Array, steps: int,
                 temperature: float = 0.0, key=None,
                 lengths=None) -> np.ndarray:
        """tokens: (B, S0) prompt -> (B, S0+steps) completed.

        ``lengths``: optional (B,) true prompt lengths of a LEFT-padded
        ragged batch (request b occupies ``tokens[b, S0-lengths[b]:]``).
        Pad tokens are masked out of attention and RoPE positions start
        at each request's first real token, so a mixed-length batch
        generates exactly what per-request calls would."""
        b, s0 = tokens.shape
        batch = {"tokens": tokens}
        pad = None
        if lengths is not None:
            if self.cfg.family not in ("dense", "moe") or \
                    self.cfg.rope_kind != "default":
                raise ValueError(
                    "ragged prompts need a pure-attention family with "
                    "default RoPE (SSM state would still absorb pads)")
            lengths = jnp.asarray(lengths, jnp.int32)
            pad = (s0 - lengths).astype(jnp.int32)          # (B,)
            idx = jnp.arange(s0, dtype=jnp.int32)[None]
            batch["positions"] = jnp.maximum(idx - pad[:, None], 0)
            batch["kv_mask"] = idx >= pad[:, None]
        # prefill is unconditional for every model family: it returns the
        # populated KV cache / SSM state (already posit8 codes+scales
        # under quantized_kv) that decode continues from.  Left padding
        # keeps the LAST column the last real token of every request, so
        # the last_logit_only logits feed sampling for ragged batches too.
        logits, cache = self._prefill(self.params, batch)
        cache = self._pad_cache(cache, b)
        last = jnp.argmax(logits, -1).astype(jnp.int32)     # (B, 1)
        key = key if key is not None else jax.random.PRNGKey(0)
        temp = jnp.float32(temperature)
        # device-resident loop: each fused step returns the (B, 1)
        # sampled token that feeds the next step; tokens accumulate on
        # device and transfer ONCE at the end -- no per-step logits (or
        # token) sync.  The key splits unconditionally (same sequence
        # the host sampler consumed when temperature > 0; unused at 0).
        outs = [jnp.asarray(tokens)]
        for i in range(steps):
            outs.append(last)
            key, sub = jax.random.split(key)
            if pad is None:
                last, cache = self._gen_step(
                    self.params, last, cache, jnp.int32(s0 + i), sub, temp)
            else:
                last, cache = self._gen_step_ragged(
                    self.params, last, cache, jnp.int32(s0 + i), pad, sub,
                    temp)
        return np.asarray(jnp.concatenate(outs, axis=1))

    # cache leaves with a sequence axis, all laid out (L, B, S, H, ...):
    # bf16 k/v, posit8 codes, and their (..., Gs) scale tensors
    _SEQ_KEYS = frozenset(
        {"k", "v", "k_codes", "v_codes", "k_scale", "v_scale"})
    # scale leaves pad with the pool's neutral scale 1.0, not jnp.pad's
    # default 0.0: a zero po2 scale in a padded slot silently dequantizes
    # ANY code written there to 0 (only the positional mask was hiding
    # it), and the paged pool initializes scales to 1.0 -- the two
    # planes must share one convention.
    _SCALE_KEYS = frozenset({"k_scale", "v_scale"})

    def _pad_cache(self, cache, b):
        """Grow prefill-length KV buffers to max_len for decode.

        Structure-aware: pads by cache KEY (the seq axis is always axis 2
        of the stacked (L, B, S, H, ...) layout) instead of guessing from
        ndim/shape/dtype -- scale tensors pad on the right rank and SSM /
        RWKV states (no seq axis, no KV keys) pass through untouched."""
        def pad(key, x):
            if key in self._SEQ_KEYS and x.shape[2] < self.max_len:
                pad_width = [(0, 0)] * x.ndim
                pad_width[2] = (0, self.max_len - x.shape[2])
                fill = 1.0 if key in self._SCALE_KEYS else 0.0
                return jnp.pad(x, pad_width, constant_values=fill)
            return x

        def rec(node):
            if isinstance(node, dict):
                return {key: (rec(val) if isinstance(val, dict)
                              else pad(key, val))
                        for key, val in node.items()}
            return node

        return rec(cache)


# ---------------------------------------------------------------------------
# Continuous batching over the paged posit8 KV pool
# ---------------------------------------------------------------------------

def _trace_counted(fn, counts: Dict[str, int], name: str):
    """Wrap ``fn`` with a Python-side tracing counter before handing it
    to ``jax.jit``: the wrapper body runs only while jax TRACES the
    function (steady-state dispatches replay the compiled executable
    without re-entering Python), so ``counts[name]`` is exactly the
    (re)trace count.  This is the compile-count sentinel bench_serve
    asserts stays flat across the measured window -- a new shape bucket
    or a leaked weak-type/python-scalar operand shows up as a count
    bump at the diff that introduced it, not as an unattributable p99
    shift."""
    counts[name] = 0

    @functools.wraps(fn)
    def counted(*args, **kwargs):
        counts[name] += 1
        return fn(*args, **kwargs)

    return counted


def _recorder(trace, profile_annotations: bool, registry):
    """The engine's span recorder: ``trace`` if given, else the shared
    no-op recorder (a fresh one under ``profile_annotations``, which
    also turns its ``profile`` switch on).  An enabled recorder
    observes span durations into ``registry``."""
    if trace is None:
        trace = TraceRecorder() if profile_annotations else NULL_RECORDER
    if profile_annotations:
        trace.profile = True
    if trace.enabled and trace.hist_registry is None:
        trace.hist_registry = registry
    return trace


def _device_only(on: bool):
    """A FRESH ``jax.transfer_guard("disallow")`` context when ``on``
    (jax guard contexts are single-use, so each guarded window needs
    its own), else a no-op.  Under the guard every IMPLICIT transfer
    raises -- a numpy or python-scalar operand silently uploaded into a
    dispatch, a device value silently pulled to host -- while the
    sanctioned explicit escapes (``jnp.asarray`` staging, the
    epoch-cache's page-table upload, ``jax.device_get`` of the sampled
    tokens) stay legal."""
    return jax.transfer_guard("disallow") if on else contextlib.nullcontext()


def _carry_write(buf: jax.Array, chunk: jax.Array, start) -> jax.Array:
    """dynamic_update_slice one bf16 KV chunk (L, 1, C, Kh, Dh) into the
    preallocated prefill carry at seq offset ``start``.  The carry is
    donated, so XLA updates the resident buffer instead of copying the
    whole prefix per chunk (the old per-chunk concatenate was O(T^2)
    bytes over a T-token prefill)."""
    return jax.lax.dynamic_update_slice(buf, chunk, (0, 0, start, 0, 0))


_ctx_write = jax.jit(program(_carry_write, "carry_write"),
                     donate_argnums=(0,))


def _build_decode_loop(cfg: ModelConfig, temperature: float, k_steps: int):
    """Build the device-resident K-step decode dispatch of the
    continuous engine.

    (params, tokens (B,1), positions (B,), cache {pool leaves},
     page_table (B,NP), slab_table (B,), done (B,) bool, budget (B,),
     eos (B,), rids (B,), gen_idx (B,), key)
      -> (sampled (B, K) int32, new cache)

    One jitted call runs ``k_steps`` decode+sample iterations in a
    ``lax.scan``: fused sampling (greedy argmax / per-request seeded
    categorical at build-time ``temperature``), device-side position
    bumps, and an on-device done-mask.  A row finishes mid-scan when it
    samples its ``eos`` id or exhausts its remaining token ``budget``;
    finished (and padded) rows freeze their token/position and re-map
    their page-table row to the parking page, so their remaining
    iterations write page 0 at position 0 -- no-op DMAs that cannot
    touch live pages (paged_kv.PARKING_PAGE).  The host syncs only the
    (B, K) token buffer per dispatch; the (B, vocab) logits never leave
    the device.

    Page kinds (``serve/paged_kv.py``): attention layers read/write the
    paged KV plane through ``page_table``; recurrent layers (ssm /
    hybrid) gather their quantized state slab by ``slab_table`` row into
    the step's per-layer cache, run the dequantize -> recur ->
    requantize round-trip inside the model, and scatter the slab back
    -- the scan carry holds the WHOLE slab plane, so state stays
    device-resident across all K iterations.  Done rows re-map to the
    parking slab (slab 0), the state twin of the parking page: their
    writes race only each other over a buffer nobody reads.

    Categorical sampling draws row r's token i from the per-request
    stream ``fold_in(fold_in(key, rids[r]), gen_idx[r] + i)`` -- a
    function of (seed, request, token index) only, so the sampled
    sequence is invariant to K, batching and scheduling.
    """
    from .paged_kv import PARKING_PAGE, PARKING_SLAB, _POOL_KEYS
    has_state = cfg.family in ("ssm", "hybrid")
    has_kv = cfg.family != "ssm"
    attn_key = f"b{cfg.attn_every // 2}" if cfg.family == "hybrid" else None

    def loop(params, tokens, positions, cache, page_table, slab_table,
             done, budget, eos, rids, gen_idx, key):
        def body(carry, _):
            tokens, positions, done, budget, gen_idx, cache = carry
            slab_idx = None
            state = None
            if has_state:
                slab_idx = jnp.where(done, PARKING_SLAB, slab_table)
                state = jax.tree.map(lambda leaf: leaf[:, slab_idx],
                                     cache["state"])
            if not has_kv:
                step_cache = state
            else:
                kv_leaves = {k: cache[k] for k in _POOL_KEYS}
                if has_state:
                    # hybrid: the attention sub-block reads the pool
                    # leaves; every other sub-block its gathered state
                    step_cache = dict(state)
                    step_cache[attn_key] = kv_leaves
                else:
                    step_cache = kv_leaves
                step_cache["page_table"] = jnp.where(
                    done[:, None], PARKING_PAGE, page_table)
                step_cache["positions"] = jnp.where(done, 0, positions)
            logits, new_cache = zoo.decode_model(
                params, tokens, cfg, step_cache, jnp.int32(0))
            if has_kv:
                new_cache.pop("page_table")
                new_cache.pop("positions")
            if not has_kv:
                cache = {"state": jax.tree.map(
                    lambda buf, new: buf.at[:, slab_idx].set(new),
                    cache["state"], new_cache)}
            elif has_state:
                kv = new_cache.pop(attn_key)
                new_state = jax.tree.map(
                    lambda buf, new: buf.at[:, slab_idx].set(new),
                    cache["state"], new_cache)
                cache = {k: kv[k] for k in _POOL_KEYS}
                cache["state"] = new_state
            else:
                cache = new_cache
            lg = logits[:, 0].astype(jnp.float32)            # (B, V)
            if temperature > 0:
                sub = jax.vmap(lambda r, i: jax.random.fold_in(
                    jax.random.fold_in(key, r), i))(rids, gen_idx)
                nxt = jax.vmap(lambda k_, row: jax.random.categorical(
                    k_, row / temperature))(sub, lg).astype(jnp.int32)
            else:
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            nxt = jnp.where(done, tokens[:, 0], nxt)         # freeze dead rows
            budget = jnp.where(done, budget, budget - 1)
            new_done = done | (nxt == eos) | (budget <= 0)
            positions = jnp.where(done, positions, positions + 1)
            gen_idx = jnp.where(done, gen_idx, gen_idx + 1)
            return ((nxt[:, None], positions, new_done, budget, gen_idx,
                     cache), nxt)
        carry0 = (tokens, positions, done, budget, gen_idx, cache)
        (_, _, _, _, _, cache), toks = jax.lax.scan(
            body, carry0, None, length=k_steps)
        return toks.T, cache                                 # (B, K)

    return program(loop, "decode_loop")


def _decode_horizon(req, decode_steps: int) -> int:
    """Pages to pre-claim for: the decode slots the next dispatch can
    write for ``req`` -- at most ``decode_steps``, capped by its
    remaining token budget (a row past its budget freezes on the
    parking page and writes nothing)."""
    return min(decode_steps,
               max(req.max_new_tokens - len(req.generated), 1))


class _PageTableCache:
    """Epoch-cached device page table: ``get`` re-uploads the (B, NP)
    table only when the scheduler epoch or the running-row order
    changed -- an unchanged (epoch, rows) pair means every row is
    bit-identical to the resident copy, so the cached device array is
    reused across dispatches (and across page handoffs on the decode
    worker, which keys on its runner's epoch the same way).  The (B,)
    slab table rides the same cache entry: a row's state-slab id can
    only change on the same transitions that bump the epoch."""

    def __init__(self):
        self.dev = None
        self.slab_dev = None
        self.epoch = -1
        self.rows: List[int] = []

    def get(self, running, epoch: int, b: int, n_pages_per_req: int):
        """-> (page table, slab table, uploaded?) for the rid-ordered
        batch."""
        rows = [req.rid for req in running]
        if self.dev is None or epoch != self.epoch or rows != self.rows:
            page_table = np.zeros((b, n_pages_per_req), np.int32)
            slab_table = np.zeros((b,), np.int32)
            for row, req in enumerate(running):
                page_table[row, :len(req.pages)] = req.pages
                if req.slab is not None:
                    slab_table[row] = req.slab
            self.dev = jnp.asarray(page_table)
            self.slab_dev = jnp.asarray(slab_table)
            self.epoch = epoch
            self.rows = rows
            return self.dev, self.slab_dev, True
        return self.dev, self.slab_dev, False


def _dispatch_decode_loop(loop, params, pool, running, b: int,
                          pt_cache: _PageTableCache, epoch: int,
                          n_pages_per_req: int, base_key):
    """Launch one K-step decode dispatch for the rid-ordered ``running``
    batch: build the (B,)-shaped host operands, fetch the epoch-cached
    page table, call the jitted loop (donating the pool cache) and park
    the updated leaves back on the pool.  Returns the in-flight dispatch
    record -- the (B, K) token buffer is still a device future, so the
    caller can overlap host work (the disaggregated engine runs a whole
    prefill chunk here) before syncing it with ``_apply_decode_tokens``.
    Shared by ``ContinuousEngine.step`` and the disaggregated
    ``DecodeWorker``; the batch-array layout and replay loop living in
    one place is what keeps their temperature-0 outputs bitwise equal."""
    tokens = np.zeros((b, 1), np.int32)
    positions = np.zeros((b,), np.int32)
    done = np.ones((b,), bool)           # padding rows stay dead
    budget = np.zeros((b,), np.int32)
    eos = np.full((b,), -1, np.int32)    # -1: matches no vocab id
    rids = np.zeros((b,), np.int32)
    gen_idx = np.zeros((b,), np.int32)
    for row, req in enumerate(running):
        tokens[row, 0] = req.next_token
        positions[row] = req.position
        done[row] = False
        budget[row] = req.max_new_tokens - len(req.generated)
        if req.eos_id is not None:
            eos[row] = req.eos_id
        rids[row] = req.rid
        gen_idx[row] = len(req.generated)
    dev_table, slab_table, uploaded = pt_cache.get(
        running, epoch, b, n_pages_per_req)
    toks_dev, new_cache = loop(
        params, jnp.asarray(tokens), jnp.asarray(positions),
        pool.device_state(), dev_table, slab_table, jnp.asarray(done),
        jnp.asarray(budget), jnp.asarray(eos), jnp.asarray(rids),
        jnp.asarray(gen_idx), base_key)
    pool.set_device_state(new_cache)
    return {"running": running, "budget": budget, "toks_dev": toks_dev,
            "uploaded": int(uploaded)}


def _apply_decode_tokens(disp, toks: np.ndarray, retire) -> List[int]:
    """Replay the device done-logic of a dispatch on host: walk each
    row's (K,) tokens until its budget or EOS froze it (later slots are
    frozen copies the scan never wrote anywhere live), retiring done
    requests through ``retire``.  Returns the tokens appended to each
    decoded request, in dispatch row order."""
    k_steps = toks.shape[1]
    appended = []
    for row, req in enumerate(disp["running"]):
        before = len(req.generated)
        for j in range(min(k_steps, int(disp["budget"][row]))):
            nxt = int(toks[row, j])
            req.generated.append(nxt)
            req.next_token = nxt
            if req.done:
                break
        appended.append(len(req.generated) - before)
        if req.done:
            retire(req)
    return appended


class _ChunkPrefillMixin:
    """Chunked paged prefill, shared verbatim by ``ContinuousEngine``
    and the disaggregated ``PrefillWorker`` (``serve/disagg.py``).  The
    host object provides: ``cfg``, ``params``, ``scheduler`` (and its
    ``pool``), ``page_size``, ``max_pages_per_req``,
    ``prefill_chunk_tokens``, ``prefill_context``, ``temperature``,
    ``_base_key``, the jitted ``_chunk_step`` / ``_chunk_step_paged``,
    the ``_prefill_ctx`` carry dict, a ``prefill_tokens_computed``
    counter and a ``_trace`` recorder.  One implementation is what makes the disaggregated
    engine's temperature-0 outputs bitwise the interleaved engine's:
    both prefill paths run the exact same chunk code."""

    def _empty_ctx(self, width: int = 0):
        # the family's own zero cache: dense/moe {"k","v"} stacks (with
        # distinct buffers -- k and v are donated independently to
        # _ctx_write, so they must not alias), rwkv the zero state
        # stack, hybrid the per-group mix of both
        return zoo.init_cache(self.cfg, 1, width)

    @property
    def _attn_key(self) -> str:
        """Sub-block key of the attention layer inside a hybrid group
        (``models.transformer._group_layout`` puts it mid-group)."""
        return f"b{self.cfg.attn_every // 2}"

    def _grow_ctx(self, ctx, kv, start: int, ln: int):
        """Fold one non-final chunk's cache into the prefill carry.
        KV planes GROW (dynamic-update-slice into a carry preallocated
        once at the prompt's page-rounded width); recurrent state is
        REPLACED wholesale (the chunk's final state is the whole
        context the next chunk needs)."""
        if not self.pool.has_kv:
            return kv                    # rwkv: state stack replaces
        if not self.pool.has_state:      # dense/moe: pure KV growth
            if ctx["k"].shape[2] == 0:
                # preallocate ONCE at the prompt's page-rounded
                # length; later chunks dynamic-update-slice into the
                # donated buffer.  (The first chunk always runs on
                # the width-0 ctx, so single-chunk prefills never
                # touch -- or trace -- the preallocated shape.)
                ctx = self._empty_ctx(
                    self.pool.pages_for(ln) * self.page_size)
            return {"k": _ctx_write(ctx["k"], kv["k"], jnp.int32(start)),
                    "v": _ctx_write(ctx["v"], kv["v"], jnp.int32(start))}
        # hybrid: the attention sub grows, the mamba subs replace
        ak = self._attn_key
        sub = ctx[ak]
        if sub["k"].shape[2] == 0:
            sub = self._empty_ctx(
                self.pool.pages_for(ln) * self.page_size)[ak]
        out = {k: v for k, v in kv.items() if k != ak}
        out[ak] = {"k": _ctx_write(sub["k"], kv[ak]["k"], jnp.int32(start)),
                   "v": _ctx_write(sub["v"], kv[ak]["v"], jnp.int32(start))}
        return out

    def _sample(self, lg: np.ndarray, req) -> int:
        """One token from one (V,) logit row -- the HOST twin of the
        device loop's fused sampler, used only for the first token at
        prefill completion.  Greedy matches jnp/np argmax tie-breaking;
        categorical draws from the same per-request stream
        ``fold_in(fold_in(base_key, rid), token_index)`` the device
        scan uses, so a request's sampled sequence does not depend on
        where (host or device) or in which dispatch a token fell."""
        if self.temperature <= 0:
            return int(np.argmax(lg))
        sub = jax.random.fold_in(
            jax.random.fold_in(self._base_key, req.rid), len(req.generated))
        return int(jax.random.categorical(
            sub, jnp.asarray(lg, jnp.float32) / self.temperature))

    def _forward_chunk(self, req, start: int, c: int, real: int):
        """Upload one chunk's tokens, run the chunk step against the
        request's prefilled context, scatter the chunk's quantized KV
        into its pages and grow (or, on completion, retire) the carry.
        Returns the chunk's logits, still on the device."""
        prefix = req.prefix
        ln = prefix.size
        stateful = self.pool.has_state
        toks = np.zeros((1, c), np.int32)
        toks[0, :real] = prefix[start:start + real]
        start_arr = jnp.full((1,), start, jnp.int32)
        if self.prefill_context == "pages":
            pt = np.zeros((1, self.max_pages_per_req), np.int32)
            pt[0, :len(req.pages)] = req.pages
            cache = self.pool.device_state()
            # (1, NP), untiled: the layer scan broadcasts it
            cache["page_table"] = jnp.asarray(pt)
            logits, new_cache = self._chunk_step_paged(
                self.params, jnp.asarray(toks), cache, start_arr)
            self.pool.set_device_state(
                {key: new_cache[key] for key in
                 ("k_codes", "v_codes", "k_scale", "v_scale")})
            return logits
        ctx = self._prefill_ctx.get(req.rid)
        if start == 0 or ctx is None:
            ctx = self._empty_ctx()
        logits, kv, chunk_q = self._chunk_step(
            self.params, jnp.asarray(toks), ctx, start_arr)
        if self.pool.has_kv:
            self.pool.write_chunk(
                chunk_q[self._attn_key] if stateful else chunk_q,
                req.pages, start)
        if start + real < ln:            # full chunk: extend the carry
            self._prefill_ctx[req.rid] = self._grow_ctx(ctx, kv, start, ln)
        elif stateful:
            # prefill completion writes the carried state into the
            # request's slab ONCE, quantized exactly like the static
            # oracle's post-prefill quantize_cache
            state_part = kv if not self.pool.has_kv else \
                {k: v for k, v in kv.items() if k != self._attn_key}
            self.pool.write_state(
                ssm.quantize_state(state_part, self.pool.kv_group), req.slab)
        return logits

    def _prefill_chunk(self, req) -> int:
        """Run at most ONE prefill chunk for ``req``: allocate the pages
        the chunk's slots land in (lazy, can preempt younger requests),
        forward the chunk against the request's prefilled context, and
        scatter its quantized KV into pages (the ``prefill_chunk``
        span).  Completes prefill (reads the first token's logits back,
        the ``prefill_sync`` span, samples it, PREFILLING -> RUNNING)
        when the chunk covers the prefix's last real token.  Returns the
        prefill tokens spent (the padded chunk width; 0 if ``req`` was
        preempted before any compute)."""
        sched = self.scheduler
        ln = req.prefix.size
        # the cursor starts past the matched shared pages of a prefix-
        # cache hit (page-aligned by construction), so a hit computes
        # only its un-cached remainder
        start = req.prefilled
        if self.pool.has_state:
            # stateful chunks are UNPADDED: every forwarded token runs
            # through the recurrent state, so pad tokens would corrupt
            # it (the KV scatter pads the trailing partial page block
            # inside write_chunk instead)
            c = ln - start if self.prefill_chunk_tokens is None \
                else min(self.prefill_chunk_tokens, ln - start)
        elif self.prefill_chunk_tokens is None:
            # monolithic: one chunk covering every remaining page slot
            c = self.pool.pages_for(ln) * self.page_size - start
        else:
            c = self.prefill_chunk_tokens
        real = min(c, ln - start)
        tr = self._trace
        with tr.span("prefill_chunk", rid=req.rid, start=start, real=real):
            if not sched.ensure_prefill_capacity(req, start + real):
                return 0                 # self-preempted: pool too dry
            logits = self._forward_chunk(req, start, c, real)
            req.prefilled = start + real
            self.prefill_tokens_computed += real
            tr.event("PREFILL_CHUNK", rid=req.rid, start=start, width=c,
                     real=real)
        if req.prefilled == ln:
            self._prefill_ctx.pop(req.rid, None)
            with tr.span("prefill_sync", rid=req.rid):
                # the first token's logit row: the prefill's device sync
                lg = jax.device_get(logits[0, real - 1])
            nxt = self._sample(lg, req)
            req.generated.append(nxt)
            req.next_token = nxt
            sched.prefill_complete(req)
        return c

    def _prefill_phase(self) -> List[Any]:
        """Chunked prefill, oldest first, inside the per-step token
        budget: at most ``prefill_chunk_tokens`` prefill tokens per step
        (None = whole prefixes, the monolithic behavior).  Returns the
        requests whose prefill COMPLETED this step (now RUNNING, first
        token sampled) and drops the bf16 carries of requests no longer
        mid-prefill (preempted or completed); a preemption victim
        re-prefills from chunk 0 on re-admission."""
        sched = self.scheduler
        budget = self.prefill_chunk_tokens
        spent = 0
        completed = []
        for req in [r for r in sched.running if r.status == PREFILLING]:
            while req.status == PREFILLING and \
                    (budget is None or spent < budget):
                spent += self._prefill_chunk(req)
            if req.status == RUNNING:
                completed.append(req)
        live = {r.rid for r in sched.running if r.status == PREFILLING}
        for rid in [r for r in self._prefill_ctx if r not in live]:
            del self._prefill_ctx[rid]
        return completed


@dataclasses.dataclass
class ContinuousEngine(_ChunkPrefillMixin):
    """Continuous-batching serving over a paged posit8 KV pool.

    The static ``ServeEngine`` batches a fixed set of requests against a
    dense ``max_len`` cache: every request pays worst-case KV memory and
    new arrivals wait for the whole batch.  This engine keeps ONE jitted
    decode step of shape ``max_batch`` alive and per step (a) ensures
    page capacity for the requests already running, (b) admits queued
    requests (FIFO, gated on unclaimed free pages), (c) prefills
    admitted requests in page-aligned CHUNKS inside a per-step token
    budget, (d) runs one batched paged decode for every running request
    at its OWN position, and (e) retires finished requests, returning
    their pages -- with LIFO preemption (free the youngest's pages,
    requeue it) when the pool runs dry.  See ``serve/scheduler.py`` for
    the policy and ``serve/paged_kv.py`` for the page layout and the
    chunk/page contract.

    Chunked paged prefill: ``prefill_chunk_tokens`` (a multiple of
    ``page_size`` that divides ``max_len``) bounds the prefill tokens
    one engine step may process, so a long-prompt arrival costs a chain
    of chunk-sized steps interleaved with decode instead of stalling
    every running request for a full prefill -- p99 DECODE-step latency
    is bounded by the chunk, not the longest prompt.  ``None`` (the
    default) prefills each admission in one whole-prefix chunk through
    the same code path (the PR 3 monolithic behavior).  The chunk's
    attention context is selected by ``prefill_context``:

      * ``"carry"`` (default): the already-prefilled prefix rides as a
        transient bf16 KV carry, so chunk logits -- and therefore
        temperature-0 tokens -- are BITWISE those of a monolithic
        prefill; the carry is dropped the moment prefill completes.
      * ``"pages"``: the chunk re-reads the prefix from its posit8
        pages (``attention.paged_prefill_blocked`` / the fused kernel
        under ``decode_impl='flash'``): zero extra residency, but the
        context is dequantized, so prompt logits carry quantization
        error and exact static parity is not guaranteed.

    Prefix caching (``prefix_cache=True``): whole prompt-prefix pages
    of completed prefills stay cached in the scheduler's
    ``PrefixIndex`` after retirement; a later request whose prompt
    starts with the same token blocks attaches them READ-ONLY and its
    chunk cursor starts past the match, so it computes -- and the
    admission gate budgets -- only the new pages (see the share/
    copy-on-write contract in ``serve/paged_kv.py``).  This implies
    ``prefill_context="pages"`` (the default under prefix caching):
    the remaining chunks must attend to the prefix THROUGH the shared
    posit8 pages, which hold bitwise the codes a cold run would write
    -- so temperature-0 outputs match a cache-off engine (also on the
    pages context) token for token.  The bf16 carry context cannot
    reproduce a prefix this request never forwarded, so
    ``prefill_context="carry"`` with ``prefix_cache=True`` is
    rejected.

    The KV plane is ALWAYS the posit8 paged pool (that is the point);
    weights pack per ``policy`` exactly like the static engine.  At
    temperature 0 with ``page_size == default_kv_block(max_len)`` of a
    static engine (and ``prefill_context="carry"``), outputs match
    per-request ``ServeEngine.generate`` token for token (the paged and
    contiguous block partitions -- and therefore the online-softmax
    accumulation order -- coincide, and chunked prefill replays the
    monolithic logits bitwise).
    """

    cfg: ModelConfig
    params: Any
    n_pages: int = 64
    page_size: Optional[int] = None
    max_batch: int = 8
    max_len: int = 512
    policy: Optional[PrecisionPolicy] = None
    temperature: float = 0.0
    eos_id: Optional[int] = None
    seed: int = 0
    prefill_chunk_tokens: Optional[int] = None
    # None resolves to "carry" (bitwise static parity), or to "pages"
    # under prefix_cache (shared pages are only readable through the
    # page table)
    prefill_context: Optional[str] = None
    prefix_cache: bool = False
    # decode iterations per jitted dispatch: one host round trip drives
    # K on-device decode+sample steps (positions bump on device; rows
    # that hit EOS / budget mid-scan freeze and re-map their writes to
    # the parking page).  Temperature-0 outputs are identical for every
    # K; K only trades host round trips against (at most K-1) wasted
    # tail iterations per dispatch.
    decode_steps: int = 1
    # state slabs of the pool (recurrent/hybrid families): every
    # admitted request holds exactly ONE for its whole lifetime, so the
    # default -- one per batch slot -- means slab capacity never gates
    # admission below max_batch.  Ignored for pure-attention families.
    n_state_slabs: Optional[int] = None
    # observability (docs/observability.md): an ``obs.TraceRecorder``
    # capturing lifecycle events + step spans, or None for the shared
    # no-op recorder -- telemetry is host-side bookkeeping only, so
    # temperature-0 outputs are bitwise identical with tracing on or
    # off.  ``profile_annotations`` turns on the recorder's ``profile``
    # switch (making a recorder if none was given): every span is also
    # a JAX profiler annotation, so device profiles carry the engine's
    # phase names on their own clock.
    trace: Any = None
    profile_annotations: bool = False
    # runtime transfer guard (bench/test harness hook): when True, the
    # decode dispatch+sync windows run under a fresh
    # ``jax.transfer_guard("disallow")`` so any IMPLICIT host<->device
    # transfer on the decode critical path raises instead of silently
    # serializing.  Off by default: the first dispatch of a fresh
    # engine may legitimately move trace-time constants; benches flip
    # it on after warm-up (the steady-state window the discipline
    # governs).
    transfer_guard: bool = False

    # every public run counter; ``reset_counters`` and ``__post_init__``
    # derive from this registry, so adding a counter here is the WHOLE
    # change (the bench warm-up reset can never miss one again)
    _COUNTERS = (
        "steps_run",
        "prefill_tokens_computed",  # real tokens forwarded (cache hits
        #                             skip their matched prefix)
        "decode_dispatches",        # jitted decode-loop calls
        "page_table_uploads",       # (B, NP) host->device uploads
        "logits_host_bytes",        # device->host logits traffic
        #                             (stays 0: sampling is fused)
        "token_host_bytes",         # device->host sampled-token sync
    )

    def __post_init__(self):
        from ..kernels.flash_decode import default_kv_block
        from .paged_kv import PagedKVPool
        from .scheduler import Scheduler
        if self.cfg.frontend != "none":
            raise ValueError(
                "ContinuousEngine serves token prompts; vision/audio "
                "frontends need per-request frame/patch embeddings the "
                "request queue does not carry")
        if self.policy is not None:
            self.params = zoo.pack_params(self.params, self.policy)
        kv_group = self.policy.group_size if self.policy else None
        if self.page_size is None:
            self.page_size = default_kv_block(self.max_len)
        if self.max_len % self.page_size:
            rounded = -(-self.max_len // self.page_size) * self.page_size
            raise ValueError(
                f"max_len={self.max_len} must be a multiple of "
                f"page_size={self.page_size}: the page-table row maps "
                f"whole pages, so a partial final page cannot be "
                f"addressed -- round max_len up to {rounded} (what "
                f"launch/serve.py does) or pick a page size that "
                f"divides it")
        self.max_pages_per_req = self.max_len // self.page_size
        if self.prefill_chunk_tokens is not None:
            c = self.prefill_chunk_tokens
            if c <= 0 or c % self.page_size or self.max_len % c:
                raise ValueError(
                    f"prefill_chunk_tokens={c} must be a positive "
                    f"multiple of page_size={self.page_size} that "
                    f"divides max_len={self.max_len} (the chunk/page "
                    f"contract of serve/paged_kv.py)")
        kinds = PagedKVPool.page_kinds(self.cfg)  # rejects unknown families
        if self.prefill_context is None:
            self.prefill_context = "pages" if self.prefix_cache else "carry"
        if self.prefill_context not in ("carry", "pages"):
            raise ValueError(self.prefill_context)
        if "state" in kinds and self.prefill_context == "pages":
            raise ValueError(
                f"family {self.cfg.family!r} carries recurrent state, "
                f"which never lands in pages and cannot be re-read "
                f"through a page table: serve it with "
                f"prefill_context='carry' (which also rules out "
                f"prefix_cache -- a cached prefix cannot reproduce the "
                f"state of tokens this request never forwarded)")
        if self.prefix_cache and self.prefill_context == "carry":
            raise ValueError(
                "prefix_cache shares posit8 pages a hit request never "
                "forwarded itself, so its chunks can only attend to the "
                "prefix THROUGH the page table: use "
                "prefill_context='pages' (the default when prefix_cache "
                "is set)")
        if self.decode_steps < 1:
            raise ValueError(
                f"decode_steps={self.decode_steps} must be >= 1")
        # one registry spans every layer of this engine; the recorder
        # defaults to the shared no-op (one predicted branch per call)
        self.metrics = MetricRegistry()
        self._trace = _recorder(self.trace, self.profile_annotations,
                                self.metrics)
        bind_counters(self, self.metrics, "engine")
        n_slabs = 0
        if "state" in kinds:
            n_slabs = self.n_state_slabs \
                if self.n_state_slabs is not None else self.max_batch
        pool = PagedKVPool(self.cfg, self.n_pages, self.page_size, kv_group,
                           n_slabs=n_slabs)
        pool.register_gauges(self.metrics, "pool")
        self.scheduler = Scheduler(pool, self.max_batch,
                                   max_pages_per_req=self.max_pages_per_req,
                                   prefix_cache=self.prefix_cache,
                                   registry=self.metrics, trace=self._trace)
        # closed-form cache traffic of the LAST decode dispatch, per
        # page kind (the same models bench_serve ties against measured
        # bytes): KV pages + state slabs combined, and the state term
        # alone -- 2x slab bytes (read + rewrite) per live request,
        # independent of position
        self.metrics.gauge(
            "engine/kv_bytes_per_step_model",
            fn=lambda: self.pool.modeled_bytes_per_step(self.last_positions)
            if self.last_positions else 0.0)
        from .paged_kv import state_slab_bytes
        self.metrics.gauge(
            "engine/state_bytes_per_step_model",
            fn=lambda: 2.0 * state_slab_bytes(self.cfg, kv_group)
            * len(self.last_positions) if self.pool.has_state else 0.0)
        # compile-count sentinel: every jitted entry point is wrapped
        # with a tracing counter BEFORE jax.jit, so
        # ``trace_counts[name]`` counts (re)traces -- bench_serve
        # snapshots this after warm-up and asserts it stays flat across
        # the measured run (zero steady-state recompiles)
        self.trace_counts: Dict[str, int] = {}
        # chunk prefill steps: FULL chunk logits (the request's last real
        # token may sit anywhere inside the final chunk)
        self._chunk_step = jax.jit(_trace_counted(
            build_prefill_chunk_step(self.cfg, kv_group),
            self.trace_counts, "prefill_chunk"))
        # the paged-context variant is attention-only (the builder
        # rejects stateful families), so it exists only when selected
        self._chunk_step_paged = None
        if self.prefill_context == "pages":
            self._chunk_step_paged = jax.jit(_trace_counted(
                build_prefill_chunk_step(self.cfg, kv_group, paged=True),
                self.trace_counts, "prefill_chunk_paged"),
                donate_argnums=(2,))
        # per-request bf16 KV carries of requests mid-prefill (rid ->
        # {"k","v"} stacked (L,1,T,Kh,Dh)); dropped on completion or
        # preemption.  Bounded by the prefix of the few PREFILLING
        # requests -- the same transient a monolithic prefill held.
        self._prefill_ctx: Dict[int, Any] = {}

        # the device-resident K-step decode dispatch (fused sampling +
        # lax.scan over decode_steps iterations); only the pool cache
        # (arg 3) is donated -- the epoch-cached page table must stay
        # resident across dispatches
        self._decode_loop = jax.jit(_trace_counted(
            _build_decode_loop(self.cfg, self.temperature,
                               self.decode_steps),
            self.trace_counts, "decode_loop"),
            donate_argnums=(3,))
        self._base_key = jax.random.PRNGKey(self.seed)
        # epoch-cached device page table: re-uploaded only when the
        # scheduler epoch or the running-row order changed
        self._pt_cache = _PageTableCache()
        # positions the LAST decode dispatch started from (requests that
        # retired within the step included) -- the per-step KV-traffic
        # ground truth benchmarks read; [] when the step decoded nothing
        self.last_positions: List[int] = []
        # rids admitted by the LAST step (regression hook: a rid must
        # never show up in scheduler.preempted_log during the same step)
        self.last_admitted: List[int] = []

    @property
    def pool(self):
        return self.scheduler.pool

    # -- request intake -----------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               eos_id: Optional[int] = None) -> int:
        """Queue one request; returns its id.  Total length must fit the
        per-request page-table width (``max_len`` slots) -- validated by
        the scheduler, which knows the row width, so a direct scheduler
        user gets the same rejection at the same point."""
        return self.scheduler.submit(
            prompt, max_new_tokens,
            eos_id if eos_id is not None else self.eos_id)

    # -- one engine step ----------------------------------------------------

    def step(self) -> int:
        """One engine step: capacity for the running batch FIRST, then
        admission, chunked prefill within the token budget, ONE
        device-resident decode dispatch (``decode_steps`` fused
        decode+sample iterations) for everyone running, retirement.
        Returns decoded request count.

        The ordering is load-bearing: PR 3 admitted (and fully
        prefilled) newcomers BEFORE ensuring capacity for the running
        batch, so under pool pressure the newcomer took the last free
        page, was immediately preempted as the youngest victim, and its
        whole prefill was wasted -- every step while the pressure
        lasted.  Capacity-first means a newcomer is only admitted
        against pages the running batch did not need this step."""
        sched = self.scheduler
        tr = self._trace
        with tr.span("step"):
            # (1) grow the already-running requests' page tables
            # (pre-claim the whole decode_steps window: no page can be
            # missing mid-scan)
            with tr.span("capacity"):
                for req in list(sched.running):
                    if req.status == RUNNING:  # a victim may drop mid-loop
                        sched.ensure_capacity(
                            req,
                            horizon=_decode_horizon(req, self.decode_steps))
            # (2) admit against the unclaimed remainder
            with tr.span("admit"):
                self.last_admitted = [r.rid for r in sched.admit()]
            # (3) chunked prefill within the token budget; a request
            # whose whole budget fit the prefill (budget of 1 / instant
            # EOS) retires without ever reaching decode
            with tr.span("prefill"):
                for req in self._prefill_phase():
                    if req.done:
                        sched.retire(req)
            # (4) ONE batched K-step decode dispatch for everyone
            # RUNNING (newly promoted requests may still need pages
            # their decode window writes -- their admission gate already
            # reserved budget for the first write, so this never
            # preempts a same-step admission)
            with tr.span("decode_prepare"):
                running = []
                for req in list(sched.running):
                    if req.status == RUNNING and sched.ensure_capacity(
                            req, horizon=_decode_horizon(
                                req, self.decode_steps)):
                        running.append(req)
                self.last_positions = [req.position for req in running]
            if not running:
                return 0
            with tr.span("decode_dispatch"), \
                    _device_only(self.transfer_guard):
                disp = _dispatch_decode_loop(
                    self._decode_loop, self.params, self.pool, running,
                    self.max_batch, self._pt_cache, sched.epoch,
                    self.max_pages_per_req, self._base_key)
            self.decode_dispatches += 1
            self.page_table_uploads += disp["uploaded"]
            tr.event("DECODE_DISPATCH", batch=len(running),
                     k=self.decode_steps, uploaded=disp["uploaded"],
                     positions=self.last_positions)
            with tr.span("decode_sync"), _device_only(self.transfer_guard):
                # the ONE sanctioned (B, K) host sync of the step
                toks = jax.device_get(disp["toks_dev"])
            self.token_host_bytes += toks.nbytes
            with tr.span("decode_apply"):
                appended = _apply_decode_tokens(disp, toks, sched.retire)
                tr.event("DECODE_SYNC", token_bytes=toks.nbytes,
                         tokens=appended)
            self.steps_run += 1
            return len(appended)

    # -- counters -----------------------------------------------------------

    def reset_counters(self) -> None:
        """Zero every run counter (bench warm-up hygiene: a warm request
        must not leak its pages/steps/preemptions into the measured
        run).  Every layer zeroes its OWN ``_COUNTERS`` registry --
        engine, scheduler, prefix index -- so a counter added to any of
        them resets without this method changing.  The pool's CURRENT
        allocation -- e.g. prefix pages the warm-up left cached --
        becomes the new peak baseline."""
        for c in self._COUNTERS:
            setattr(self, c, 0)
        self.pool.alloc_peak = self.pool.used_pages
        self.scheduler.reset_counters()
        # registry-wide sweep: clears span/SLO histograms too (callback
        # gauges are live reads and have nothing to reset)
        self.metrics.reset()

    # -- drive to completion ------------------------------------------------

    def run(self, max_steps: int = 100000) -> Dict[int, np.ndarray]:
        """Step until every submitted request finished; returns
        {rid: prompt+generated}.  Admission can always make progress
        when nothing is running (all pages are free then), so the step
        bound only guards against bugs."""
        steps = 0
        while self.scheduler.has_work:
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError("continuous engine failed to drain")
        return {rid: req.output
                for rid, req in self.scheduler.finished.items()}
