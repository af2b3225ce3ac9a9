"""Disaggregated prefill/decode serving over a posit8 page handoff.

The interleaved ``ContinuousEngine`` time-slices ONE device program
between two workloads with opposite rooflines: prefill (compute-bound
-- big matmuls over whole chunks) and decode (memory-bound -- one token
per request against the paged KV pool).  Even with chunked prefill
bounding the stall, every prefill chunk still sits INSIDE the decode
step's critical path: a long-prompt arrival inflates decode p99 by a
chunk forward pass.

This module splits the engine along that roofline boundary:

  ``PrefillWorker``   owns its own posit8 page pool + the PR 4 chunk-
                      budget admitter (admission, chunk pacing, prefix-
                      cache hits, mid-prefill preemption).  When a
                      request's prefill completes, its pages are
                      EXPORTED -- posit8 codes + po2 group scales, the
                      wire format IS the pool format -- and the request
                      parks until the handoff channel has room.
  ``PageHandoffChannel``
                      a depth-bounded (default 2: double-buffered)
                      queue of ``(request, payload)`` pairs.  The
                      payload is the gathered page leaves -- ~4x
                      smaller than a bf16 KV handoff
                      (``paged_kv.page_handoff_bytes`` is the exact
                      per-page model) -- plus, for recurrent families,
                      the request's quantized state slab
                      (``export_state``), which crosses bitwise and
                      makes the handoff exact for SSM/RWKV/hybrid
                      requests too -- optionally ``device_put`` to
                      the decode worker's device slice so the copy
                      overlaps whatever both workers are computing.
  ``DecodeWorker``    owns its own pool + the K-step device-resident
                      decode loop of PR 6, running UNINTERRUPTED: no
                      prefill chunk ever executes between its
                      dispatches.  Imported pages scatter bitwise into
                      its pool; the ``DecodeRunner`` keeps the mapping-
                      epoch protocol, so the page table stays cached
                      across handoffs that do not change the batch.

``DisaggEngine.step`` overlaps the two: the decode dispatch is launched
FIRST (JAX dispatch is async -- the jitted loop runs on device while
host code continues), the prefill worker then runs a full admit/chunk/
handoff step, and only afterwards does the engine sync the decode
dispatch's (B, K) token buffer.  Prefill chunks for request A hide
behind decode iterations for requests B..Z; ``last_decode_step_s``
times ONLY the dispatch+sync halves, which is the decode-latency
isolation the split buys (bench_serve's ``disagg`` scenario asserts
its p99 against the interleaved engine's).

Backpressure is structural, not configured: a completed prefill parks
holding its prefill pages AND its admitter batch slot until the channel
drains, a full channel blocks further exports, and a handoff stays
queued until the decode pool can allocate its pages.  When the decode
pool runs dry mid-decode the runner BOUNCES its youngest request --
pages freed, request handed back to the admitter's queue FRONT
(``Scheduler.reaccept``), where it re-prefills prompt+generated and
re-crosses the channel: the disaggregated analogue of LIFO preemption.
``submit`` rejects requests whose total footprint cannot fit the decode
pool, so a lone bounced request always fits on retry (no livelock).

PARITY: at temperature 0 the disaggregated engine's outputs are
token-for-token those of the interleaved ``ContinuousEngine`` (same
chunk code via ``_ChunkPrefillMixin``, same dispatch/replay code via
``_dispatch_decode_loop``/``_apply_decode_tokens``, bitwise page
export/import) and -- on the carry prefill context -- of per-request
static ``ServeEngine.generate``, including across mid-prefill
preemption and prefix-cache hits.  ``tests/test_disagg.py`` pins all
three leg pairs.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core.policy import PrecisionPolicy
from ..models import zoo
from ..obs import MetricRegistry, NULL_RECORDER, bind_counters
from .engine import (_build_decode_loop, _ChunkPrefillMixin,
                     _apply_decode_tokens, _decode_horizon,
                     _device_only, _dispatch_decode_loop, _PageTableCache,
                     _recorder, _trace_counted, build_prefill_chunk_step)
from .paged_kv import PagedKVPool
from .scheduler import RUNNING, DecodeRunner, Request, Scheduler

__all__ = ["PageHandoffChannel", "PrefillWorker", "DecodeWorker",
           "DisaggEngine"]


class PageHandoffChannel:
    """Depth-bounded queue of completed prefills crossing from the
    prefill worker to the decode worker.

    Each entry is ``(request, payload)`` where the payload is the
    request's gathered pool leaves (posit8 codes + bf16 po2 scales,
    ``PagedKVPool.export_pages``) -- the handoff moves the COMPRESSED
    cache, never a bf16 one.  Attention-only families push the flat
    page-leaf dict; stateful families push the nested
    ``{"state": export_state(slab)[, "kv": export_pages(pages)]}``
    form, so a recurrent request's whole footprint -- its one slab,
    plus KV pages for hybrids -- crosses in one entry and imports
    bitwise.  ``depth`` bounds the prefills in flight
    (default 2: the decode side imports one buffer while the prefill
    side fills the next); a full channel parks further completions on
    the prefill side, holding their pages and batch slots -- the
    backpressure that keeps the admitter from racing ahead of decode.

    With ``device`` set, ``push`` copies the payload to the decode
    worker's device slice immediately, so the transfer overlaps both
    workers' compute instead of serializing into the import."""

    _COUNTERS = ("handoffs",        # payloads pushed
                 "handoff_pages",   # pages moved
                 "handoff_bytes")   # device bytes moved (sum of .nbytes)

    def __init__(self, depth: int = 2, device=None,
                 registry: Optional[MetricRegistry] = None,
                 trace=None, namespace: str = "channel"):
        assert depth >= 1, depth
        self.depth = int(depth)
        self.device = device
        self._q: Deque[Tuple[Request, Dict[str, jax.Array]]] = deque()
        self.metrics = registry if registry is not None else MetricRegistry()
        self._trace = trace if trace is not None else NULL_RECORDER
        bind_counters(self, self.metrics, namespace)

    def reset_counters(self) -> None:
        for c in self._COUNTERS:
            setattr(self, c, 0)

    def __len__(self) -> int:
        return len(self._q)

    @property
    def full(self) -> bool:
        return len(self._q) >= self.depth

    def push(self, req: Request, payload: Dict[str, jax.Array]) -> None:
        assert not self.full, "push on a full channel (check .full first)"
        rid = getattr(req, "rid", None)
        with self._trace.span("channel_push", rid=rid):
            if self.device is not None:
                payload = jax.tree.map(
                    lambda val: jax.device_put(val, self.device), payload)
        kv = payload.get("kv") if "state" in payload else payload
        pages = int(kv["k_codes"].shape[1]) if kv is not None else 0
        nbytes = sum(int(leaf.nbytes) for leaf in jax.tree.leaves(payload))
        self.handoffs += 1
        self.handoff_pages += pages
        self.handoff_bytes += nbytes
        self._trace.event("HANDOFF", rid=rid, pages=pages, bytes=nbytes)
        self._q.append((req, payload))

    def peek(self) -> Tuple[Request, Dict[str, jax.Array]]:
        return self._q[0]

    def pop(self) -> Tuple[Request, Dict[str, jax.Array]]:
        return self._q.popleft()


class PrefillWorker(_ChunkPrefillMixin):
    """The prefill half: PR 4's chunk-budget admitter over its own
    posit8 pool, exporting completed prefills into the handoff channel.

    Runs the EXACT interleaved chunk code (``_ChunkPrefillMixin``):
    admission, lazy page claims, carry/pages contexts, prefix-cache
    hits and mid-prefill preemption all behave as they do in
    ``ContinuousEngine`` -- that shared implementation is the parity
    argument's first half.  A completed prefill (first token sampled,
    PREFILLING -> RUNNING) parks on ``_ready`` until the channel has
    room; parked requests still hold their pages and admitter slots
    (structural backpressure) and remain legal preemption victims -- a
    preempted parked request simply drops off ``_ready`` and
    re-completes after its re-prefill, like any RUNNING victim."""

    _COUNTERS = ("prefill_tokens_computed",)

    def __init__(self, cfg: ModelConfig, params: Any, n_pages: int,
                 page_size: int, max_batch: int, max_pages_per_req: int,
                 kv_group: Optional[int], temperature: float, base_key,
                 prefill_chunk_tokens: Optional[int], prefill_context: str,
                 prefix_cache: bool, device=None,
                 registry: Optional[MetricRegistry] = None, trace=None):
        self.cfg = cfg
        self.params = params
        self.page_size = page_size
        self.max_pages_per_req = max_pages_per_req
        self.temperature = temperature
        self._base_key = base_key
        self.prefill_chunk_tokens = prefill_chunk_tokens
        self.prefill_context = prefill_context
        self.metrics = registry if registry is not None else MetricRegistry()
        self._trace = trace if trace is not None else NULL_RECORDER
        n_slabs = max_batch \
            if "state" in PagedKVPool.page_kinds(cfg) else 0
        pool = PagedKVPool(cfg, n_pages, page_size, kv_group,
                           n_slabs=n_slabs)
        if device is not None:
            pool.set_device_state(jax.tree.map(
                lambda leaf: jax.device_put(leaf, device),
                pool.device_state()))
        pool.register_gauges(self.metrics, "prefill/pool")
        self.scheduler = Scheduler(pool, max_batch,
                                   max_pages_per_req=max_pages_per_req,
                                   prefix_cache=prefix_cache,
                                   registry=self.metrics, trace=self._trace,
                                   namespace="prefill/scheduler")
        self.trace_counts: Dict[str, int] = {}
        self._chunk_step = jax.jit(_trace_counted(
            build_prefill_chunk_step(cfg, kv_group),
            self.trace_counts, "prefill_chunk"))
        # the paged context re-reads the prefix through the page table,
        # which stateful families cannot do (their context is the
        # recurrent state) -- DisaggEngine rejects that combination, so
        # only build the paged step when it will actually be called
        self._chunk_step_paged = None
        if prefill_context == "pages":
            self._chunk_step_paged = jax.jit(_trace_counted(
                build_prefill_chunk_step(cfg, kv_group, paged=True),
                self.trace_counts, "prefill_chunk_paged"),
                donate_argnums=(2,))
        self._prefill_ctx: Dict[int, Any] = {}
        self._ready: List[Request] = []       # completed, awaiting channel
        bind_counters(self, self.metrics, "prefill")

    @property
    def pool(self) -> PagedKVPool:
        return self.scheduler.pool

    def reset_counters(self) -> None:
        for c in self._COUNTERS:
            setattr(self, c, 0)
        self.pool.alloc_peak = self.pool.used_pages
        self.scheduler.reset_counters()

    def _drain_ready(self, channel: PageHandoffChannel) -> int:
        """Export parked completions into the channel, oldest first,
        until it fills.  Export before release: ``export_pages`` /
        ``export_state`` are pure functional gathers, so the payload
        stays valid after the source pages (and slab) return to the
        free lists (prefix-shared pages just decref back to the
        index).  Stateful families export the nested form the channel
        and decode worker understand: the request's slab, plus its KV
        pages for hybrids."""
        sent = 0
        while self._ready:
            req = self._ready[0]
            if req.status != RUNNING:
                # preempted while parked: the admitter already freed its
                # pages and requeued it; it re-parks after re-prefill
                self._ready.pop(0)
                continue
            if channel.full:
                break
            if self.pool.has_state:
                payload: Dict = {"state": self.pool.export_state(req.slab)}
                if req.pages:
                    payload["kv"] = self.pool.export_pages(req.pages)
            else:
                payload = self.pool.export_pages(req.pages)
            self.scheduler.release(req)
            channel.push(req, payload)
            self._ready.pop(0)
            sent += 1
        return sent

    def step(self, channel: PageHandoffChannel) -> int:
        """One prefill-side step: drain parked completions (channel
        room may have opened since last step), admit, run the chunk
        budget, park/retire this step's completions, drain again.
        Returns handoffs pushed."""
        sent = self._drain_ready(channel)
        for req in self.scheduler.admit():
            if req.status == RUNNING:
                # a resumed preemption/bounce snapshot: its state (+ KV)
                # just imported bitwise, nothing to prefill -- park it
                # for re-handoff straight away
                self._ready.append(req)
        for req in self._prefill_phase():
            if req.done:
                # budget of 1 / instant EOS: never needs a decode side
                self.scheduler.retire(req)
            else:
                self._ready.append(req)
        return sent + self._drain_ready(channel)


class DecodeWorker:
    """The decode half: PR 6's K-step device-resident loop over its own
    posit8 pool, fed exclusively by imported page handoffs.

    ``dispatch``/``sync`` are split so the engine can overlap host work
    with the device scan: ``dispatch`` launches the jitted loop (async)
    and returns the in-flight record; ``sync`` blocks on the (B, K)
    token buffer and replays the done-logic.  Both run the SAME
    ``_dispatch_decode_loop``/``_apply_decode_tokens`` code as the
    interleaved engine -- the parity argument's second half."""

    _COUNTERS = ("decode_dispatches",   # jitted decode-loop calls
                 "page_table_uploads",  # (B, NP) host->device uploads
                 "logits_host_bytes",   # stays 0: sampling is fused
                 "token_host_bytes")    # device->host sampled-token sync

    def __init__(self, cfg: ModelConfig, params: Any, n_pages: int,
                 page_size: int, max_batch: int, max_pages_per_req: int,
                 kv_group: Optional[int], temperature: float, base_key,
                 decode_steps: int, device=None,
                 registry: Optional[MetricRegistry] = None, trace=None):
        self.params = params
        self.max_batch = max_batch
        self.max_pages_per_req = max_pages_per_req
        self.decode_steps = decode_steps
        self._base_key = base_key
        self.metrics = registry if registry is not None else MetricRegistry()
        self._trace = trace if trace is not None else NULL_RECORDER
        n_slabs = max_batch \
            if "state" in PagedKVPool.page_kinds(cfg) else 0
        pool = PagedKVPool(cfg, n_pages, page_size, kv_group,
                           n_slabs=n_slabs)
        if device is not None:
            pool.set_device_state(jax.tree.map(
                lambda leaf: jax.device_put(leaf, device),
                pool.device_state()))
        pool.register_gauges(self.metrics, "decode/pool")
        self.runner = DecodeRunner(pool, max_batch,
                                   registry=self.metrics, trace=self._trace,
                                   namespace="decode/runner")
        # compile-count sentinel + transfer-guard hook: same contract
        # as ContinuousEngine (see engine._trace_counted/_device_only);
        # benches flip ``transfer_guard`` on after warm-up
        self.trace_counts: Dict[str, int] = {}
        self.transfer_guard = False
        self._decode_loop = jax.jit(_trace_counted(
            _build_decode_loop(cfg, temperature, decode_steps),
            self.trace_counts, "decode_loop"),
            donate_argnums=(3,))
        self._pt_cache = _PageTableCache()
        self.last_positions: List[int] = []
        bind_counters(self, self.metrics, "decode")

    @property
    def pool(self) -> PagedKVPool:
        return self.runner.pool

    def reset_counters(self) -> None:
        for c in self._COUNTERS:
            setattr(self, c, 0)
        self.pool.alloc_peak = self.pool.used_pages
        self.runner.reset_counters()

    def admit_handoffs(self, channel: PageHandoffChannel) -> int:
        """Import queued handoffs while a batch slot AND pool pages are
        available.  A handoff the pool cannot place stays queued (the
        channel is the buffer) -- head-of-line blocking here is the
        deliberate backpressure that eventually parks the prefill side
        rather than thrashing decode with bounces."""
        took = 0
        while len(channel) and self.runner.has_slot:
            req, payload = channel.peek()
            nested = "state" in payload
            kv = payload.get("kv") if nested else payload
            n = int(kv["k_codes"].shape[1]) if kv is not None else 0
            pages = self.pool.alloc(n) if n else []
            if pages is None:
                break                     # decode pool dry: retry next step
            slab = None
            if nested:
                slab = self.pool.alloc_slab()
                if slab is None:          # state plane dry: roll back
                    if pages:
                        self.pool.free(pages)
                    break
            with self._trace.span("channel_pull", rid=req.rid):
                if kv is not None:
                    self.pool.import_pages(kv, pages)
                if nested:
                    self.pool.import_state(payload["state"], slab)
            self.runner.accept(req, pages, slab)
            channel.pop()
            took += 1
        return took

    def dispatch(self):
        """Launch one K-step decode dispatch for everyone running (after
        pre-claiming each request's decode window, bouncing the youngest
        on pool exhaustion).  Returns the in-flight dispatch record, or
        None if nothing decoded."""
        runner = self.runner
        tr = self._trace
        with tr.span("decode_prepare"):
            running = []
            for req in list(runner.running):
                if req.status == RUNNING and runner.ensure_capacity(
                        req, horizon=_decode_horizon(req, self.decode_steps)):
                    running.append(req)
            self.last_positions = [req.position for req in running]
        if not running:
            return None
        with tr.span("decode_dispatch"), _device_only(self.transfer_guard):
            disp = _dispatch_decode_loop(
                self._decode_loop, self.params, self.pool, running,
                self.max_batch, self._pt_cache, runner.epoch,
                self.max_pages_per_req, self._base_key)
        self.decode_dispatches += 1
        self.page_table_uploads += disp["uploaded"]
        tr.event("DECODE_DISPATCH", batch=len(running), k=self.decode_steps,
                 uploaded=disp["uploaded"], positions=self.last_positions)
        return disp

    def sync(self, disp) -> int:
        """Block on a dispatch's (B, K) token buffer and replay the
        device done-logic; retires finished requests to the runner.
        Returns decoded request count."""
        if disp is None:
            return 0
        tr = self._trace
        with tr.span("decode_sync"), _device_only(self.transfer_guard):
            # the ONE sanctioned (B, K) host sync of the decode side
            toks = jax.device_get(disp["toks_dev"])
        self.token_host_bytes += toks.nbytes
        with tr.span("decode_apply"):
            appended = _apply_decode_tokens(disp, toks, self.runner.retire)
            tr.event("DECODE_SYNC", token_bytes=toks.nbytes, tokens=appended)
        return len(appended)


@dataclasses.dataclass
class DisaggEngine:
    """Disaggregated prefill/decode serving engine (see module doc).

    Drop-in for ``ContinuousEngine`` at the submit/step/run level; the
    pool splits into ``prefill_pages`` + ``decode_pages`` (two pools,
    two device programs) and ``channel_depth`` bounds the prefills in
    flight across the handoff.  ``last_decode_step_s`` is the previous
    step's decode-side wall time EXCLUDING the overlapped prefill work
    -- the isolation metric the split exists for."""

    cfg: ModelConfig
    params: Any
    prefill_pages: int = 64
    decode_pages: int = 64
    page_size: Optional[int] = None
    max_batch: int = 8
    max_len: int = 512
    policy: Optional[PrecisionPolicy] = None
    temperature: float = 0.0
    eos_id: Optional[int] = None
    seed: int = 0
    prefill_chunk_tokens: Optional[int] = None
    prefill_context: Optional[str] = None
    prefix_cache: bool = False
    decode_steps: int = 1
    channel_depth: int = 2
    # distinct device slices for the two workers (parallel/sharding.py
    # ``split_devices``); None/None runs both programs on the default
    # device -- the dispatch-async overlap still applies
    prefill_device: Any = None
    decode_device: Any = None
    # observability (docs/observability.md): see ``ContinuousEngine``
    trace: Any = None
    profile_annotations: bool = False

    _COUNTERS = ("steps_run",)

    def __post_init__(self):
        from ..kernels.flash_decode import default_kv_block
        if self.cfg.frontend != "none":
            raise ValueError(
                "DisaggEngine serves token prompts; vision/audio "
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
                f"page_size={self.page_size} (round up to {rounded})")
        self.max_pages_per_req = self.max_len // self.page_size
        if self.prefill_chunk_tokens is not None:
            c = self.prefill_chunk_tokens
            if c <= 0 or c % self.page_size or self.max_len % c:
                raise ValueError(
                    f"prefill_chunk_tokens={c} must be a positive "
                    f"multiple of page_size={self.page_size} that "
                    f"divides max_len={self.max_len}")
        if self.prefill_context is None:
            self.prefill_context = "pages" if self.prefix_cache else "carry"
        if self.prefill_context not in ("carry", "pages"):
            raise ValueError(self.prefill_context)
        if "state" in PagedKVPool.page_kinds(self.cfg) \
                and self.prefill_context == "pages":
            raise ValueError(
                f"family {self.cfg.family!r} carries recurrent state, "
                f"which never lands in pages and cannot be re-read "
                f"through a page table: serve it with "
                f"prefill_context='carry' (which also rules out "
                f"prefix_cache -- a cached prefix cannot reproduce the "
                f"state of tokens this request never forwarded)")
        if self.prefix_cache and self.prefill_context == "carry":
            raise ValueError(
                "prefix_cache needs prefill_context='pages' (shared "
                "posit8 pages are only readable through the page table)")
        if self.decode_steps < 1:
            raise ValueError(
                f"decode_steps={self.decode_steps} must be >= 1")
        base_key = jax.random.PRNGKey(self.seed)
        # one registry + recorder spans the engine and both workers
        self.metrics = MetricRegistry()
        self._trace = _recorder(self.trace, self.profile_annotations,
                                self.metrics)
        bind_counters(self, self.metrics, "engine")
        params_p = self.params if self.prefill_device is None else \
            jax.device_put(self.params, self.prefill_device)
        params_d = self.params if self.decode_device is None else \
            jax.device_put(self.params, self.decode_device)
        self.prefill = PrefillWorker(
            self.cfg, params_p, self.prefill_pages, self.page_size,
            self.max_batch, self.max_pages_per_req, kv_group,
            self.temperature, base_key, self.prefill_chunk_tokens,
            self.prefill_context, self.prefix_cache,
            device=self.prefill_device,
            registry=self.metrics, trace=self._trace)
        self.decode = DecodeWorker(
            self.cfg, params_d, self.decode_pages, self.page_size,
            self.max_batch, self.max_pages_per_req, kv_group,
            self.temperature, base_key, self.decode_steps,
            device=self.decode_device,
            registry=self.metrics, trace=self._trace)
        self.channel = PageHandoffChannel(self.channel_depth,
                                          device=self.decode_device,
                                          registry=self.metrics,
                                          trace=self._trace)
        # decode-side critical path (dispatch + sync, prefill hidden):
        # the per-step sample behind ``last_decode_step_s``
        self._step_hist = self.metrics.histogram("engine/decode_step_ms")
        self.last_decode_step_s = 0.0

    # -- request intake -----------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               eos_id: Optional[int] = None) -> int:
        """Queue one request; returns its id.  Beyond the admitter's own
        checks, the request's TOTAL footprint must fit the decode pool
        alone: a bounced request retries against an otherwise-empty
        decode side, so this is the no-livelock guarantee (the prefill
        pool is checked by the admitter as usual)."""
        prompt_arr = np.asarray(prompt, np.int32).reshape(-1)
        need = self.decode.pool.pages_for(
            prompt_arr.size + int(max_new_tokens))
        if need > self.decode.pool.n_pages:
            raise ValueError(
                f"request needs {need} pages but the decode pool only "
                f"has {self.decode.pool.n_pages}: raise decode_pages or "
                f"shorten the request")
        return self.prefill.scheduler.submit(
            prompt_arr, max_new_tokens,
            eos_id if eos_id is not None else self.eos_id)

    # -- one engine step ----------------------------------------------------

    def step(self) -> int:
        """One disaggregated step.  Order is the overlap:

          1. import queued handoffs (cheap scatter, must land before the
             dispatch so a new arrival decodes this step),
          2. LAUNCH the decode dispatch -- async, device starts the
             K-step scan,
          3. hand bounced decode requests back to the admitter,
          4. run a whole prefill-side step (admit / chunks / handoff)
             WHILE the decode scan runs,
          5. sync the dispatch's token buffer and retire.

        ``last_decode_step_s`` sums only (2) and (5): the decode
        critical path with prefill hidden behind it.  Returns decoded
        request count."""
        tr = self._trace
        with tr.span("step"):
            with tr.span("admit"):
                self.decode.admit_handoffs(self.channel)
            t0 = time.perf_counter()
            disp = self.decode.dispatch()  # decode_prepare, decode_dispatch
            t1 = time.perf_counter()
            for req in self.decode.runner.drain_bounced():
                self.prefill.scheduler.reaccept(req)
            with tr.span("prefill"):
                self.prefill.step(self.channel)
            t2 = time.perf_counter()
            n = self.decode.sync(disp)     # decode_sync, decode_apply
            t3 = time.perf_counter()
            self.last_decode_step_s = (t1 - t0) + (t3 - t2)
            self._step_hist.observe(self.last_decode_step_s * 1e3)
            self.steps_run += 1
            return n

    # -- aggregate views ----------------------------------------------------

    @property
    def finished(self) -> Dict[int, Request]:
        """rid -> finished request, across both sides (instant-done
        requests retire on the prefill side and never cross)."""
        return {**self.prefill.scheduler.finished,
                **self.decode.runner.finished}

    @property
    def has_work(self) -> bool:
        return (self.prefill.scheduler.has_work or len(self.channel) > 0
                or bool(self.decode.runner.running))

    @property
    def prefill_tokens_computed(self) -> int:
        return self.prefill.prefill_tokens_computed

    @property
    def decode_dispatches(self) -> int:
        return self.decode.decode_dispatches

    @property
    def page_table_uploads(self) -> int:
        return self.decode.page_table_uploads

    @property
    def logits_host_bytes(self) -> int:
        return self.decode.logits_host_bytes

    @property
    def token_host_bytes(self) -> int:
        return self.decode.token_host_bytes

    @property
    def handoffs(self) -> int:
        return self.channel.handoffs

    @property
    def handoff_pages(self) -> int:
        return self.channel.handoff_pages

    @property
    def handoff_bytes(self) -> int:
        return self.channel.handoff_bytes

    @property
    def decode_bounces(self) -> int:
        return self.decode.runner.bounce_count

    # -- counters -----------------------------------------------------------

    def reset_counters(self) -> None:
        """Zero every run counter on every layer (engine, both workers,
        their scheduler/runner, the channel) -- each layer zeroes its
        OWN ``_COUNTERS`` registry."""
        for c in self._COUNTERS:
            setattr(self, c, 0)
        self.last_decode_step_s = 0.0
        self.prefill.reset_counters()
        self.decode.reset_counters()
        self.channel.reset_counters()
        # registry-wide sweep: clears span/SLO histograms too (callback
        # gauges are live reads and have nothing to reset)
        self.metrics.reset()

    # -- drive to completion ------------------------------------------------

    def run(self, max_steps: int = 100000) -> Dict[int, np.ndarray]:
        """Step until every submitted request finished; returns
        {rid: prompt+generated}."""
        steps = 0
        while self.has_work:
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError("disaggregated engine failed to drain")
        return {rid: req.output for rid, req in self.finished.items()}
