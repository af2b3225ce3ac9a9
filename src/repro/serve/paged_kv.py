"""Paged posit8 KV pool: the physical cache plane of continuous batching.

The static engine's contiguous cache charges every request worst-case
``max_len`` KV memory up front.  The pool instead holds one shared set
of fixed-size PAGES per layer -- posit8 codes + po2 group scales, the
same unified ``quant.group_scales`` layout as the contiguous quantized
cache -- and each request owns an ordered list of page ids (its page
table).  A request's KV footprint is ceil(live_tokens / page) pages, so
pool capacity is spent on LIVE tokens, and admission/preemption decide
who gets pages when they run out.

Layout (page size == the decode kernel's KV block, so paged and
contiguous decode share one block partition and agree bitwise):

  k_codes/v_codes : (L, P, page, Kh, Dh) uint8
  k_scale/v_scale : (L, P, page, Kh, Gs) bf16, Gs = Dh/group

A page id indexes every layer's pool simultaneously (one allocation
covers all L layers).  Page 0 is the PARKING page: never allocated,
never read through a live mask -- padded batch rows in the fixed-shape
decode step write their garbage there, and page-table rows are padded
with it so dead gathers stay in bounds.

Alloc/free is host-side (a free list, LIFO for locality, backed by an
allocated-page set so the double-free guard is O(1) per page); the
device arrays move only through ``write_prefill``/``write_chunk``
(batched scatter of a quantized prefill cache / chunk into pages), the
in-jit chunk scatter of paged chunk prefill
(``attention._attn_prefill_paged``) and the decode step itself (the
per-token scatter in ``attention._attn_decode_paged``).

Chunk/page contract (chunked paged prefill)
-------------------------------------------
Prefill proceeds in fixed-size CHUNKS that are whole pages:
``chunk == k * page_size`` and chunks start at page boundaries, so a
chunk's tokens land in ``chunk / page_size`` consecutive page-table
slots and ``write_chunk`` is a pure page scatter -- no page is ever
written by two different chunks, and a half-prefilled request can be
preempted by freeing its pages with no partial-page state to unwind.
The engine additionally requires ``chunk | max_len`` so the last chunk
of a ``max_len``-long prefix never indexes past the page table.  The
final chunk of a prefix may cover fewer real tokens than ``chunk``;
its pad slots scatter garbage that decode never reads (the live mask
is positional), exactly like the monolithic prefill bucket did.

Share / refcount / copy-on-write contract (prefix caching)
----------------------------------------------------------
A page may appear in MORE than one request's page table: the
scheduler's prefix index shares whole PROMPT-prefix pages between
requests with a common preamble.  The pool therefore counts references
per page -- ``alloc`` hands out pages at refcount 1, ``incref`` adds a
holder (a sharing request, or the prefix index itself), and ``free`` is
a DECREF: a page only returns to the free list when its last holder
drops it.  The old ``_allocated``-set invariants become refcount
invariants -- ``_allocated`` is exactly the pages with refcount >= 1,
and decref of an unallocated page is the double-free bug it always was.

The copy-on-write discipline is that only whole prompt-prefix pages
are ever shared, and shared pages are READ-ONLY by construction rather
than by trap: the prefix match is capped so the page holding the
prompt's LAST token is always recomputed privately, a hit request's
chunk cursor starts past the matched pages (so the chunk-prefill
scatter of ``attention._attn_prefill_paged`` / ``write_chunk`` only
ever lands in its private pages), and the decode scatter of
``attention._attn_decode_paged`` writes at ``position >= len(prompt)``
-- past every shared slot.  No write path can reach a shared page, so
sharing needs no copy and the pages reproduce the cold path's KV
bitwise (same tokens, same params, same chunk computation).

Page KINDS: growable KV pages vs fixed-size state SLABS
-------------------------------------------------------
The pool stores up to two kinds of physical cache, decided by the
config's layer kinds (``page_kinds``):

  "kv"    -- attention layers.  Growable: a request's footprint is
             ceil(live_tokens / page) pages and climbs as it decodes.
  "state" -- recurrent (mamba / rwkv) layers.  FIXED: one slab per
             request holds the whole quantized state pytree (posit8
             codes + bf16 group scales per leaf -- conv boundary,
             scan state, token-shift carries), and a decode step
             rewrites it in place.  No growth, no lazy allocation:
             admission budgets exactly one slab for the request's
             entire lifetime.

Slab buffers are the ``models.transformer.init_state_cache`` pytree
with the per-request batch axis widened to ``n_slabs + 1`` (axis 1 of
every leaf, exactly where the KV leaves keep their page axis).  Slab 0
is the PARKING slab, the state twin of the parking page: decode rows
whose request finished mid-scan read and write it instead of a live
slab.  Hybrid families (jamba) hold both kinds at once -- attention
sub-layers page through the KV plane while mamba sub-layers ride one
slab -- and pure-recurrent families (rwkv) hold zero-size KV leaves
and page nothing.  Slabs refcount/alloc/free exactly like pages and
hand off bitwise through ``export_state``/``import_state``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..models import ssm as _ssm
from ..models import transformer as _transformer
from ..models.attention import kv_scale_cols
from .programs import program

__all__ = ["PARKING_PAGE", "PARKING_SLAB", "PagedKVPool",
           "paged_kv_bytes_per_step", "page_handoff_bytes",
           "state_slab_bytes"]

_POOL_KEYS = ("k_codes", "v_codes", "k_scale", "v_scale")

# Page 0 is never allocated: padded batch rows -- and, in the multi-step
# decode dispatch, rows whose request finished mid-scan -- re-map their
# writes here (page-table row of zeros, position 0), so dead decode
# iterations are no-op DMAs against one scratch page instead of
# corrupting live pages.  Its scales initialize to the neutral 1.0, so
# even a masked read through it dequantizes to finite values.
PARKING_PAGE = 0

# Slab 0 plays the same role on the state plane: decode rows of
# finished requests gather/scatter their (discarded) state here.
PARKING_SLAB = 0


def _page_scatter(dst: jax.Array, src: jax.Array,
                  idx: jax.Array) -> jax.Array:
    """In-place page scatter: ``dst`` is donated, so XLA updates the pool
    buffer where it lives instead of copying the whole L x P x page
    array per admission."""
    return dst.at[:, idx].set(src)


_scatter_pages = jax.jit(program(_page_scatter, "page_scatter"),
                         donate_argnums=(0,))


def _init_state_buffers(cfg: ModelConfig, n_slabs: int,
                        kv_group: Optional[int]):
    """Slab buffers: the quantized-state pytree with the batch axis
    widened to ``n_slabs + 1``.  Built from shape specs only (no
    quantization runs): codes start at 0, scales at the neutral 1.0,
    so a masked read through the parking slab dequantizes to zeros."""
    specs = jax.eval_shape(
        lambda: _ssm.quantize_state(
            _transformer.init_state_cache(cfg, n_slabs + 1), kv_group))

    def init(path, sds):
        if path[-1].key.endswith("_scale"):
            return jnp.ones(sds.shape, sds.dtype)
        return jnp.zeros(sds.shape, sds.dtype)

    return jax.tree_util.tree_map_with_path(init, specs)


class PagedKVPool:
    """Fixed-size paged posit8 cache pool with host-side accounting.

    Two page kinds (see the module contract): growable attention-KV
    pages and fixed-size recurrent-state slabs.  ``n_pages`` counts
    allocatable KV pages and ``n_slabs`` allocatable state slabs; one
    extra parking page / slab (id 0) is added on top of each, so the
    device arrays hold ``n_pages + 1`` pages and ``n_slabs + 1`` slabs.
    """

    # layer kinds per family: which cache planes the pool must hold
    _FAMILY_KINDS = {"dense": ("kv",), "moe": ("kv",),
                     "ssm": ("state",), "hybrid": ("kv", "state")}

    @classmethod
    def page_kinds(cls, cfg: ModelConfig) -> tuple:
        """Cache kinds the config's layer mix needs: ``"kv"`` if any
        layer is attention, ``"state"`` if any layer is recurrent.
        Raises (naming the supported families) for anything else --
        the single copy of the capability check, shared with
        ``launch.specs.paged_cache_specs`` so lowering and runtime
        reject the same configs with the same error."""
        kinds = cls._FAMILY_KINDS.get(cfg.family)
        if kinds is None:
            raise ValueError(
                f"no page-kind mapping for family {cfg.family!r}: the "
                f"paged serving plane supports "
                f"{sorted(cls._FAMILY_KINDS)} (attention layers page "
                f"KV; recurrent layers ride fixed-size state slabs)")
        return kinds

    @classmethod
    def validate_family(cls, cfg: ModelConfig) -> None:
        cls.page_kinds(cfg)

    def __init__(self, cfg: ModelConfig, n_pages: int, page_size: int,
                 kv_group: Optional[int] = None, n_slabs: int = 0):
        kinds = self.page_kinds(cfg)
        self.has_kv = "kv" in kinds
        self.has_state = "state" in kinds
        self.cfg = cfg
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.kv_group = kv_group
        hd = cfg.resolved_head_dim
        self.gs = kv_scale_cols(hd, kv_group)
        # KV leaves span the ATTENTION layers only (= all layers for
        # dense/moe, one per group for hybrid, none for pure-recurrent
        # -- the leaves stay present at L=0 so the key set is uniform)
        self.kv_layers = cfg.n_attn_layers if self.has_kv else 0
        P = self.n_pages + 1
        code_shape = (self.kv_layers, P, self.page_size, cfg.n_kv_heads, hd)
        scale_shape = code_shape[:-1] + (self.gs,)
        self.k_codes = jnp.zeros(code_shape, jnp.uint8)
        self.v_codes = jnp.zeros(code_shape, jnp.uint8)
        self.k_scale = jnp.ones(scale_shape, jnp.bfloat16)
        self.v_scale = jnp.ones(scale_shape, jnp.bfloat16)
        # LIFO free list: recently-freed pages are re-used first.  The
        # refcount map mirrors it so alloc/free can assert their
        # invariants in O(1) per page (the old ``pg not in self._free``
        # guard was a linear scan -- O(P^2) to retire a long request);
        # ``_allocated`` == the pages with refcount >= 1 (prefix-shared
        # pages carry one count per holder, see the module contract).
        self._free: List[int] = list(range(P - 1, 0, -1))
        self._ref: Dict[int, int] = {}
        self._allocated: set = set()
        self.alloc_peak = 0
        # state-slab plane: same accounting discipline, own id space
        self.n_slabs = int(n_slabs) if self.has_state else 0
        self.state: Dict[str, Any] = {}
        if self.has_state:
            self.state = _init_state_buffers(cfg, self.n_slabs, kv_group)
        self._slab_free: List[int] = list(range(self.n_slabs, 0, -1))
        self._slab_ref: Dict[int, int] = {}
        self._slab_allocated: set = set()
        self.slab_alloc_peak = 0

    # -- accounting ---------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.n_pages - len(self._free)

    @property
    def utilization(self) -> float:
        return self.used_pages / max(self.n_pages, 1)

    def pages_for(self, tokens: int) -> int:
        """KV pages needed to hold ``tokens`` cache slots (0 for
        pure-recurrent families: their whole footprint is one slab)."""
        if not self.has_kv:
            return 0
        return -(-tokens // self.page_size)

    @property
    def free_slabs(self) -> int:
        return len(self._slab_free)

    @property
    def used_slabs(self) -> int:
        return self.n_slabs - len(self._slab_free)

    def register_gauges(self, registry, namespace: str = "pool") -> None:
        """Expose the pool's occupancy accounting as callback gauges on
        an ``obs.MetricRegistry``.  Everything reads existing properties
        lazily at snapshot time, so the alloc/free hot path stays
        untouched; ``page_bytes`` is the closed-form per-page byte model
        (``page_handoff_bytes``) the handoff tie-outs check against."""
        registry.gauge(f"{namespace}/n_pages", fn=lambda: self.n_pages)
        registry.gauge(f"{namespace}/used_pages", fn=lambda: self.used_pages)
        registry.gauge(f"{namespace}/free_pages", fn=lambda: self.free_pages)
        registry.gauge(f"{namespace}/utilization",
                       fn=lambda: self.utilization)
        registry.gauge(f"{namespace}/alloc_peak", fn=lambda: self.alloc_peak)
        registry.gauge(
            f"{namespace}/page_bytes",
            fn=lambda: page_handoff_bytes(self.cfg, self.page_size,
                                          self.kv_group))
        if self.has_state:
            registry.gauge(f"{namespace}/n_slabs", fn=lambda: self.n_slabs)
            registry.gauge(f"{namespace}/used_slabs",
                           fn=lambda: self.used_slabs)
            registry.gauge(f"{namespace}/free_slabs",
                           fn=lambda: self.free_slabs)
            registry.gauge(f"{namespace}/slab_alloc_peak",
                           fn=lambda: self.slab_alloc_peak)
            registry.gauge(
                f"{namespace}/slab_bytes",
                fn=lambda: state_slab_bytes(self.cfg, self.kv_group))

    # -- alloc / free -------------------------------------------------------

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` pages off the free list at refcount 1; None (and no
        change) if the pool cannot satisfy the request."""
        if n > len(self._free):
            return None
        got = [self._free.pop() for _ in range(n)]
        for pg in got:
            assert pg not in self._allocated, f"page {pg} double-allocated"
            self._allocated.add(pg)
            self._ref[pg] = 1
        self.alloc_peak = max(self.alloc_peak, self.used_pages)
        return got

    def incref(self, pages: List[int]) -> None:
        """Add one holder to already-allocated pages (prefix sharing:
        a request attaching cached prompt-prefix pages, or the prefix
        index registering a freshly prefilled prefix)."""
        for pg in pages:
            assert pg in self._allocated, f"incref of unallocated page {pg}"
            self._ref[pg] += 1

    def free(self, pages: List[int]) -> None:
        """Drop ONE reference per page; a page returns to the free list
        only when its last holder lets go (decref -- the refcount form
        of the old free, which is the refcount == 1 special case)."""
        for pg in pages:
            assert 0 < pg <= self.n_pages, pg
            assert pg in self._allocated, f"double free of page {pg}"
            self._ref[pg] -= 1
            if self._ref[pg] == 0:
                del self._ref[pg]
                self._allocated.remove(pg)
                self._free.append(pg)

    def refcount(self, pg: int) -> int:
        """Current holder count of a page (0 = free)."""
        return self._ref.get(pg, 0)

    # -- slab alloc / free (state plane: same discipline, own id space) -----

    def alloc_slab(self) -> Optional[int]:
        """Pop ONE slab at refcount 1; None (and no change) if the
        state plane is exhausted.  A request needs exactly one slab for
        its whole lifetime -- there is no multi-slab allocation."""
        assert self.has_state, "slab alloc on a pool without state"
        if not self._slab_free:
            return None
        sl = self._slab_free.pop()
        assert sl not in self._slab_allocated, f"slab {sl} double-allocated"
        self._slab_allocated.add(sl)
        self._slab_ref[sl] = 1
        self.slab_alloc_peak = max(self.slab_alloc_peak, self.used_slabs)
        return sl

    def incref_slab(self, sl: int) -> None:
        assert sl in self._slab_allocated, f"incref of unallocated slab {sl}"
        self._slab_ref[sl] += 1

    def free_slab(self, sl: int) -> None:
        """Decref; the slab returns to the free list when the last
        holder lets go (mirrors :meth:`free`)."""
        assert 0 < sl <= self.n_slabs, sl
        assert sl in self._slab_allocated, f"double free of slab {sl}"
        self._slab_ref[sl] -= 1
        if self._slab_ref[sl] == 0:
            del self._slab_ref[sl]
            self._slab_allocated.remove(sl)
            self._slab_free.append(sl)

    def slab_refcount(self, sl: int) -> int:
        return self._slab_ref.get(sl, 0)

    # -- device state -------------------------------------------------------

    def device_state(self) -> Dict[str, Any]:
        """The pool leaves a paged decode step reads AND writes.  KV
        leaves appear only for attention-bearing families and the
        ``"state"`` subtree only for recurrent ones, so each family's
        decode-loop carry is exactly its resident cache -- no zero-size
        ballast rides through jit donation."""
        out: Dict[str, Any] = {}
        if self.has_kv:
            out.update({k: getattr(self, k) for k in _POOL_KEYS})
        if self.has_state:
            out["state"] = self.state
        return out

    def set_device_state(self, state: Dict[str, Any]) -> None:
        if self.has_kv:
            for k in _POOL_KEYS:
                setattr(self, k, state[k])
        if self.has_state:
            self.state = state["state"]

    @staticmethod
    def device_specs(cfg: ModelConfig, n_pages: int, page_size: int,
                     kv_group: Optional[int] = None,
                     n_slabs: int = 0) -> Dict[str, Any]:
        """ShapeDtypeStructs of the pool leaves (dry-run lowering)."""
        kinds = PagedKVPool.page_kinds(cfg)
        out: Dict[str, Any] = {}
        if "kv" in kinds:
            hd = cfg.resolved_head_dim
            gs = kv_scale_cols(hd, kv_group)
            cs = (cfg.n_attn_layers, n_pages + 1, page_size,
                  cfg.n_kv_heads, hd)
            out.update({
                "k_codes": jax.ShapeDtypeStruct(cs, jnp.uint8),
                "v_codes": jax.ShapeDtypeStruct(cs, jnp.uint8),
                "k_scale": jax.ShapeDtypeStruct(cs[:-1] + (gs,),
                                                jnp.bfloat16),
                "v_scale": jax.ShapeDtypeStruct(cs[:-1] + (gs,),
                                                jnp.bfloat16),
            })
        if "state" in kinds:
            out["state"] = jax.eval_shape(
                lambda: _ssm.quantize_state(
                    _transformer.init_state_cache(cfg, n_slabs + 1),
                    kv_group))
        return out

    # -- data movement ------------------------------------------------------

    def write_prefill(self, cache_q, pages: List[int]) -> None:
        """Scatter a quantized prefill cache into allocated pages.

        ``cache_q``: the scan-stacked quantized cache of a B=1 prefill
        whose seq length is a multiple of ``page_size`` -- leaves
        (L, 1, S, Kh, X).  The first S/page_size entries of ``pages``
        receive the S tokens in logical order."""
        self.write_chunk(cache_q, pages, 0)

    def write_chunk(self, cache_q, pages: List[int], start: int) -> None:
        """Scatter one quantized prefill CHUNK into a request's pages --
        the partial form of :func:`write_prefill` (``start=0`` with a
        whole-prefix chunk IS write_prefill).

        ``cache_q``: quantized B=1 chunk, leaves (L, 1, C, Kh, X) with
        C a multiple of ``page_size``.  ``start`` is the chunk's first
        token slot within the request; it must be page-aligned (the
        chunk/page contract above), so the chunk occupies page-table
        slots ``start/page_size ..`` and the scatter stays whole-page.
        A final chunk padded past the request's live prefix may own
        fewer pages than C/page_size: only ``pages[start/page_size:]``
        are written and the trailing pad pages are dropped."""
        leaf = cache_q["k_codes"]
        L, b, c = leaf.shape[:3]
        assert b == 1, "prefill writes are per-request (B=1)"
        if c % self.page_size:
            # recurrent-family prefill chunks are UNPADDED (pad tokens
            # would corrupt the carried state), so a hybrid prefix's
            # final chunk may end mid-page: pad the trailing block here
            # instead.  The pad slots hold zero codes / neutral scales
            # and are either overwritten by decode or never read (the
            # live mask is positional), exactly like monolithic pad.
            pad = self.page_size - c % self.page_size
            cache_q = {
                key: jnp.pad(
                    cache_q[key],
                    [(0, pad) if ax == 2 else (0, 0)
                     for ax in range(cache_q[key].ndim)],
                    constant_values=1.0 if key.endswith("_scale") else 0)
                for key in _POOL_KEYS}
            c += pad
        assert start % self.page_size == 0, (start, self.page_size)
        first = start // self.page_size
        nblk = min(c // self.page_size, len(pages) - first)
        assert nblk > 0, (start, c, len(pages))
        idx = jnp.asarray(pages[first:first + nblk], jnp.int32)
        s = nblk * self.page_size
        for key in _POOL_KEYS:
            src = cache_q[key][:, 0, :s]                 # (L, S, Kh, X)
            src = src.reshape(L, nblk, self.page_size, *src.shape[2:])
            setattr(self, key, _scatter_pages(getattr(self, key), src, idx))


    # -- page handoff (disaggregated prefill/decode) ------------------------

    def export_pages(self, pages: List[int]) -> Dict[str, jax.Array]:
        """Gather whole pages as a detachable payload -- the prefill
        side of the disaggregated page handoff (``serve/disagg.py``).

        Returns ``{key: (L, n, page, Kh, X)}`` device arrays holding the
        posit8 codes + po2 group scales of ``pages`` in logical order --
        exactly the bytes the handoff moves, ~4x smaller than a bf16
        cache.  The gather is a pure functional read: the returned
        arrays do not alias the pool leaves, so the caller may ``free``
        (and the pool re-use) the source pages immediately, even while
        the gather is still dispatching asynchronously."""
        idx = jnp.asarray(pages, jnp.int32)
        return {key: getattr(self, key)[:, idx] for key in _POOL_KEYS}

    def import_pages(self, payload: Dict[str, jax.Array],
                     pages: List[int]) -> None:
        """Scatter an exported payload into this pool's ``pages`` -- the
        decode side of the handoff.  The destination pool must share the
        source's geometry (page size, layer count, head layout); the
        page IDS need not match -- the request's new page-table row is
        simply the destination list.  Codes and scales land bitwise, so
        decode over imported pages reproduces the source pool's reads
        exactly."""
        leaf = payload["k_codes"]
        assert leaf.shape[0] == self.kv_layers, leaf.shape
        assert leaf.shape[2] == self.page_size, \
            (leaf.shape, self.page_size)
        assert leaf.shape[1] == len(pages), (leaf.shape, len(pages))
        idx = jnp.asarray(pages, jnp.int32)
        for key in _POOL_KEYS:
            setattr(self, key,
                    _scatter_pages(getattr(self, key), payload[key], idx))

    def gather_request(self, pages: List[int]) -> Dict[str, jax.Array]:
        """Read a request's pages back as a contiguous (1, T, Kh, X)
        quantized cache per layer (debug / test oracle)."""
        idx = jnp.asarray(pages, jnp.int32)
        out = {}
        for key in _POOL_KEYS:
            x = getattr(self, key)[:, idx]               # (L, n, page, ...)
            out[key] = x.reshape(x.shape[0], 1, -1, *x.shape[3:])
        return out

    # -- state slab movement ------------------------------------------------

    def write_state(self, state_q, slab: int) -> None:
        """Scatter one request's quantized state into its slab -- the
        state twin of :meth:`write_prefill` (prefill completion writes
        the final carried state here ONCE; decode then rewrites the
        slab in place inside the jitted loop).  ``state_q`` leaves have
        batch width 1 on axis 1."""
        idx = jnp.asarray([slab], jnp.int32)
        self.state = jax.tree.map(
            lambda dst, src: _scatter_pages(dst, src, idx),
            self.state, state_q)

    def export_state(self, slab: int) -> Dict[str, Any]:
        """Gather one slab as a detachable payload (batch width 1) --
        the state side of the disagg handoff AND the scheduler's
        preemption snapshot.  A pure functional read, like
        :meth:`export_pages`: valid after the slab is freed."""
        idx = jnp.asarray([slab], jnp.int32)
        return jax.tree.map(lambda leaf: leaf[:, idx], self.state)

    def import_state(self, payload, slab: int) -> None:
        """Scatter an exported state payload into this pool's ``slab``
        (decode side of the handoff / preemption resume).  Codes and
        scales land bitwise, so the restored request's decode continues
        exactly where the source left off."""
        self.write_state(payload, slab)

    # -- roofline -----------------------------------------------------------

    def modeled_bytes_per_step(self, positions) -> float:
        """Modeled cache HBM bytes one batched decode step moves, per
        page kind: each live request reads its ceil((pos+1)/page) live
        KV pages across the attention layers, and reads + rewrites its
        whole state slab -- a function of LIVE pages/slabs, never of
        any ``max_len``."""
        total = paged_kv_bytes_per_step(self.cfg, positions,
                                        self.page_size, self.kv_group)
        if self.has_state:
            n_live = int(np.atleast_1d(np.asarray(positions)).size)
            total += 2.0 * state_slab_bytes(self.cfg, self.kv_group) * n_live
        return total


def paged_kv_bytes_per_step(cfg: ModelConfig, positions, page_size: int,
                            kv_group: Optional[int] = None) -> float:
    """Companion of ``roofline.analysis.decode_kv_bytes`` for the paged
    plane: codes+scales bytes of the live pages of every request."""
    hd = cfg.resolved_head_dim
    gs = kv_scale_cols(hd, kv_group)
    toks = sum(-(-(int(p) + 1) // page_size) * page_size
               for p in np.atleast_1d(np.asarray(positions)))
    return float(2 * cfg.n_attn_layers * cfg.n_kv_heads * toks
                 * (hd * 1 + gs * 2))


def page_handoff_bytes(cfg: ModelConfig, page_size: int,
                       kv_group: Optional[int] = None) -> int:
    """Bytes ONE page moves across the prefill->decode handoff: K+V
    posit8 codes (1 byte/slot/feature) plus bf16 po2 group scales over
    every attention layer -- the exact ``.nbytes`` sum of one page's
    slice of an ``export_pages`` payload, which is what makes the
    handoff ~4x cheaper than shipping bf16 KV."""
    hd = cfg.resolved_head_dim
    gs = kv_scale_cols(hd, kv_group)
    return int(2 * cfg.n_attn_layers * page_size * cfg.n_kv_heads
               * (hd * 1 + gs * 2))


def state_slab_bytes(cfg: ModelConfig, kv_group: Optional[int] = None) -> int:
    """Bytes ONE request's quantized recurrent state occupies -- the
    exact ``.nbytes`` sum of an ``export_state`` payload (posit8 codes
    + bf16 group scales over every recurrent leaf), i.e. the per-kind
    closed form for the "state" plane: a slab costs this much resident,
    a handoff moves this much, and a decode step streams 2x (read +
    rewrite).  0 for pure-attention families."""
    if "state" not in PagedKVPool.page_kinds(cfg):
        return 0
    specs = jax.eval_shape(
        lambda: _ssm.quantize_state(
            _transformer.init_state_cache(cfg, 1), kv_group))
    return int(sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(specs)))
