"""Continuous-batching serve engine over a shared block-paged KV cache.

Cloud Kotta's provisioning argument, applied to token decode. The paper keeps
utilization high under bursty multi-user load by (a) pooling capacity that
static per-user provisioning would strand, (b) admitting work the moment
capacity frees up (its elastic worker pools / spot market), and (c) keeping
ONE copy of a hot shared dataset that many jobs read (its tiered storage).
This engine is the serving analogue, with the KV cache playing the role of
the provisioned resource:

- **Slots are worker nodes.** ``max_decode_slots`` fixed batch lanes decode
  in lockstep at hardware speed; a request occupies a slot only while live,
  exactly like a Kotta job occupies a pool node.
- **Pages are the storage tier.** The physical KV pool is one shared array of
  ``page_size``-row pages; each request addresses its logical KV stream
  through a per-slot page-table row. Paging provisions per *actual* demand
  and returns capacity on completion with zero copies or compaction.
- **The queue is the job queue.** Between decode chunks the engine retires
  finished sequences and admits waiting prompts into the freed slots/pages —
  continuous batching, the scheduling move that gives Kotta its up-to-16x
  cost reduction over static provisioning.
- **Admission is O(new tokens), not O(prompt length).** Prompts are prefilled
  in fixed ``prefill_chunk``-token steps whose KV rows are scattered straight
  into pool pages (``prefill_paged``): no dense ragged cache, no
  re-layout/transpose into pages afterwards, and one jit signature per batch
  bucket instead of one per prompt-length pad bucket.
- **Prompt prefixes are shared copy-on-write.** A page-granular radix index
  (:mod:`repro.serve.paging`) maps token chunks to the pool pages already
  holding their KV. Admission aliases every fully-matched page into the new
  request's page-table row (refcount++), copy-on-writes the one partially
  matched boundary page, and prefills only the unmatched suffix — the
  paper's shared-dataset tiering, for caches. Retirement decrefs instead of
  freeing, and a retired request's pages stay hittable until actually
  reallocated.
- **No host round-trips on the hot path.** The decode loop is a
  ``lax.fori_loop`` of exactly ``decode_chunk`` on-device steps (a static
  bound: one compile, ever) with the pool donated to each chunk; tokens
  accumulate on device and cross to the host once per chunk.
- **Every phase is a span and a counter.** An admission wave (plan /
  prefill / sync) and a decode chunk (prep / dispatch / sync / post) each
  open a ``jax.profiler.TraceAnnotation`` named ``engine.<phase>``, on the
  profiler's clock beside the device's trace, and add their host seconds
  to ``stats``, beside counts of the decode slot-steps and prefill rows
  that did useful work and each request's wait in the queue. A
  registry-bound engine exports them as ``kotta_engine_<key>_total``.
- **Speculative multi-token decode** (``enable_spec_decode``): each on-device
  step drafts ``spec_tokens`` candidates per slot by bigram prompt-lookup
  over the slot's own token history (kept on device in the chunk carry),
  scores all drafts plus the current token in ONE multi-query paged verify
  pass (:mod:`repro.kernels.verify_attention`), and emits the verified
  prefix — up to ``spec_tokens + 1`` tokens per step for the cost of one
  cache sweep. Greedy outputs are token-identical to the non-speculative
  path; rejected draft tails roll back by construction (the next step
  re-writes their KV rows) and writes past a slot's token budget are routed
  to the sink page so shared/refcounted pages are never corrupted. The trip
  count stays static: still one compile, ever. The draft lookup is FUSED
  with the verify pass into one jitted step per loop iteration
  (:func:`repro.train.train_step.build_fused_spec_step` over
  :mod:`repro.serve.drafting`).
- **Per-slot adaptive speculation** (``spec_adaptive_k``): each slot's
  accept-rate EMA governs its own speculative window ``kslot`` in [1, K] —
  halved when drafts keep getting rejected, re-doubled when acceptance
  recovers — and each chunk dispatches at the smallest jitted verify-window
  *bucket* covering the live slots, so low-acceptance workloads stop paying
  for K verify rows per step. Greedy outputs stay token-identical for any
  window schedule (accepted prefixes are always exact greedy matches).
- **int8-quantized KV pages** (``kv_cache_dtype="int8"``): the pool stores
  K/V rows as int8 with per-row f32 scale pages, quantized on scatter and
  dequantized inside the attention-kernel tile loads (f32 accumulation) —
  ~``4*hd/(hd+4)``x the slot-token capacity at a fixed HBM budget, with the
  f32 layout untouched as the parity baseline (see kernels/kv_quant).

Physical page 0 is reserved as a write sink: idle slots keep ``pos=0`` and an
all-zero page-table row, and prefill pads route their KV writes there, so
masked writes can never corrupt pages belonging to live requests.

Beyond the one-shot ``generate`` loop the engine exposes a **stepped API**
(``enqueue`` / ``admit`` / ``decode_step`` / ``abort``) so an external
control plane can drive it request-by-request: the Kotta serving gateway
(:mod:`repro.serve.gateway`) keeps the queue deadline/cost-ordered, scopes
each request's prefix-cache ``namespace`` by (tenant, data-zone), and
re-enqueues a revoked spot replica's requests through ``abort`` — turning
every generation request into a first-class secured, scheduled Kotta job.

**Deadline-aware decode preemption** rides on the stepped API:
``preempt(slot)`` pauses a running request mid-stream with zero lost work —
its KV pages stay allocated and *pinned* (their refcounts are untouched, so
the allocator can never hand them out, and eviction-on-realloc can never
scrub their prefix-cache entries) while the host-side cursor / token
history / draft state parks in a :class:`PausedRequest`. The freed slot
admits an interactive request immediately. ``resume`` re-attaches the
parked pages to a fresh slot through the page table and continues decoding
with **zero re-prefill** (``prefill_tokens`` does not move) and greedy
tokens identical to an uninterrupted run — with or without speculative
decode, whose per-slot history buffer is parked and restored too.

``ServeEngine`` (static batch, dense cache) is kept as the fallback path for
recurrent-state families and as the benchmark baseline;
``prefill_mode="dense"`` keeps the PR-1 bucketed dense-prefill admission
path alive as an in-engine baseline/oracle.
"""
from __future__ import annotations

import enum
import math
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models import get_family
from repro.train.train_step import (build_decode_step, build_fused_spec_step,
                                    build_paged_decode_step,
                                    build_paged_prefill_step,
                                    build_prefill_step)

from .drafting import build_ngram_draft
from .paging import PageAllocator, PoolCapacityError, PrefixCache
from .telemetry import RegistryDict


@dataclass
class ServeResult:
    tokens: np.ndarray          # (B, max_new)
    prompt_lens: list[int]


class ServeEngine:
    """Legacy static-batch engine: pads the batch, dense per-request cache."""

    def __init__(self, cfg, params, *, max_len: int = 512):
        if cfg.encoder_only:
            raise ValueError("encoder-only models cannot decode")
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.family = get_family(cfg)
        self._prefill = jax.jit(build_prefill_step(cfg))
        self._decode = jax.jit(build_decode_step(cfg))

    def _pad_cache(self, cache, cur_len: int):
        """Grow the prefill cache to max_len along the cache_seq axis."""
        def grow(x):
            # cache_seq axis = 2 for (L,B,S,KV,hd); SSM states have no seq axis.
            if x.ndim >= 3 and x.shape[2] == cur_len:
                pad = [(0, 0)] * x.ndim
                pad[2] = (0, self.max_len - cur_len)
                return jnp.pad(x, pad)
            return x
        return jax.tree.map(grow, cache)

    def generate(self, prompts: list[list[int]], max_new: int = 16) -> ServeResult:
        b = len(prompts)
        plen = max(len(p) for p in prompts)
        toks = np.zeros((b, plen), np.int32)
        for i, p in enumerate(prompts):
            toks[i, plen - len(p):] = p  # left-pad so last position is newest
        batch = {"tokens": jnp.asarray(toks)}
        logits, cache = self._prefill(self.params, batch)
        cache = self._pad_cache(cache, plen)
        pos = jnp.full((b,), plen - 1, jnp.int32)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        # Tokens accumulate on device; one host transfer at the end (the seed
        # did a blocking np.asarray round-trip per decoded token).
        out = jnp.zeros((b, max_new), jnp.int32)
        for t in range(max_new):
            out = out.at[:, t].set(next_tok)
            pos = pos + 1
            step_batch = {"tokens": next_tok[:, None], "pos": pos}
            next_tok, _, cache = self._decode(self.params, step_batch, cache)
        return ServeResult(np.asarray(out), [len(p) for p in prompts])


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------

@dataclass
class EngineRequest:
    """One generation request as the engine's queue sees it.

    ``rid`` is an opaque caller-chosen id (``generate`` uses the prompt
    index; the gateway uses its job ids). ``max_new`` is per-request — the
    stepped API admits requests with heterogeneous budgets in one wave.
    ``namespace`` scopes the prefix cache: pages registered under one
    namespace are invisible to lookups from another (the gateway keys it by
    (tenant, data-zone), so cross-tenant prompts can never alias KV pages).
    """
    rid: object
    prompt: list[int]
    max_new: int
    namespace: object = None


@dataclass
class _Live:
    """A request occupying a slot."""
    req: EngineRequest
    pages: list[int]
    emitted: int = 0
    tokens: list[int] = field(default_factory=list)


@dataclass
class PausedRequest:
    """A preempted request parked host-side, its KV pages still pinned.

    ``pages`` keep their refcounts (never released, never reallocatable)
    so the sequence's KV survives any eviction pressure while paused;
    ``cur``/``pos``/``limit`` — and ``hist``, the speculative-decode
    drafting history row — are the exact slot state at the chunk boundary
    where the request was paused. ``resume`` restores all of it into a
    fresh slot with zero re-prefill.
    """
    req: EngineRequest
    pages: list[int]
    emitted: int
    tokens: list[int]
    cur: int
    pos: int
    limit: int
    hist: np.ndarray | None = None
    # Adaptive-speculation state (spec decode only): the slot's speculative
    # window and accept-rate EMA survive preemption, so a resumed request
    # picks its tuned window back up instead of re-warming from K.
    kslot: int = 0
    ema: float = 0.0


class ExportReason(str, enum.Enum):
    """Why a request's pages are leaving the device pool.

    The one residency API (:meth:`ContinuousBatchingEngine.export`)
    serves three movements that used to be parallel code paths —
    cross-replica shipping and cross-tier demotion are two *transports*
    behind the same gather:

    - ``HANDOFF``: disaggregated prefill -> decode replica hop.
    - ``EVACUATE``: revocation-notice migration off a dying replica.
    - ``DEMOTE``: tier demotion into a :class:`~repro.serve.kv_store.TieredKVStore`
      (device -> host/object storage instead of device -> device).
    """

    HANDOFF = "handoff"
    EVACUATE = "evacuate"
    DEMOTE = "demote"


@dataclass
class ShippedKV:
    """A request's finished KV pages in flight between engines (or tiers).

    The disaggregated-serving handoff payload: a prefill-role replica runs
    admission prefill, ``export`` snapshots the request's *content*
    pages (the ``ceil(pos / page_size)`` pages actually holding KV rows —
    trailing decode-budget pages are empty and never ship) into host arrays,
    and ``import_pages`` on a decode-role replica re-registers everything:
    fresh pages from the destination allocator, a page-table row, the
    destination's radix prefix cache (so the shipped prefix stays shareable
    after the hop), and the decode cursor exactly where the source stopped.
    Greedy decode continues token-identically to a never-shipped run.

    ``content`` maps every pool leaf name to a ``(L, KV, n_content,
    page_size[, hd])`` host array — for int8 pools that is the int8 data
    pages AND their f32 ``k_scale``/``v_scale`` pages, so dequantization
    state travels with the data.

    The payload is not prefill-specific: a request exported **mid-decode**
    (the evacuation path) carries its already-decoded KV rows in the same
    content pages, its emitted tokens in ``tokens``, and — under
    speculative decode — its tuned adaptive window ``kslot`` and
    accept-rate EMA, so the destination resumes with the speculation
    controller warm instead of re-learning from K. ``consumed`` flips true
    on a successful import: a payload is a one-shot move, and importing it
    twice would mint two live copies of one request (a second import
    raises ``ValueError``; a *failed* import leaves it re-importable).
    """
    req: EngineRequest
    emitted: int
    tokens: list[int]
    cur: int                    # next token to emit (seeds dest decode)
    pos: int                    # == len(prompt) + emitted
    content: dict[str, np.ndarray]
    kv_cache_dtype: str
    page_size: int
    hist: np.ndarray | None = None     # spec-decode drafting history, if any
    kslot: int = 0              # adaptive speculative window (0 = untracked)
    ema: float = 0.0            # accept-rate EMA riding along with kslot
    consumed: bool = False      # set by a successful import / restore
    reason: ExportReason = ExportReason.HANDOFF

    @property
    def n_content(self) -> int:
        return next(iter(self.content.values())).shape[2]

    def page_nbytes(self) -> int:
        """Bytes of ONE shipped page across every content leaf — int8 data
        pages AND their f32 scale pages alike. The single source of truth
        for per-page sizing: ship budgets, tier capacities and metrics all
        multiply this by a page count, so no stats path can count data
        pages while forgetting the scales."""
        n = self.n_content
        return sum(a.nbytes // n for a in self.content.values())

    @property
    def nbytes(self) -> int:
        """Wire size of the shipped pages (data + scale pages alike);
        derived from :meth:`page_nbytes` so every sizing path agrees."""
        return self.page_nbytes() * self.n_content


def _next_pow2(n: int) -> int:
    """Bucket size for wave-shaped device calls: a handful of jit
    signatures (1, 2, 4, ...) instead of one per wave width."""
    return 1 << max(0, n - 1).bit_length()


@dataclass
class _Admit:
    """A request accepted into the current admission wave."""
    slot: int
    req: EngineRequest
    pages: list[int]
    start: int                  # first position to prefill (= prefix match)
    group: int = 1              # intra-wave prefill stage (same-wave dedup)


class ContinuousBatchingEngine:
    """Continuous-batching decode over a shared paged KV pool (module doc)."""

    def __init__(self, cfg, params, *, max_len: int = 512,
                 max_slots: int | None = None, num_pages: int | None = None,
                 decode_chunk: int | None = None,
                 prefill_chunk: int | None = None,
                 prefill_mode: str = "paged",
                 enable_prefix_cache: bool | None = None,
                 enable_spec_decode: bool | None = None,
                 spec_tokens: int | None = None,
                 spec_ngram: int | None = None,
                 kv_cache_dtype: str | None = None,
                 spec_adaptive_k: bool | None = None,
                 role: str = "unified"):
        if cfg.encoder_only:
            raise ValueError("encoder-only models cannot decode")
        if prefill_mode not in ("paged", "dense"):
            raise ValueError(f"prefill_mode {prefill_mode!r}")
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(f"role must be 'unified', 'prefill' or "
                             f"'decode', got {role!r}")
        self.role = role
        if role == "prefill":
            # A prefill-specialized replica only runs admission prefill and
            # ships the finished pages out (export_pages); it never decodes,
            # so speculation has nothing to govern there.
            if enable_spec_decode:
                raise ValueError("role='prefill' engines never decode; "
                                 "enable_spec_decode is meaningless there")
            enable_spec_decode = False
            spec_adaptive_k = False
        self.kv_cache_dtype = cfg.kv_cache_dtype if kv_cache_dtype is None \
            else kv_cache_dtype
        if self.kv_cache_dtype not in ("f32", "int8"):
            raise ValueError(f"kv_cache_dtype must be 'f32' or 'int8', got "
                             f"{self.kv_cache_dtype!r}")
        # The dense baseline prefills an unquantized ragged cache and
        # re-layouts it into whole pages, bypassing quantize-on-scatter; an
        # explicit request for both is a contradiction, not a default.
        if self.kv_cache_dtype == "int8" and prefill_mode == "dense":
            raise ValueError("kv_cache_dtype='int8' requires "
                             "prefill_mode='paged' (dense prefill re-layouts "
                             "an unquantized cache into pool pages and "
                             "bypasses quantize-on-scatter)")
        step = build_paged_decode_step(cfg)   # raises for recurrent families
        self.cfg = cfg
        # A replica runs where its params live. Committing them there (no
        # copy) makes every dispatch follow them, and the pool is built on
        # the same device, so replicas on different chips never meet on
        # the default device.
        self.device = next(iter(jax.tree_util.tree_leaves(params)[0]
                                .devices()))
        self.params = jax.device_put(params, self.device)
        self.family = get_family(cfg)
        self.page_size = cfg.page_size
        self.max_slots = cfg.max_decode_slots if max_slots is None \
            else max_slots
        if self.max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {self.max_slots}")
        self.pages_per_seq = math.ceil(max_len / self.page_size)
        # +1: physical page 0 is the reserved idle-slot/pad write sink.
        self.num_pages = (num_pages or self.max_slots * self.pages_per_seq) + 1
        if enable_spec_decode is None:
            enable_spec_decode = cfg.enable_spec_decode
        self.spec_tokens = cfg.spec_tokens if spec_tokens is None \
            else spec_tokens
        self.spec_ngram = cfg.spec_ngram if spec_ngram is None else spec_ngram
        self.spec_decode = bool(enable_spec_decode)
        self.spec_adaptive_k = bool(
            cfg.spec_adaptive_k if spec_adaptive_k is None
            else spec_adaptive_k)
        if self.spec_adaptive_k and not self.spec_decode:
            raise ValueError("spec_adaptive_k=True requires "
                             "enable_spec_decode=True (the adaptive window "
                             "governs speculative drafting)")
        if self.spec_decode:
            # Fail here, with the knob named, instead of as a shape error
            # deep inside the verify step / Pallas kernel.
            k = self.spec_tokens
            if k < 1:
                raise ValueError(
                    f"enable_spec_decode requires spec_tokens >= 1, got {k} "
                    "(each verify step scores spec_tokens drafts + the "
                    "current token)")
            if self.spec_ngram not in (2, 3):
                raise ValueError(
                    f"spec_ngram must be 2 (bigram) or 3 (trigram draft "
                    f"keys), got {self.spec_ngram}")
            window = k + 1
            if window > self.pages_per_seq * self.page_size:
                raise ValueError(
                    f"spec_tokens+1 = {window} verify rows exceed the "
                    f"{self.pages_per_seq * self.page_size}-row page-table "
                    f"window (max_len {max_len}, page_size "
                    f"{self.page_size}); shrink spec_tokens or raise "
                    "max_len")
            group = cfg.num_heads // cfg.num_kv_heads
            if cfg.attn_impl == "pallas" and (window * group) % 8:
                raise ValueError(
                    f"verify query tile (spec_tokens+1)*G = {window}*{group}"
                    f" = {window * group} rows must be a multiple of 8 "
                    "sublanes for the Pallas verify kernel; adjust "
                    "spec_tokens (or num_kv_heads)")
        if decode_chunk is None:
            # Occupancy heuristic (BENCH_serve batch-32 droop): hold
            # slots * chunk * expected-tokens-per-step ≈ decode_chunk_tokens
            # per dispatch — narrow batches take long chunks to amortize the
            # host sync, wide batches take short chunks so freed slots
            # re-admit waiters sooner (p95 TTFT), the sync already being
            # amortized across slots. A speculative step emits 1..K+1 tokens,
            # so spec chunks are shortened by the FULL window: an oversized
            # chunk sails past every slot's budget and burns dead masked
            # steps (each costing a whole verify pass), while an undersized
            # chunk merely adds a cheap host sync.
            per_step = 1 + self.spec_tokens if self.spec_decode else 1
            decode_chunk = min(cfg.decode_chunk_max,
                               max(2, cfg.decode_chunk_min // per_step,
                                   cfg.decode_chunk_tokens
                                   // (self.max_slots * per_step)))
        self.decode_chunk = decode_chunk
        self.prefill_chunk = prefill_chunk or cfg.prefill_chunk
        if self.decode_chunk < 1:
            raise ValueError(f"decode_chunk must be >= 1, got "
                             f"{self.decode_chunk}")
        if self.prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{self.prefill_chunk}")
        self.prefill_mode = prefill_mode

        # int8 pools add (L,KV,P,1,ps) f32 per-row scale pages; all downstream
        # paths (model scatter, kernels, COW) handle the dict structurally.
        with jax.default_device(self.device):
            self.pool = jax.device_put(
                self.family.paged_pool(cfg, self.num_pages,
                                       self.kv_cache_dtype), self.device)

        self.alloc = PageAllocator(self.num_pages)
        # Prefix sharing needs paged prefill: the dense path re-writes whole
        # pad-rounded pages and would clobber aliased prefix pages. An
        # explicit request for both is a contradiction, not a default.
        if enable_prefix_cache and prefill_mode == "dense":
            raise ValueError("enable_prefix_cache=True requires "
                             "prefill_mode='paged' (dense prefill re-writes "
                             "whole pages and cannot alias shared prefixes)")
        if enable_prefix_cache is None:
            enable_prefix_cache = cfg.enable_prefix_cache
        self.prefix_cache = PrefixCache(self.page_size) \
            if (enable_prefix_cache and prefill_mode == "paged") else None
        if self.prefix_cache is not None:
            self.alloc.on_alloc = self.prefix_cache.evict

        s = self.max_slots
        self._page_table = np.zeros((s, self.pages_per_seq), np.int32)
        self._pos = np.zeros(s, np.int32)
        self._cur = np.zeros(s, np.int32)
        self._active = np.zeros(s, bool)
        # Per-slot KV write limit (prompt_len + max_new): spec-decode draft
        # windows running past it are routed to the sink page.
        self._limit = np.zeros(s, np.int32)
        # Per-slot adaptive speculation: current speculative window (1..K,
        # seeded at K on admit) and accept-rate EMA. Host-side: updated once
        # per chunk from the chunk's (n_out, n_it) tallies.
        self._kslot = np.zeros(s, np.int32)
        self._ema = np.zeros(s, np.float64)
        # Per-slot token history (prompt + verified output) for on-device
        # n-gram drafting; lives in the decode-chunk carry while decoding.
        self.hist_len = self.pages_per_seq * self.page_size
        self._hist = jax.device_put(
            np.zeros((s, self.hist_len), np.int32), self.device) \
            if self.spec_decode else None
        self._live: dict[int, _Live] = {}
        # Preempted requests parked host-side; their pages stay pinned.
        self._paused: dict[object, PausedRequest] = {}
        # Tier demotion (set by the control plane when a TieredKVStore is
        # attached): a finishing request's content pages are exported
        # (reason=DEMOTE) *before* retirement and parked in ``demoted_out``
        # for the gateway to drain into the store — so by the time
        # eviction-on-realloc scrubs the index entries, the content already
        # lives in a lower tier (demoted, not destroyed).
        self.demote_on_retire = False
        self.demoted_out: list[ShippedKV] = []
        # Admission queue, consumed front-first by ``admit``. The caller
        # controls its order: ``generate`` fills it FCFS, the gateway keeps
        # it policy-ordered (EDF within priority class). Each request sits
        # beside its enqueue stamp (``time.perf_counter``), which admission
        # turns into ``queue_wait_seconds``.
        self._queue: deque[tuple[EngineRequest, float]] = deque()
        self.stats: dict[str, float] = {}
        self._reset_stats()

        # -- jitted steps ----------------------------------------------------
        self._prefill_ragged = jax.jit(
            lambda p, b: self.family.prefill_ragged(cfg, p, b))

        self._n_prefill_traces = 0
        pstep = build_paged_prefill_step(cfg)

        def prefill_chunk_fn(params, batch, pool):
            self._n_prefill_traces += 1
            return pstep(params, batch, pool)

        self._prefill_chunked = jax.jit(prefill_chunk_fn, donate_argnums=(2,))

        self._n_decode_traces = 0

        def decode_chunk_fn(params, cur, pos, page_table, active, budget,
                            pool):
            self._n_decode_traces += 1
            out = jnp.zeros((s, self.decode_chunk), jnp.int32)

            def body(i, carry):
                cur, pos, pool, out = carry
                out = out.at[:, i].set(cur)
                # A slot whose token budget is spent mid-chunk must stop
                # writing KV: its pos sits at prompt_len + max_new, and for a
                # row that fills its whole page table the clamped gather
                # would redirect that write INTO the request's last real
                # page, corrupting prompt rows the prefix cache may already
                # share. Masking the row to the all-zero sink makes the
                # overshoot steps harmless.
                live = active & (i < budget)
                pt = jnp.where(live[:, None], page_table, 0)
                batch = {"tokens": cur[:, None], "pos": pos,
                         "page_table": pt}
                nxt, _, pool = step(params, batch, pool)
                cur = jnp.where(live, nxt, cur)
                pos = jnp.where(live, pos + 1, pos)
                return cur, pos, pool, out

            # Static trip count: ragged remaining-token counts can never mint
            # new jit signatures; spent slots idle against the sink page.
            return lax.fori_loop(0, self.decode_chunk, body,
                                 (cur, pos, pool, out))

        # Donating the pool lets XLA scatter new KV rows in place instead of
        # copying the whole pool every chunk.
        self._chunk = jax.jit(decode_chunk_fn, donate_argnums=(6,))

        if self.spec_decode:
            k_spec = self.spec_tokens
            hlen = self.hist_len
            ngram = self.spec_ngram
            group = cfg.num_heads // cfg.num_kv_heads

            # Verify-window buckets: the adaptive controller shrinks a
            # slot's speculative window kslot per its accept-rate EMA, and
            # the host dispatches each chunk at the smallest bucket covering
            # every live slot's window — a genuinely narrower verify pass
            # (fewer query rows), not just masked acceptance. Buckets are
            # pow2 sizes plus K itself, filtered by the Pallas sublane rule
            # ((b+1)*G % 8 == 0) so every bucket is dispatchable; K always
            # survives the filter (validated above). Non-adaptive engines
            # use the single bucket K, keeping one chunk trace ever.
            if self.spec_adaptive_k:
                cand = {1 << i for i in range(k_spec.bit_length())}
                cand.add(k_spec)
                self._spec_buckets = sorted(
                    b for b in cand if b <= k_spec
                    and (cfg.attn_impl != "pallas"
                         or ((b + 1) * group) % 8 == 0))
            else:
                self._spec_buckets = [k_spec]
            self._spec_chunks: dict[int, object] = {}

            def make_spec_chunk(kb: int):
                """Build + jit the decode chunk for verify-window bucket kb.

                Each ``fori_loop`` step is ONE fused dispatch
                (:func:`build_fused_spec_step`): n-gram draft lookup, window
                assembly, KV scatter and the multi-query verify all in the
                same traced step. Acceptance is additionally masked to the
                slot's own window ``kslot <= kb``, so two slots in the same
                chunk can run different effective speculation depths.
                """
                t_spec = kb + 1
                fstep = build_fused_spec_step(
                    cfg, build_ngram_draft(hlen, kb, ngram))

                def spec_chunk_fn(params, cur, pos, hist, page_table, active,
                                  budget, limit, kslot, pool):
                    self._n_decode_traces += 1
                    out = jnp.zeros((s, self.decode_chunk * t_spec),
                                    jnp.int32)
                    n_out = jnp.zeros(s, jnp.int32)
                    n_it = jnp.zeros(s, jnp.int32)
                    bidx = jnp.arange(s)

                    def body(i, carry):
                        cur, pos, hist, n_out, n_it, pool, out = carry
                        live = active & (n_out < budget)
                        # The verified current token enters the history
                        # first: hist[:pos+1] is now the exact token stream
                        # the fused step's draft lookup reads.
                        hist = hist.at[bidx, pos].set(cur)
                        pt = jnp.where(live[:, None], page_table, 0)
                        wl = jnp.where(live, limit, 0)
                        batch = {"cur": cur, "pos": pos, "hist": hist,
                                 "page_table": pt, "write_limit": wl}
                        window, drafts, nxt, pool = fstep(params, batch,
                                                          pool)
                        # Drafted tokens become history; the tail past the
                        # next pos is re-written before any read.
                        hidx = pos[:, None] + 1 + jnp.arange(kb)[None, :]
                        hist = hist.at[bidx[:, None], hidx].set(
                            drafts, mode="drop")
                        # -- acceptance: longest draft prefix the model
                        # agrees with, capped at the slot's own adaptive
                        # window; nxt[:, a] is the correction after it ----
                        match = (drafts == nxt[:, :kb]) & \
                                (jnp.arange(kb)[None, :] < kslot[:, None])
                        a = jnp.cumprod(match.astype(jnp.int32),
                                        axis=1).sum(axis=1)        # (S,)
                        # -- emit cur + accepted drafts; the tail beyond
                        # 1+a is overwritten by the next step's emission --
                        base = jnp.where(live, n_out, out.shape[1])
                        oidx = base[:, None] + jnp.arange(t_spec)[None, :]
                        out = out.at[bidx[:, None], oidx].set(window,
                                                              mode="drop")
                        n_out = n_out + jnp.where(live, 1 + a, 0)
                        n_it = n_it + live.astype(jnp.int32)
                        cur = jnp.where(live, nxt[bidx, a], cur)
                        pos = jnp.where(live, pos + 1 + a, pos)
                        return cur, pos, hist, n_out, n_it, pool, out

                    # Static trip count, exactly like the plain decode
                    # chunk: one compile per bucket, however the accept
                    # rate fluctuates.
                    return lax.fori_loop(
                        0, self.decode_chunk, body,
                        (cur, pos, hist, n_out, n_it, pool, out))

                return jax.jit(spec_chunk_fn, donate_argnums=(9,))

            self._make_spec_chunk = make_spec_chunk

        @partial(jax.jit, donate_argnums=(0,))
        def cow_copy(pool, src, dst):
            """src/dst: (n,) int32 — one dispatch copies a whole wave's
            boundary pages; pad pairs are (0, 0), a sink-to-sink no-op.
            Page axis is 2 for EVERY pool leaf (data and scale pages alike),
            so the copy is one structural map over the dict."""
            return {name: leaf.at[:, :, dst].set(leaf[:, :, src])
                    for name, leaf in pool.items()}

        self._cow = cow_copy
        self._writer_cache = {}
        # Page-shipping gather/scatter, jitted lazily per pow2 page-count
        # bucket (page axis 2 on every pool leaf, like _cow).
        self._ship_gather_cache = {}
        self._ship_scatter_cache = {}

    # -- stats ---------------------------------------------------------------
    # Phase counters: each ``*_seconds`` key is the host time of one engine
    # phase, also written as a profiler span by ``_span``; the step and row
    # counts beside them say how much of each dispatch did useful work.
    _PHASE_ZEROS = {"admit_waves": 0, "admit_plan_seconds": 0.0,
                    "admit_dispatch_seconds": 0.0, "admit_sync_seconds": 0.0,
                    "queue_wait_seconds": 0.0, "prefill_steps": 0,
                    "prefill_slot_tokens": 0, "decode_chunks": 0,
                    "decode_seconds": 0.0, "decode_prep_seconds": 0.0,
                    "decode_dispatch_seconds": 0.0,
                    "decode_sync_seconds": 0.0, "decode_post_seconds": 0.0,
                    "decode_slot_steps": 0, "decode_live_steps": 0}
    _STAT_ZEROS = {"admitted": 0, "prefill_tokens": 0, "cached_tokens": 0,
                   "cow_copies": 0, "admit_seconds": 0.0,
                   "spec_steps": 0, "spec_emitted": 0,
                   "preempted": 0, "resumed": 0,
                   "page_exports": 0, "page_imports": 0,
                   "page_demotes": 0, "page_restores": 0,
                   "accept_ema_sum": 0.0, "accept_ema_n": 0, **_PHASE_ZEROS}
    # Keys exported when bound to a MetricsRegistry; the scratch
    # accumulators (accept EMA terms) stay local-only.
    _STAT_EXPORTED = ("admitted", "prefill_tokens", "cached_tokens",
                      "cow_copies", "admit_seconds", "spec_steps",
                      "spec_emitted",
                      "preempted", "resumed", "page_exports", "page_imports",
                      "page_demotes", "page_restores", *_PHASE_ZEROS)

    def _reset_stats(self):
        stats = getattr(self, "stats", None)
        if isinstance(stats, RegistryDict):
            # Registry-bound: zero the local mirror in place. Counter
            # deltas are positive-only, so the bound series stay monotonic
            # across resets (Prometheus counter-reset semantics).
            for k, v in self._STAT_ZEROS.items():
                stats[k] = v
        else:
            self.stats = dict(self._STAT_ZEROS)

    def bind_registry(self, registry, engine: str) -> None:
        """Swap ``stats`` for a write-through view over ``registry``
        counters labeled ``{engine=...}``; pre-bind totals carry into the
        series and call sites keep the plain-dict idiom."""
        rd = RegistryDict()
        for key in self._STAT_EXPORTED:
            fam = registry.counter(
                f"kotta_engine_{key}_total",
                f"Engine {key.replace('_', ' ')} (cumulative)", ("engine",))
            rd.bind(key, fam, initial=self.stats[key], engine=engine)
        for key in self._STAT_ZEROS:
            if key not in self._STAT_EXPORTED:
                rd.bind(key, None, initial=self.stats[key])
        self.stats = rd

    @contextmanager
    def _span(self, name: str, key: str, **args):
        """One engine phase: a profiler span ``name`` (about a microsecond
        when no profiler runs) whose host seconds also add to
        ``stats[key]``. Yields the span, for metadata known only at its
        end (``set_metadata``)."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name, **args) as span:
            yield span
        self.stats[key] += time.perf_counter() - t0

    @property
    def prefix_hit_rate(self) -> float:
        tot = self.stats["cached_tokens"] + self.stats["prefill_tokens"]
        return self.stats["cached_tokens"] / tot if tot else 0.0

    @property
    def mean_accepted_len(self) -> float:
        """Mean drafted tokens accepted per verify step (0 <= . <= K).

        Every verify step emits 1 + accepted tokens, so this is
        (emitted - steps) / steps over the last ``generate`` call.
        """
        steps = self.stats["spec_steps"]
        return (self.stats["spec_emitted"] - steps) / steps if steps else 0.0

    @property
    def mean_accept_ema(self) -> float:
        """Mean final per-slot accept-rate EMA over retired requests."""
        n = self.stats["accept_ema_n"]
        return self.stats["accept_ema_sum"] / n if n else 0.0

    def slot_spec_state(self) -> dict[int, dict[str, float]]:
        """Live per-slot adaptive-speculation state (bench introspection)."""
        return {slot: {"kslot": int(self._kslot[slot]),
                       "accept_ema": float(self._ema[slot])}
                for slot in sorted(self._live)}

    # -- legacy dense page writer (prompt KV -> pool), per (pad, group) ------
    def _write_pages(self, k, v, pages):
        """k/v: (L, G, S_pad, KV, hd) prompt cache; pages: (G * npp,) int32."""
        key = (k.shape[1], k.shape[2])
        if key not in self._writer_cache:
            ps = self.page_size

            @partial(jax.jit, donate_argnums=(0, 1))
            def write(pool_k, pool_v, k, v, pages):
                nl, g, s_pad, nkv, hd = k.shape
                npp = g * (s_pad // ps)
                kp = k.reshape(nl, npp, ps, nkv, hd).transpose(0, 3, 1, 2, 4)
                vp = v.reshape(nl, npp, ps, nkv, hd).transpose(0, 3, 1, 2, 4)
                pool_k = pool_k.at[:, :, pages].set(kp.astype(pool_k.dtype))
                pool_v = pool_v.at[:, :, pages].set(vp.astype(pool_v.dtype))
                return pool_k, pool_v

            self._writer_cache[key] = write
        self.pool["k"], self.pool["v"] = self._writer_cache[key](
            self.pool["k"], self.pool["v"], k, v,
            jnp.asarray(pages, jnp.int32))

    # -- admission -----------------------------------------------------------
    def _admit_wave(self) -> int:
        """Admit requests from the queue, front-first, while slots and pages
        last.

        The queue's order IS the admission policy: ``generate`` keeps it
        FCFS, the serving gateway keeps it deadline/cost-ordered
        (:mod:`repro.serve.admission`) — ``_admit_wave`` just consumes it.

        Each accepted request first consults the prefix cache (within the
        request's namespace): fully matched pages are aliased into its
        page-table row (refcount++), a partially matched boundary page is
        copy-on-written, and only the remaining suffix is prefilled — chunk
        by chunk, batched across the wave.

        **Same-wave dedup:** a request's pages are registered in the radix
        index the moment it is accepted, so a later request in the SAME
        wave (e.g. an identical prompt) aliases them instead of prefilling
        privately. Content for those pages only exists after the donor's
        prefill runs, so the wave is prefilled in dependency *groups*: a
        request that aliases an in-wave donor lands in a later group than
        the donor, each group is one batched prefill, and a group's
        copy-on-write boundary copies are dispatched after its donors'
        group has prefilled but before its own prefill reads them.

        A wave is three phases (:meth:`_span`): ``plan`` (the queue loop,
        host only), ``prefill`` (every group's copies and chunk steps,
        dispatched without waiting) and ``sync`` (the first tokens read
        back, the wave's only wait on the device).
        """
        with self._span("engine.admit", "admit_seconds") as span:
            with self._span("engine.admit.plan", "admit_plan_seconds"):
                wave, cow_pairs = self._plan_wave()
            if wave:
                with self._span("engine.admit.prefill",
                                "admit_dispatch_seconds"):
                    firsts = []
                    for g in sorted({a.group for a in wave}):
                        self._dispatch_cows(cow_pairs.get(g, ()))
                        members = [a for a in wave if a.group == g]
                        if self.prefill_mode == "dense":
                            firsts += self._prefill_dense(members)
                        else:
                            firsts += self._prefill_paged_wave(members)
                    if self.spec_decode:
                        self._load_histories(wave)
                with self._span("engine.admit.sync", "admit_sync_seconds"):
                    self._take_first_tokens(firsts)
                    for a in wave:
                        self._live[a.slot] = _Live(a.req, a.pages)
                self.stats["admitted"] += len(wave)
                self.stats["admit_waves"] += 1
            span.set_metadata(requests=len(wave))
        return len(wave)

    def _plan_wave(self):
        """The queue loop of :meth:`_admit_wave`: take requests front-first
        while a slot and their pages last, reserve both, and add each one's
        wait from ``enqueue`` to this wave's start to ``queue_wait_seconds``.
        Returns the wave and its copy-on-write pairs by prefill group."""
        t_wave = time.perf_counter()
        ps = self.page_size
        wave: list[_Admit] = []
        cow_pairs: dict[int, list[tuple[int, int]]] = {}   # group -> pairs
        page_group: dict[int, int] = {}    # page -> group whose prefill fills it
        while self._queue:
            req, t_enq = self._queue[0]
            prompt = req.prompt
            plen = len(prompt)
            free_slots = [i for i in range(self.max_slots)
                          if not self._active[i]]
            if not free_slots:
                break
            need_total = math.ceil((plen + req.max_new) / ps)  # checked at
            if self.prefix_cache is not None:                  # enqueue
                chain, raw = self.prefix_cache.lookup(prompt, req.namespace)
                # Always recompute at least the last prompt token: its logits
                # seed decode, and capping also keeps a fully-cached prompt
                # from needing zero prefill steps.
                match = min(raw, plen - 1)
            else:
                chain, match = [], 0
            n_alias, cow_m = divmod(match, ps)
            cow_src = chain[n_alias] if cow_m else None
            n_fresh = need_total - n_alias
            # Pin every matched page (incl. the copy-on-write source) BEFORE
            # allocating: a cache hit on a retired request's page finds it in
            # the free list, and an unpinned hit could be reallocated as one
            # of our own fresh pages, clobbering the prefix it still holds.
            shared = chain[:n_alias]
            for p in shared:
                self.alloc.share(p)
            if cow_src is not None:
                self.alloc.share(cow_src)
            if self.alloc.available() < n_fresh:
                for p in shared:                # not enough pages: wave ends
                    self.alloc.release(p)
                if cow_src is not None:
                    self.alloc.release(cow_src)
                break
            slot = free_slots[0]
            fresh = [self.alloc.alloc() for _ in range(n_fresh)]
            pages = shared + fresh
            # Aliasing an in-wave donor sequences us after its prefill.
            deps = shared if cow_src is None else shared + [cow_src]
            group = 1 + max((page_group.get(p, 0) for p in deps), default=0)
            if cow_src is not None:
                # Boundary page: first cow_m rows of the matched page are this
                # prompt's KV; copy them into our private page and append.
                # The copy is deferred to our group's dispatch — the pin on
                # cow_src holds until it lands.
                cow_pairs.setdefault(group, []).append((cow_src, fresh[0]))
                self.stats["cow_copies"] += 1
            for p in fresh:
                page_group[p] = group
            self._active[slot] = True          # reserve within this wave
            row = np.zeros(self.pages_per_seq, np.int32)
            row[:len(pages)] = pages
            self._page_table[slot] = row
            self.stats["cached_tokens"] += match
            self.stats["prefill_tokens"] += plen - match
            wave.append(_Admit(slot, req, pages, match, group))
            if self.prefix_cache is not None:
                # Publish now so the rest of this wave can alias; the grouped
                # prefill below guarantees the content lands first.
                self.prefix_cache.register(prompt, pages, req.namespace)
            self._queue.popleft()
            self.stats["queue_wait_seconds"] += t_wave - t_enq
        return wave, cow_pairs

    def _dispatch_cows(self, cow_pairs) -> None:
        """One device dispatch copies a prefill group's boundary pages,
        padded to a pow2 bucket (pad pairs write sink -> sink)."""
        if not cow_pairs:
            return
        n = _next_pow2(len(cow_pairs))
        src = np.zeros(n, np.int32)
        dst = np.zeros(n, np.int32)
        for i, (s_, d_) in enumerate(cow_pairs):
            src[i], dst[i] = s_, d_
        self.pool = self._cow(self.pool, jnp.asarray(src), jnp.asarray(dst))
        for s_, _ in cow_pairs:
            self.alloc.release(s_)              # pin no longer needed

    def _take_first_tokens(self, firsts) -> None:
        """Read each admitted request's first token back from its prefill
        output, ``firsts`` = [(slot, device array, row, prompt length)]:
        one host read per distinct array."""
        host: dict[int, np.ndarray] = {}
        for slot, arr, row, plen in firsts:
            if id(arr) not in host:
                host[id(arr)] = np.asarray(arr)
            self._cur[slot] = host[id(arr)][row]
            self._pos[slot] = plen

    def _load_histories(self, wave: list[_Admit]) -> None:
        """Seed the on-device drafting history + write limit for new slots."""
        rows = np.zeros((len(wave), self.hist_len), np.int32)
        slots = np.zeros(len(wave), np.int32)
        for i, a in enumerate(wave):
            rows[i, :len(a.req.prompt)] = a.req.prompt
            slots[i] = a.slot
            self._limit[a.slot] = len(a.req.prompt) + a.req.max_new
            # Speculation starts wide open; the per-chunk EMA update shrinks
            # the window if this request's drafts keep getting rejected.
            self._kslot[a.slot] = self.spec_tokens
            self._ema[a.slot] = 0.0
        self._hist = self._hist.at[jnp.asarray(slots)].set(jnp.asarray(rows))

    # -- paged chunked prefill (default admission path) ----------------------
    def _prefill_paged_wave(self, wave: list[_Admit]) -> list:
        """Prefill every wave member's suffix in fixed-width chunk steps.

        The batch is padded to a power-of-two bucket so the jitted step sees
        a handful of (bucket, chunk) signatures total — never one per prompt
        length. Pad rows carry ``kv_len=0`` so all their KV writes land in
        the sink page. Nothing waits for the device: returns, per member,
        where its first token lies (see :meth:`_take_first_tokens`).
        """
        ps, c = self.page_size, self.prefill_chunk
        gp = _next_pow2(len(wave))
        page_tables = np.zeros((gp, self.pages_per_seq), np.int32)
        for i, a in enumerate(wave):
            page_tables[i] = self._page_table[a.slot]
        pt_dev = jnp.asarray(page_tables)
        nsteps = max(math.ceil((len(a.req.prompt) - a.start) / c)
                     for a in wave)

        step_toks = []
        for j in range(nsteps):
            toks = np.zeros((gp, c), np.int32)
            qs = np.zeros(gp, np.int32)
            kl = np.zeros(gp, np.int32)
            li = np.zeros(gp, np.int32)
            for i, a in enumerate(wave):
                plen = len(a.req.prompt)
                s0 = a.start + j * c
                qs[i] = s0
                kl[i] = plen
                li[i] = plen - 1 - s0                 # clamped in the step
                seg = a.req.prompt[s0:s0 + c]
                if seg:
                    toks[i, :len(seg)] = seg
            batch = {"tokens": jnp.asarray(toks), "q_start": jnp.asarray(qs),
                     "kv_len": jnp.asarray(kl), "page_table": pt_dev,
                     "logit_idx": jnp.asarray(li)}
            nxt, _, self.pool = self._prefill_chunked(self.params, batch,
                                                      self.pool)
            step_toks.append(nxt)
        self.stats["prefill_steps"] += nsteps
        self.stats["prefill_slot_tokens"] += nsteps * gp * c

        # The first sampled token of request i comes from the chunk holding
        # position plen-1.
        return [(a.slot, step_toks[(len(a.req.prompt) - 1 - a.start) // c],
                 i, len(a.req.prompt)) for i, a in enumerate(wave)]

    # -- dense ragged prefill (PR-1 baseline, kept as in-engine oracle) ------
    def _prefill_dense(self, wave: list[_Admit]) -> list:
        """Batched-by-pad-bucket dense prefill + page re-layout (legacy);
        returns where each member's first token lies, like
        :meth:`_prefill_paged_wave`."""
        ps = self.page_size
        firsts = []
        by_pad: dict[int, list[_Admit]] = {}
        for a in wave:
            s_pad = math.ceil(len(a.req.prompt) / ps) * ps
            by_pad.setdefault(s_pad, []).append(a)

        for s_pad, items in by_pad.items():
            g = len(items)
            npp = s_pad // ps
            toks = np.zeros((g, s_pad), np.int32)
            lens = np.zeros(g, np.int32)
            for i, a in enumerate(items):
                toks[i, :len(a.req.prompt)] = a.req.prompt
                lens[i] = len(a.req.prompt)
            batch = {"tokens": jnp.asarray(toks),
                     "length": jnp.asarray(lens)}
            logits, cache = self._prefill_ragged(self.params, batch)
            prompt_pages = np.concatenate(
                [np.asarray(a.pages[:npp], np.int32) for a in items])
            self._write_pages(cache["k"], cache["v"], prompt_pages)
            self.stats["prefill_steps"] += 1
            self.stats["prefill_slot_tokens"] += g * s_pad
            first = jnp.argmax(logits, axis=-1)
            firsts += [(a.slot, first, i, len(a.req.prompt))
                       for i, a in enumerate(items)]
        return firsts

    def _retire(self, slot: int) -> _Live:
        live = self._live.pop(slot)
        for p in live.pages:
            self.alloc.release(p)       # refcount--: aliased pages survive
        self._active[slot] = False
        self._page_table[slot] = 0          # all-zero row -> sink page 0
        self._pos[slot] = 0
        self._cur[slot] = 0
        self._limit[slot] = 0               # spec writes masked until re-seeded
        if self.spec_decode:
            # Fold the request's final accept-rate EMA into the run stats
            # (serve_bench reports the mean) before clearing the slot.
            self.stats["accept_ema_sum"] += float(self._ema[slot])
            self.stats["accept_ema_n"] += 1
            self._kslot[slot] = 0
            self._ema[slot] = 0.0
        return live

    # -- invariants (exercised by tests) -------------------------------------
    def _debug_check_refcounts(self) -> None:
        """Every physical page's refcount == page-table rows referencing it
        (a paused request's pinned pages count as one reference each)."""
        counts = np.zeros(self.num_pages, np.int64)
        for live in self._live.values():
            for p in live.pages:
                counts[p] += 1
        for paused in self._paused.values():
            for p in paused.pages:
                counts[p] += 1
        if not np.array_equal(counts[1:], self.alloc.refs[1:]):
            bad = np.nonzero(counts[1:] != self.alloc.refs[1:])[0] + 1
            raise AssertionError(
                f"refcount drift on pages {bad.tolist()}: "
                f"rows={counts[bad].tolist()} refs={self.alloc.refs[bad].tolist()}")

    # -- stepped serving API (the gateway drives these) ----------------------
    def _validate_request(self, req: EngineRequest) -> None:
        """Reject requests that can never run — before reserving anything."""
        p = req.prompt
        max_len = self.pages_per_seq * self.page_size
        pool_cap = self.num_pages - 1
        if not p:
            raise ValueError(f"request {req.rid}: empty prompt (nothing to "
                             "prefill)")
        if len(p) + req.max_new > max_len:
            raise ValueError(f"request {req.rid}: {len(p)}+{req.max_new} "
                             f"tokens exceed max_len {max_len}")
        need = math.ceil((len(p) + req.max_new) / self.page_size)
        if need > pool_cap:
            raise ValueError(
                f"request {req.rid}: needs {need} pages for "
                f"{len(p)}+{req.max_new} tokens but the pool only holds "
                f"{pool_cap}; raise num_pages or shorten the request")

    def enqueue(self, req: EngineRequest) -> None:
        """Append a validated request to the admission queue."""
        self._validate_request(req)
        self._queue.append((req, time.perf_counter()))

    def admit(self) -> int:
        """Run one admission wave off the queue; returns requests admitted."""
        return self._admit_wave()

    @property
    def queued(self) -> int:
        return len(self._queue)

    @property
    def live(self) -> int:
        return len(self._live)

    @property
    def paused(self) -> int:
        return len(self._paused)

    @property
    def free_slots(self) -> int:
        """Physically unoccupied decode slots. Paused requests hold pages but
        no slot, so this is what ``resume`` needs to be positive."""
        return int(self.max_slots - np.count_nonzero(self._active))

    @property
    def has_work(self) -> bool:
        return bool(self._queue or self._live or self._paused)

    @property
    def open_slots(self) -> int:
        """Slots not yet claimed by a live or queued request (the gateway
        dispatches new work only while this is positive, keeping the
        per-replica queue shallow so EDF reordering stays effective)."""
        return self.max_slots - len(self._live) - len(self._queue)

    def remaining_tokens(self) -> list[int]:
        """Per live slot, tokens still to emit — scheduling estimates."""
        return [l.req.max_new - l.emitted for l in self._live.values()]

    def preempt(self, slot: int) -> PausedRequest:
        """Pause the request in ``slot`` mid-stream and free the slot.

        The request's KV pages stay allocated and **pinned**: their
        refcounts are untouched, so the allocator can never reallocate them
        (and eviction-on-realloc can never scrub the prefix-cache entries
        they anchor) however hard later admissions churn the pool. The
        cursor, emitted-token tally, write limit and — under speculative
        decode — the slot's drafting-history row park host-side in the
        returned :class:`PausedRequest`. ``resume`` undoes all of it with
        zero re-prefill.
        """
        if slot not in self._live:
            raise KeyError(f"slot {slot} has no live request to preempt")
        live = self._live.pop(slot)
        hist = np.array(self._hist[slot]) if self.spec_decode else None
        paused = PausedRequest(
            req=live.req, pages=live.pages, emitted=live.emitted,
            tokens=live.tokens, cur=int(self._cur[slot]),
            pos=int(self._pos[slot]), limit=int(self._limit[slot]),
            hist=hist, kslot=int(self._kslot[slot]),
            ema=float(self._ema[slot]))
        self._paused[live.req.rid] = paused
        # Identical to _retire EXCEPT the pages are not released: the slot
        # idles against the sink page while the paused sequence's KV waits.
        self._active[slot] = False
        self._page_table[slot] = 0
        self._pos[slot] = 0
        self._cur[slot] = 0
        self._limit[slot] = 0
        self._kslot[slot] = 0
        self._ema[slot] = 0.0
        self.stats["preempted"] += 1
        return paused

    def resume(self, paused: PausedRequest) -> int:
        """Re-admit a preempted request into a free slot; returns the slot.

        Zero re-prefill: the parked pages are re-attached through the page
        table, the cursor/limit/history restored, and decoding continues
        exactly where :meth:`preempt` stopped it — greedy tokens are
        identical to a never-paused run. Raises ``PoolCapacityError`` when
        every slot is occupied (check :attr:`free_slots` first).
        """
        if self._paused.get(paused.req.rid) is not paused:
            raise KeyError(f"request {paused.req.rid} is not paused on this "
                           "engine")
        free = [i for i in range(self.max_slots) if not self._active[i]]
        if not free:
            raise PoolCapacityError("no free slot to resume into")
        slot = free[0]
        del self._paused[paused.req.rid]
        row = np.zeros(self.pages_per_seq, np.int32)
        row[:len(paused.pages)] = paused.pages
        self._page_table[slot] = row
        self._active[slot] = True
        self._pos[slot] = paused.pos
        self._cur[slot] = paused.cur
        self._limit[slot] = paused.limit
        if self.spec_decode:
            self._hist = self._hist.at[slot].set(jnp.asarray(paused.hist))
            # Restore the tuned speculation window (0 = paused before this
            # engine tracked it; re-warm from K).
            self._kslot[slot] = paused.kslot or self.spec_tokens
            self._ema[slot] = paused.ema
        self._live[slot] = _Live(paused.req, paused.pages, paused.emitted,
                                 paused.tokens)
        self.stats["resumed"] += 1
        return slot

    # -- page residency (one export seam: cross-replica shipping and
    # cross-tier demotion are two transports behind the same gather) ---------
    def export(self, slot: int | None = None, *, rid: object = None,
               reason: ExportReason = ExportReason.HANDOFF) -> ShippedKV:
        """Ship a request out of this engine as a :class:`ShippedKV`.

        THE residency exit point, unifying what used to be two parallel
        methods: pass ``slot=`` for a live request (gathered, then retired
        through the normal refcount path) or ``rid=`` for a request parked
        by :meth:`preempt` (gathered, then its pin dropped). Exactly one
        must be given — slot ints and caller rids can share values, so
        positional guessing would be ambiguous. ``reason`` tags the payload
        with *why* the pages left (handoff / evacuate / demote) without
        changing the gather.

        Only *content* pages travel — the ``ceil(pos / page_size)`` pages
        holding prefilled (and already-decoded) KV rows; trailing pages
        allocated against the decode budget are empty and are simply
        released. Aliased prefix pages are gathered like any other page, so
        the payload is always a self-contained private copy; this engine's
        prefix-cache entries survive, keeping the replica a valid affinity
        target for the next request with the same prefix.

        Works mid-decode, not just post-prefill: a request that already
        emitted tokens ships its decoded KV rows, emitted tokens, and (spec
        decode) its drafting history plus tuned kslot/accept-EMA. Greedy
        decode continues token-identically wherever the payload lands.
        """
        if (slot is None) == (rid is None):
            raise ValueError("export needs exactly one of slot= (live "
                             "request) or rid= (paused request)")
        if slot is not None:
            if slot not in self._live:
                raise KeyError(f"slot {slot} has no live request to export")
            live = self._live[slot]
            hist = np.array(self._hist[slot]) if self.spec_decode else None
            payload = self._export(
                req=live.req, emitted=live.emitted, tokens=list(live.tokens),
                cur=int(self._cur[slot]), pos=int(self._pos[slot]),
                pages=live.pages, hist=hist, kslot=int(self._kslot[slot]),
                ema=float(self._ema[slot]), reason=reason)
            self._retire(slot)
            return payload
        paused = self._paused.get(rid)
        if paused is None:
            raise KeyError(f"request {rid} is not paused on this engine")
        payload = self._export(
            req=paused.req, emitted=paused.emitted,
            tokens=list(paused.tokens), cur=paused.cur, pos=paused.pos,
            pages=paused.pages, hist=paused.hist, kslot=paused.kslot,
            ema=paused.ema, reason=reason)
        del self._paused[rid]
        for p in paused.pages:
            self.alloc.release(p)       # unpin: aliased pages survive
        return payload

    def export_pages(self, slot: int, *,
                     reason: ExportReason = ExportReason.HANDOFF
                     ) -> ShippedKV:
        """Deprecated alias for ``export(slot=...)`` (pre-residency name)."""
        return self.export(slot=slot, reason=reason)

    def export_paused(self, rid: object, *,
                      reason: ExportReason = ExportReason.EVACUATE
                      ) -> ShippedKV:
        """Deprecated alias for ``export(rid=...)`` (pre-residency name)."""
        return self.export(rid=rid, reason=reason)

    def _export(self, *, req, emitted, tokens, cur, pos, pages, hist,
                kslot, ema,
                reason: ExportReason = ExportReason.HANDOFF) -> ShippedKV:
        """Gather ``ceil(pos/page_size)`` content pages into a payload."""
        ps = self.page_size
        n_content = math.ceil(pos / ps)
        nb = _next_pow2(max(1, n_content))
        idx = np.zeros(nb, np.int32)            # pads gather the sink page
        idx[:n_content] = pages[:n_content]
        gather = self._ship_gather_cache.get(nb)
        if gather is None:
            def gather_fn(pool, idx):
                return {name: leaf[:, :, idx] for name, leaf in pool.items()}
            gather = self._ship_gather_cache[nb] = jax.jit(gather_fn)
        gathered = gather(self.pool, jnp.asarray(idx))
        content = {name: np.ascontiguousarray(
                       np.asarray(a)[:, :, :n_content])
                   for name, a in gathered.items()}
        self.stats["page_exports"] += 1
        return ShippedKV(
            req=req, emitted=emitted, tokens=tokens, cur=cur, pos=pos,
            content=content, kv_cache_dtype=self.kv_cache_dtype,
            page_size=ps, hist=hist, kslot=kslot, ema=ema, reason=reason)

    def page_nbytes(self) -> int:
        """Wire bytes of ONE shipped page across every pool leaf (data +
        scale pages) — what the evacuation planner multiplies by a
        request's content-page count to budget the notice window without
        exporting first."""
        return sum(leaf.nbytes // leaf.shape[2] for leaf in
                   self.pool.values())

    def import_pages(self, payload: ShippedKV) -> int:
        """Re-register a :class:`ShippedKV` payload here; returns the slot.

        Fresh pages come from THIS engine's allocator (the full
        prompt+budget span, not just the shipped content pages), the
        page-table row re-attaches them, the prompt re-registers in this
        engine's radix prefix cache (existing entries win, exactly like
        admission), and the decode cursor resumes where the source stopped —
        greedy tokens are identical to a run that never hopped. Raises
        ``ValueError`` on a layout mismatch or a re-imported payload and
        ``PoolCapacityError`` when no slot or not enough pages are free (the
        caller retries later — only a *successful* import marks the payload
        consumed).
        """
        if payload.consumed:
            raise ValueError(
                f"payload for request {payload.req.rid} was already "
                "imported; a ShippedKV is a one-shot move, not a template")
        if payload.kv_cache_dtype != self.kv_cache_dtype:
            raise ValueError(
                f"shipped pages are {payload.kv_cache_dtype!r} but this "
                f"engine's pool is {self.kv_cache_dtype!r}")
        if payload.page_size != self.page_size:
            raise ValueError(
                f"shipped page_size {payload.page_size} != engine "
                f"page_size {self.page_size}")
        if set(payload.content) != set(self.pool):
            raise ValueError(
                f"shipped pool leaves {sorted(payload.content)} != engine "
                f"pool leaves {sorted(self.pool)}")
        req = payload.req
        self._validate_request(req)
        if payload.pos != len(req.prompt) + payload.emitted:
            raise ValueError(
                f"inconsistent payload for request {req.rid}: pos "
                f"{payload.pos} != prompt {len(req.prompt)} + emitted "
                f"{payload.emitted}")
        free = [i for i in range(self.max_slots) if not self._active[i]]
        if not free:
            raise PoolCapacityError("no free slot to import into")
        ps = self.page_size
        need_total = math.ceil((len(req.prompt) + req.max_new) / ps)
        n_content = payload.n_content
        if n_content > need_total:
            raise ValueError(
                f"payload ships {n_content} content pages but request "
                f"{req.rid} spans only {need_total}")
        if self.alloc.available() < need_total:
            raise PoolCapacityError(
                f"insufficient free pages to import request {req.rid}: "
                f"need {need_total}, have {self.alloc.available()}")
        slot = free[0]
        pages = [self.alloc.alloc() for _ in range(need_total)]
        nb = _next_pow2(max(1, n_content))
        dst = np.zeros(nb, np.int32)            # pads scatter into the sink
        dst[:n_content] = pages[:n_content]
        scatter = self._ship_scatter_cache.get(nb)
        if scatter is None:
            @partial(jax.jit, donate_argnums=(0,))
            def scatter_fn(pool, content, dst):
                return {name: pool[name].at[:, :, dst].set(
                            content[name].astype(pool[name].dtype))
                        for name in pool}
            scatter = self._ship_scatter_cache[nb] = scatter_fn
        padded = {}
        for name, a in payload.content.items():
            buf = np.zeros(a.shape[:2] + (nb,) + a.shape[3:], a.dtype)
            buf[:, :, :n_content] = a
            padded[name] = jnp.asarray(buf)
        self.pool = scatter(self.pool, padded, jnp.asarray(dst))
        row = np.zeros(self.pages_per_seq, np.int32)
        row[:need_total] = pages
        self._page_table[slot] = row
        self._active[slot] = True
        self._pos[slot] = payload.pos
        self._cur[slot] = payload.cur
        self._limit[slot] = len(req.prompt) + req.max_new
        if self.spec_decode:
            # Seed the drafting history: the parked row if the source ran
            # spec decode too, else reconstructed from prompt + emitted
            # tokens (identical content — draft tails past pos are always
            # re-written before any read).
            if payload.hist is not None and \
                    len(payload.hist) == self.hist_len:
                hrow = np.asarray(payload.hist, np.int32)
            else:
                hrow = np.zeros(self.hist_len, np.int32)
                hrow[:len(req.prompt)] = req.prompt
                hrow[len(req.prompt):payload.pos] = payload.tokens[
                    :payload.pos - len(req.prompt)]
            self._hist = self._hist.at[slot].set(jnp.asarray(hrow))
            # Restore the source's tuned speculation window, capped at this
            # engine's K (0 = the source never tracked one: warm from K).
            self._kslot[slot] = min(payload.kslot, self.spec_tokens) \
                or self.spec_tokens
            self._ema[slot] = payload.ema
        if self.prefix_cache is not None:
            # The shipped prefix stays shareable after the hop: later
            # requests on THIS engine alias these pages instead of
            # re-prefilling (existing entries win, exactly like admission).
            self.prefix_cache.register(req.prompt, pages, req.namespace)
        self._live[slot] = _Live(req, pages, payload.emitted,
                                 list(payload.tokens))
        self.stats["page_imports"] += 1
        payload.consumed = True
        return slot

    def restore_pages(self, payload: ShippedKV) -> list[int]:
        """Land a demoted payload's content pages back in the device pool
        WITHOUT occupying a decode slot; returns the restored page list.

        The tier-restore transport behind the residency API: the store's
        payload is scattered into freshly allocated pages, the covered
        token stream (prompt + emitted tokens) is registered in the radix
        prefix cache under the payload's namespace, and the pages are
        immediately released to refcount zero — *free-but-hittable*,
        exactly the state a retired request's pages occupy. The next
        admission of a prompt sharing the stream pins and aliases them
        with **zero re-prefill**; if nothing claims them, the allocator
        reuses them and eviction scrubs the entries as usual. int8 scale
        pages scatter alongside their data pages (the content dict is
        structural), so token identity holds for f32 and int8 pools alike.

        Raises ``ValueError`` on layout mismatch / re-used payload and
        ``PoolCapacityError`` when fewer than ``n_content`` pages are free (the
        caller retries a later round).
        """
        if payload.consumed:
            raise ValueError(
                f"payload for request {payload.req.rid} was already "
                "imported or restored; a ShippedKV is a one-shot move")
        if payload.kv_cache_dtype != self.kv_cache_dtype:
            raise ValueError(
                f"restored pages are {payload.kv_cache_dtype!r} but this "
                f"engine's pool is {self.kv_cache_dtype!r}")
        if payload.page_size != self.page_size:
            raise ValueError(
                f"restored page_size {payload.page_size} != engine "
                f"page_size {self.page_size}")
        if set(payload.content) != set(self.pool):
            raise ValueError(
                f"restored pool leaves {sorted(payload.content)} != engine "
                f"pool leaves {sorted(self.pool)}")
        if self.prefix_cache is None:
            raise RuntimeError("restore_pages needs the prefix cache: "
                               "restored pages are only reachable through "
                               "the radix index")
        n_content = payload.n_content
        if self.alloc.available() < n_content:
            raise PoolCapacityError(
                f"insufficient free pages to restore request "
                f"{payload.req.rid}: need {n_content}, have "
                f"{self.alloc.available()}")
        pages = [self.alloc.alloc() for _ in range(n_content)]
        nb = _next_pow2(max(1, n_content))
        dst = np.zeros(nb, np.int32)            # pads scatter into the sink
        dst[:n_content] = pages
        scatter = self._ship_scatter_cache.get(nb)
        if scatter is None:
            @partial(jax.jit, donate_argnums=(0,))
            def scatter_fn(pool, content, dst):
                return {name: pool[name].at[:, :, dst].set(
                            content[name].astype(pool[name].dtype))
                        for name in pool}
            scatter = self._ship_scatter_cache[nb] = scatter_fn
        padded = {}
        for name, a in payload.content.items():
            buf = np.zeros(a.shape[:2] + (nb,) + a.shape[3:], a.dtype)
            buf[:, :, :n_content] = a
            padded[name] = jnp.asarray(buf)
        self.pool = scatter(self.pool, padded, jnp.asarray(dst))
        # Register the full covered token stream — prompt plus the tokens
        # decoded before demotion — so a resumed session's longer prompt
        # walks straight down the restored chain.
        req = payload.req
        stream = list(req.prompt) + list(
            payload.tokens[:payload.pos - len(req.prompt)])
        self.prefix_cache.register(stream, pages, req.namespace)
        for p in pages:
            self.alloc.release(p)       # free-but-hittable, like retirement
        self.stats["page_restores"] += 1
        payload.consumed = True
        return pages

    def drop_queued(self) -> list[EngineRequest]:
        """Hand back queued-but-unadmitted requests (e.g. transient page
        pressure); live and paused requests are untouched."""
        dropped = [req for req, _ in self._queue]
        self._queue.clear()
        return dropped

    def abort(self) -> list[EngineRequest]:
        """Drop all live, paused and queued requests; return them for
        re-enqueue.

        The spot-revocation path: a revoked replica's requests restart from
        scratch on another replica (greedy decode is deterministic, so the
        retry emits identical tokens). Pages — including a paused request's
        pinned pages — are released through the normal refcount path, so
        refcounts stay exact and cached prefixes survive until reallocated.
        """
        dropped = [self._live[s].req for s in sorted(self._live)]
        for slot in list(self._live):
            self._retire(slot)
        for paused in self._paused.values():
            for p in paused.pages:
                self.alloc.release(p)
            dropped.append(paused.req)
        self._paused.clear()
        dropped.extend(req for req, _ in self._queue)
        self._queue.clear()
        return dropped

    def decode_step(self, on_chunk=None) -> list[tuple[EngineRequest,
                                                       list[int]]]:
        """Run ONE on-device decode chunk; returns requests that finished.

        ``on_chunk(steps, seconds)`` (optional) observes the chunk.
        ``steps`` is the chunk's *device* trip count — always
        ``decode_chunk`` — so ``seconds / steps`` is the inter-token
        latency. It is NOT a count of usable tokens: a slot whose
        ``max_new`` budget ends mid-chunk idles (masked against the sink
        page) for the remaining steps. Under speculative decode one step
        emits 1..spec_tokens+1 tokens per slot, so ``seconds / steps`` is
        per-VERIFY-step latency there.

        A chunk is four phases (:meth:`_span`): ``prep`` (budgets, bucket
        choice, inputs to the device), ``dispatch`` (the jitted chunk call),
        ``sync`` (the chunk's outputs read back, its only wait on the
        device) and ``post`` (tokens to their requests, retirement).
        ``seconds`` runs from after the budgets are built to the end of
        ``sync``.
        """
        if self.role == "prefill":
            raise RuntimeError("decode_step on a prefill-role engine: "
                               "export_pages its admitted slots to a "
                               "decode-role replica instead")
        if not self._live:
            return []
        with self._span("engine.decode", "decode_seconds",
                        live=len(self._live)):
            with self._span("engine.decode.prep", "decode_prep_seconds"):
                budget = np.zeros(self.max_slots, np.int32)
                for slot, live in self._live.items():
                    budget[slot] = live.req.max_new - live.emitted
                t0 = time.perf_counter()
                if self.spec_decode:
                    # Smallest verify bucket covering every live slot's
                    # adaptive window: a chunk full of low-acceptance slots
                    # dispatches a genuinely narrower verify pass. Chunks are
                    # jitted lazily per bucket; non-adaptive engines always
                    # land on bucket K.
                    kslot = np.maximum(np.where(self._active, self._kslot,
                                                1), 1)
                    kmax = int(kslot[self._active].max())
                    kb = min(b for b in self._spec_buckets if b >= kmax)
                    chunk = self._spec_chunks.get(kb)
                    if chunk is None:
                        chunk = self._spec_chunks[kb] = \
                            self._make_spec_chunk(kb)
                    args = (jnp.asarray(self._cur), jnp.asarray(self._pos),
                            self._hist, jnp.asarray(self._page_table),
                            jnp.asarray(self._active), jnp.asarray(budget),
                            jnp.asarray(self._limit),
                            jnp.asarray(kslot.astype(np.int32)))
                else:
                    chunk = self._chunk
                    args = (jnp.asarray(self._cur), jnp.asarray(self._pos),
                            jnp.asarray(self._page_table),
                            jnp.asarray(self._active), jnp.asarray(budget))
            with self._span("engine.decode.dispatch",
                            "decode_dispatch_seconds"):
                if self.spec_decode:
                    cur, pos, self._hist, n_out, n_it, self.pool, out = \
                        chunk(self.params, *args, self.pool)
                else:
                    cur, pos, self.pool, out = chunk(self.params, *args,
                                                     self.pool)
            with self._span("engine.decode.sync", "decode_sync_seconds"):
                out_host = np.asarray(out)              # one sync per chunk
                if self.spec_decode:
                    n_out_host = np.asarray(n_out)
                    n_it_host = np.asarray(n_it)
                else:                      # every live slot emits the chunk
                    n_out_host = n_it_host = None
            if on_chunk is not None:
                on_chunk(self.decode_chunk, time.perf_counter() - t0)
            with self._span("engine.decode.post", "decode_post_seconds"):
                if self.spec_decode:
                    self._adapt_windows(kslot, n_out_host, n_it_host)
                finished = self._take_chunk(cur, pos, out_host, n_out_host,
                                            n_it_host)
            self.stats["decode_chunks"] += 1
            self.stats["decode_slot_steps"] += \
                self.max_slots * self.decode_chunk
        return finished

    def _adapt_windows(self, kslot, n_out_host, n_it_host) -> None:
        """Per-slot accept-rate EMA and adaptive window control after a
        speculative chunk. rate = accepted drafts / drafted tokens this
        chunk; EMA with alpha=0.5 reacts within a couple of chunks. High
        acceptance re-opens the window (x2, capped at K), low acceptance
        halves it (floor 1) so near-random content stops paying for K-token
        verify rows it never accepts."""
        self.stats["spec_steps"] += int(n_it_host.sum())
        for slot in self._live:
            it = int(n_it_host[slot])
            if not it:
                continue
            rate = (int(n_out_host[slot]) - it) / (it * int(kslot[slot]))
            self._ema[slot] = 0.5 * self._ema[slot] + 0.5 * rate
            if self.spec_adaptive_k:
                if self._ema[slot] > 0.8:
                    self._kslot[slot] = min(2 * int(self._kslot[slot]),
                                            self.spec_tokens)
                elif self._ema[slot] < 0.3:
                    self._kslot[slot] = max(int(self._kslot[slot]) // 2, 1)

    def _take_chunk(self, cur, pos, out_host, n_out_host, n_it_host
                    ) -> list[tuple[EngineRequest, list[int]]]:
        """Hand a finished chunk's tokens to their requests, count the
        steps each live slot did useful work (``decode_live_steps``), and
        retire (or demote) the requests whose budget is spent."""
        self._cur = np.array(cur)          # np.array: writable host copies
        self._pos = np.array(pos)
        finished: list[tuple[EngineRequest, list[int]]] = []
        for slot in list(self._live):
            live = self._live[slot]
            left = live.req.max_new - live.emitted
            if n_out_host is None:
                ntok = self.decode_chunk
                # Live for min(chunk, budget) steps, then masked.
                self.stats["decode_live_steps"] += min(ntok, left)
            else:
                ntok = int(n_out_host[slot])
                self.stats["decode_live_steps"] += int(n_it_host[slot])
                # Count only delivered tokens: the final verify step can
                # overshoot the budget and its truncated tail must not
                # inflate mean_accepted_len.
                self.stats["spec_emitted"] += min(ntok, left)
            live.tokens.extend(out_host[slot, :ntok].tolist())
            live.emitted += ntok
            if live.emitted >= live.req.max_new:
                finished.append((live.req, live.tokens[:live.req.max_new]))
                if self.demote_on_retire:
                    # Export-before-retire: the finished stream's content
                    # pages leave for a lower tier *before* the refcounts
                    # drop, so a later eviction-on-realloc scrubs index
                    # entries whose KV already lives off-device. export()
                    # retires the slot itself.
                    self.demoted_out.append(self.export(
                        slot=slot, reason=ExportReason.DEMOTE))
                    self.stats["page_demotes"] += 1
                else:
                    self._retire(slot)
        return finished

    # -- the serving loop ----------------------------------------------------
    def generate(self, prompts: list[list[int]], max_new: int = 16,
                 on_chunk=None) -> ServeResult:
        """Greedy-decode ``max_new`` tokens for every prompt, FCFS admission.

        A convenience loop over the stepped API (enqueue / admit /
        decode_step); see :meth:`decode_step` for ``on_chunk`` semantics.
        """
        if not prompts:
            return ServeResult(np.zeros((0, max_new), np.int32), [])
        if self.has_work:
            raise RuntimeError("generate() on a busy engine: drain or abort "
                               "the stepped API first")
        reqs = [EngineRequest(rid, list(p), max_new)
                for rid, p in enumerate(prompts)]
        for r in reqs:                        # validate before reserving
            self._validate_request(r)
        self._reset_stats()
        now = time.perf_counter()
        self._queue.extend((r, now) for r in reqs)
        done: dict[object, list[int]] = {}
        self._admit_wave()
        if self._queue and not self._live:
            raise RuntimeError("admission stalled: request needs more pages "
                               "than the pool holds free")
        while self._live:
            for req, toks in self.decode_step(on_chunk=on_chunk):
                done[req.rid] = toks
            self._admit_wave()
            if self._queue and not self._live:
                raise RuntimeError("admission stalled: request needs more "
                                   "pages than the pool holds free")
        tokens = np.stack([np.asarray(done[i], np.int32)
                           for i in range(len(prompts))])
        return ServeResult(tokens, [len(p) for p in prompts])
