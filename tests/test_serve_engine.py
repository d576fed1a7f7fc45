"""Serve-engine lifecycle: paged chunked prefill vs the dense-prefill oracle,
copy-on-write prefix sharing, same-wave prefix dedup, refcount invariants,
page reuse across retire/readmit, eviction-on-realloc, exhaustion mid-wave,
up-front capacity validation, speculative decode token-identity, lossless
decode preemption (pause/resume with pinned pages and zero re-prefill), the
one-compile guarantees for the decode/verify/prefill hot paths, and the
engine's phase spans and counters."""
import glob
import time

import jax
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models import get_family
from repro.models.params import init_params
from repro.serve import (ContinuousBatchingEngine, EngineRequest,
                         PageAllocator, PrefixCache, ServeEngine)


def _make(arch="yi-6b", **kw):
    cfg = get_reduced_config(arch).replace(dtype="float32", page_size=8, **kw)
    fam = get_family(cfg)
    params = init_params(fam.layout(cfg), jax.random.PRNGKey(0),
                         cfg.param_dtype)
    return cfg, params


def _prompts(vocab, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=n).tolist() for n in lens]


@pytest.fixture(scope="module")
def model():
    return _make()


@pytest.fixture(scope="module")
def gold_engine(model):
    cfg, params = model
    return ServeEngine(cfg, params, max_len=64)


def _gold(gold_engine, prompts, max_new):
    """Per-request static-engine decode: the padding-free oracle."""
    return np.concatenate(
        [gold_engine.generate([p], max_new=max_new).tokens for p in prompts])


# ---------------------------------------------------------------------------
# Paged chunked prefill vs oracle
# ---------------------------------------------------------------------------

def test_paged_prefill_matches_oracle_mixed_lengths(model, gold_engine):
    """Chunked paged admission must emit the same tokens as the dense path,
    including prompts that straddle chunk and page boundaries."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, [3, 7, 12, 5, 17, 8], seed=1)
    gold = _gold(gold_engine, prompts, 8)
    for chunk in (4, 8, 32):
        eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=6,
                                       prefill_chunk=chunk)
        out = eng.generate(prompts, max_new=8)
        np.testing.assert_array_equal(gold, out.tokens)


def test_paged_prefill_matches_dense_mode(model):
    """The in-engine dense baseline and the paged path agree token-for-token."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, [4, 9, 14, 6], seed=2)
    dense = ContinuousBatchingEngine(cfg, params, max_len=48, max_slots=4,
                                     prefill_mode="dense")
    paged = ContinuousBatchingEngine(cfg, params, max_len=48, max_slots=4,
                                     prefill_chunk=8)
    np.testing.assert_array_equal(dense.generate(prompts, max_new=6).tokens,
                                  paged.generate(prompts, max_new=6).tokens)


# ---------------------------------------------------------------------------
# Prefix sharing + copy-on-write
# ---------------------------------------------------------------------------

def test_prefix_sharing_across_waves(model, gold_engine):
    """Requests admitted after a shared prefix is cached alias its pages and
    still decode the exact oracle tokens."""
    cfg, params = model
    rng = np.random.RandomState(3)
    shared = rng.randint(0, cfg.vocab_size, size=16).tolist()
    prompts = [shared + rng.randint(0, cfg.vocab_size, size=4).tolist()
               for _ in range(4)]
    gold = _gold(gold_engine, prompts, 6)
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   prefill_chunk=8)
    out = eng.generate(prompts, max_new=6)      # 2 slots: 2 admission waves
    np.testing.assert_array_equal(gold, out.tokens)
    assert eng.stats["cached_tokens"] > 0       # later waves hit the prefix
    eng._debug_check_refcounts()

    # Warm-cache readmission: nearly all prompt tokens served from cache.
    out2 = eng.generate(prompts, max_new=6)
    np.testing.assert_array_equal(gold, out2.tokens)
    assert eng.prefix_hit_rate > 0.5
    eng._debug_check_refcounts()


def test_copy_on_write_boundary_page(model, gold_engine):
    """A prefix match ending mid-page copies the boundary page instead of
    appending into the (still referenced) donor page."""
    cfg, params = model
    rng = np.random.RandomState(4)
    donor = rng.randint(0, cfg.vocab_size, size=12).tolist()   # partial page
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   prefill_chunk=8)
    eng.generate([donor], max_new=4)            # caches 1 full + 1 partial page
    follow = [donor + rng.randint(0, cfg.vocab_size, size=5).tolist()
              for _ in range(2)]
    gold = _gold(gold_engine, follow, 6)
    out = eng.generate(follow, max_new=6)
    np.testing.assert_array_equal(gold, out.tokens)
    assert eng.stats["cow_copies"] >= 1
    assert eng.stats["cached_tokens"] >= 2 * len(donor)
    eng._debug_check_refcounts()
    # Donor pages untouched: replaying the donor still matches its oracle.
    gold_d = _gold(gold_engine, [donor], 4)
    np.testing.assert_array_equal(gold_d, eng.generate([donor], max_new=4).tokens)


def test_budget_overshoot_cannot_corrupt_shared_prefix(model, gold_engine):
    """A spent slot decoding out its chunk must not clobber cached pages.

    prompt 61 + max_new 3 fills the page-table row exactly (max_len 64,
    page_size 8); decode_chunk 16 leaves 13 overshoot steps whose pos runs
    past max_len. Unmasked, the clamped page-table gather would redirect
    those KV writes into the request's LAST REAL page — corrupting prompt
    rows the prefix cache has already published, so a follow-up sharing the
    prefix would copy-on-write garbage."""
    cfg, params = model
    rng = np.random.RandomState(12)
    donor = rng.randint(0, cfg.vocab_size, size=61).tolist()

    def boundary_page(decode_chunk):
        eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                       prefill_chunk=8,
                                       decode_chunk=decode_chunk)
        gold_d = _gold(gold_engine, [donor], 3)
        np.testing.assert_array_equal(gold_d,
                                      eng.generate([donor], max_new=3).tokens)
        page = eng.prefix_cache.lookup(donor)[0][-1]    # partial-tail page
        return eng, np.asarray(eng.pool["k"])[:, :, page]

    # decode_chunk=1 cannot overshoot (budget 3, 3 chunks): its page bytes
    # are the uncorrupted reference for the 13-step-overshoot engine.
    _, ref_rows = boundary_page(1)
    eng, rows = boundary_page(16)
    np.testing.assert_array_equal(ref_rows, rows)

    follow = [donor + rng.randint(0, cfg.vocab_size, size=1).tolist()]
    gold_f = _gold(gold_engine, follow, 2)
    out = eng.generate(follow, max_new=2)
    assert eng.stats["cached_tokens"] >= len(donor)     # prefix was shared
    assert eng.stats["cow_copies"] >= 1                 # boundary page COW'd
    np.testing.assert_array_equal(gold_f, out.tokens)


def test_refcounts_track_rows_mid_flight(model):
    """The refcount invariant holds at every decode chunk, with sharing on."""
    cfg, params = model
    rng = np.random.RandomState(5)
    shared = rng.randint(0, cfg.vocab_size, size=8).tolist()
    prompts = [shared + rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in (2, 5, 9, 3, 7)]
    eng = ContinuousBatchingEngine(cfg, params, max_len=48, max_slots=2,
                                   prefill_chunk=8, decode_chunk=4)
    eng.generate(prompts, max_new=8,
                 on_chunk=lambda s, t: eng._debug_check_refcounts())
    eng._debug_check_refcounts()
    assert eng.alloc.available() == eng.num_pages - 1   # all pages returned


# ---------------------------------------------------------------------------
# Same-wave prefix dedup
# ---------------------------------------------------------------------------

def test_same_wave_identical_prompts_dedup(model, gold_engine):
    """Two identical prompts admitted in ONE wave: the second aliases the
    first's pages (grouped sequenced prefill) instead of prefilling
    privately, and both decode the exact oracle tokens."""
    cfg, params = model
    rng = np.random.RandomState(20)
    prompt = rng.randint(0, cfg.vocab_size, size=20).tolist()
    prompts = [prompt, prompt]
    gold = _gold(gold_engine, prompts, 6)
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   prefill_chunk=8)
    out = eng.generate(prompts, max_new=6)      # ONE admission wave
    np.testing.assert_array_equal(gold, out.tokens)
    # match is capped at plen-1 = 19: 2 full pages aliased + boundary COW'd
    assert eng.stats["cached_tokens"] >= 16
    assert eng.stats["cow_copies"] >= 1
    eng._debug_check_refcounts()
    assert eng.alloc.available() == eng.num_pages - 1


def test_same_wave_dedup_chained_groups(model, gold_engine):
    """A aliases nothing, B aliases A's pages, C aliases pages B prefills:
    three dependency groups sequenced inside one admission wave."""
    cfg, params = model
    rng = np.random.RandomState(21)
    a = rng.randint(0, cfg.vocab_size, size=16).tolist()     # 2 full pages
    b = a + rng.randint(0, cfg.vocab_size, size=8).tolist()  # +1 full page
    c = b + rng.randint(0, cfg.vocab_size, size=5).tolist()
    prompts = [a, b, c]
    gold = _gold(gold_engine, prompts, 6)
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=3,
                                   prefill_chunk=8)
    out = eng.generate(prompts, max_new=6)      # ONE admission wave
    np.testing.assert_array_equal(gold, out.tokens)
    # B hits A's 2 pages (16 tokens, match capped at 15); C hits A's 2 pages
    # plus the page B's prefill fills (24 tokens, capped at 23).
    assert eng.stats["cached_tokens"] >= 15 + 23
    eng._debug_check_refcounts()


# ---------------------------------------------------------------------------
# Eviction on reallocation
# ---------------------------------------------------------------------------

def test_allocator_realloc_evicts_cache_entries():
    """The on_alloc hook scrubs a page's radix entries the moment the page
    is handed out again."""
    al = PageAllocator(4)                       # pages 1..3
    pc = PrefixCache(2)
    al.on_alloc = pc.evict
    p1, p2 = al.alloc(), al.alloc()
    pc.register([1, 2, 3, 4], [p1, p2])
    al.release(p1)
    al.release(p2)
    assert pc.lookup([1, 2, 3, 4])[1] == 4      # retired but still hittable
    got = {al.alloc(), al.alloc()}              # reallocation scrubs entries
    assert got == {p1, p2}
    assert pc.lookup([1, 2, 3, 4]) == ([], 0)


def test_realloc_rejects_stale_prefix_hit(model, gold_engine):
    """A radix hit on a retired page that has since been REALLOCATED must be
    rejected (not aliased): the readmitted donor prefills from scratch and
    still emits oracle tokens."""
    cfg, params = model
    rng = np.random.RandomState(22)
    donor = rng.randint(0, cfg.vocab_size, size=16).tolist()   # 2 full pages
    flush = rng.randint(0, cfg.vocab_size, size=24).tolist()
    # 4-page pool: donor needs 3 (16+8 tokens), flush needs all 4.
    eng = ContinuousBatchingEngine(cfg, params, max_len=32, max_slots=1,
                                   num_pages=4, prefill_chunk=8,
                                   decode_chunk=4)
    gold_d = _gold(gold_engine, [donor], 8)
    np.testing.assert_array_equal(gold_d,
                                  eng.generate([donor], max_new=8).tokens)
    assert eng.prefix_cache.lookup(donor)[1] == 16   # retired, still cached
    eng.generate([flush], max_new=8)            # reallocates every pool page
    assert eng.prefix_cache.lookup(donor) == ([], 0)
    out = eng.generate([donor], max_new=8)      # no stale alias: full prefill
    assert eng.stats["cached_tokens"] == 0
    np.testing.assert_array_equal(gold_d, out.tokens)
    eng._debug_check_refcounts()


def test_cow_boundary_refcounts_consistent(model, gold_engine):
    """Three followers COW the same boundary page in one wave: refcounts hold
    at every decode chunk, the pins drain, and tokens match the oracle."""
    cfg, params = model
    rng = np.random.RandomState(23)
    donor = rng.randint(0, cfg.vocab_size, size=12).tolist()   # partial page
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=3,
                                   prefill_chunk=8, decode_chunk=4)
    eng.generate([donor], max_new=4)            # caches 1 full + 1 partial
    followers = [donor + rng.randint(0, cfg.vocab_size, size=3).tolist()
                 for _ in range(3)]
    gold = _gold(gold_engine, followers, 6)
    out = eng.generate(followers, max_new=6,
                       on_chunk=lambda s, t: eng._debug_check_refcounts())
    np.testing.assert_array_equal(gold, out.tokens)
    assert eng.stats["cow_copies"] == 3         # each COWs its private copy
    eng._debug_check_refcounts()
    assert eng.alloc.available() == eng.num_pages - 1


# ---------------------------------------------------------------------------
# Speculative multi-token decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec_tokens", [1, 4])
def test_spec_decode_token_identical(model, gold_engine, spec_tokens):
    """Greedy speculative decode emits EXACTLY the non-speculative tokens,
    across ragged prompts, queued admission and page-boundary crossings."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, [3, 7, 12, 5, 17], seed=24)
    gold = _gold(gold_engine, prompts, 12)
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   prefill_chunk=8, decode_chunk=4,
                                   enable_spec_decode=True,
                                   spec_tokens=spec_tokens)
    out = eng.generate(prompts, max_new=12)     # 2 slots: queued waves
    np.testing.assert_array_equal(gold, out.tokens)
    assert eng.stats["spec_steps"] > 0
    assert eng.stats["spec_emitted"] >= eng.stats["spec_steps"]
    assert 0.0 <= eng.mean_accepted_len <= spec_tokens
    eng._debug_check_refcounts()


def test_spec_decode_with_prefix_sharing(model, gold_engine):
    """Spec decode composes with COW prefix sharing: a follower aliasing the
    donor's pages (incl. the partial page spec decode wrote into) still
    decodes oracle tokens — rejected draft tails never corrupt shared
    pages."""
    cfg, params = model
    rng = np.random.RandomState(25)
    donor = rng.randint(0, cfg.vocab_size, size=12).tolist()
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   prefill_chunk=8, decode_chunk=4,
                                   enable_spec_decode=True, spec_tokens=4)
    gold_d = _gold(gold_engine, [donor], 6)
    np.testing.assert_array_equal(gold_d,
                                  eng.generate([donor], max_new=6).tokens)
    follow = [donor + rng.randint(0, cfg.vocab_size, size=2).tolist()
              for _ in range(2)]
    gold_f = _gold(gold_engine, follow, 8)
    out = eng.generate(follow, max_new=8)
    np.testing.assert_array_equal(gold_f, out.tokens)
    assert eng.stats["cached_tokens"] >= 2 * 8   # full prefix pages aliased
    eng._debug_check_refcounts()


def test_spec_decode_budget_overshoot_masked(model, gold_engine):
    """Draft windows running past a slot's token budget route their KV to
    the sink page: the boundary page a later request will COW keeps exactly
    the bytes a no-overshoot engine produces.

    prompt 61 + max_new 3 fills the page-table row exactly (max_len 64):
    every verify window past pos 63 would otherwise spill through the
    clamped page-table gather into the request's last real page."""
    cfg, params = model
    rng = np.random.RandomState(26)
    donor = rng.randint(0, cfg.vocab_size, size=61).tolist()
    gold_d = _gold(gold_engine, [donor], 3)

    ref_eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                       prefill_chunk=8, decode_chunk=1)
    np.testing.assert_array_equal(
        gold_d, ref_eng.generate([donor], max_new=3).tokens)
    page_ref = ref_eng.prefix_cache.lookup(donor)[0][-1]
    ref_rows = np.asarray(ref_eng.pool["k"])[:, :, page_ref]

    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   prefill_chunk=8, decode_chunk=8,
                                   enable_spec_decode=True, spec_tokens=4)
    np.testing.assert_array_equal(gold_d,
                                  eng.generate([donor], max_new=3).tokens)
    page = eng.prefix_cache.lookup(donor)[0][-1]
    rows = np.asarray(eng.pool["k"])[:, :, page]
    # allclose, not array_equal: the verify step batches T positions through
    # one projection GEMM, which may round differently from the 1-token step.
    np.testing.assert_allclose(ref_rows, rows, rtol=1e-5, atol=1e-6)

    follow = [donor + rng.randint(0, cfg.vocab_size, size=1).tolist()]
    gold_f = _gold(gold_engine, follow, 2)
    out = eng.generate(follow, max_new=2)
    assert eng.stats["cached_tokens"] >= len(donor)     # prefix was shared
    assert eng.stats["cow_copies"] >= 1                 # boundary page COW'd
    np.testing.assert_array_equal(gold_f, out.tokens)


def test_spec_decode_chunk_compiles_once(model):
    """Data-dependent accept lengths never retrace the spec decode chunk:
    the fori_loop trip count stays static."""
    cfg, params = model
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   decode_chunk=8, prefill_chunk=8,
                                   enable_spec_decode=True, spec_tokens=4)
    prompts = _prompts(cfg.vocab_size, [5, 9], seed=27)
    for max_new in (8, 11, 3, 13):              # ragged budgets + tails
        eng.generate(prompts, max_new=max_new)
    assert eng._n_decode_traces == 1


def test_decode_chunk_occupancy_heuristic(model):
    """decode_chunk=None picks chunk = clamp(tokens_target/slots): long
    chunks for narrow batches, short chunks at high occupancy."""
    cfg, params = model
    narrow = ContinuousBatchingEngine(cfg, params, max_len=32, max_slots=1)
    wide = ContinuousBatchingEngine(cfg, params, max_len=32, max_slots=32)
    assert narrow.decode_chunk == cfg.decode_chunk_max
    assert wide.decode_chunk == max(cfg.decode_chunk_min,
                                    cfg.decode_chunk_tokens // 32)
    assert wide.decode_chunk < narrow.decode_chunk


# ---------------------------------------------------------------------------
# Lifecycle: reuse, exhaustion, validation
# ---------------------------------------------------------------------------

def test_retire_then_readmit_reuses_pages(model, gold_engine):
    """Back-to-back generates recycle the same physical pool correctly."""
    cfg, params = model
    a = _prompts(cfg.vocab_size, [6, 11, 4], seed=6)
    b = _prompts(cfg.vocab_size, [9, 5, 13], seed=7)
    eng = ContinuousBatchingEngine(cfg, params, max_len=48, max_slots=3,
                                   prefill_chunk=8)
    for prompts in (a, b, a):                   # a's pages were reused by b
        gold = _gold(gold_engine, prompts, 6)
        np.testing.assert_array_equal(gold,
                                      eng.generate(prompts, max_new=6).tokens)
        eng._debug_check_refcounts()
        assert eng.alloc.available() == eng.num_pages - 1


def test_admission_when_pages_exhaust_mid_wave(model, gold_engine):
    """A pool that only fits one request at a time forces per-wave admission
    yet completes every request with oracle tokens."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, [9, 12, 10], seed=8)
    gold = _gold(gold_engine, prompts, 6)
    # 3 pages: one request (ceil((12+6)/8)=3) exhausts the pool by itself.
    eng = ContinuousBatchingEngine(cfg, params, max_len=24, max_slots=3,
                                   num_pages=3, prefill_chunk=8,
                                   decode_chunk=2, enable_prefix_cache=False)
    out = eng.generate(prompts, max_new=6)
    np.testing.assert_array_equal(gold, out.tokens)
    assert eng.alloc.available() == eng.num_pages - 1


def test_pool_capacity_validated_up_front(model):
    """A request that can never fit fails fast, before reserving anything."""
    cfg, params = model
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   num_pages=2)
    with pytest.raises(ValueError, match="pool only holds"):
        eng.generate([[1, 2, 3], list(range(20))], max_new=8)
    assert not eng._active.any()
    assert eng.alloc.available() == eng.num_pages - 1
    out = eng.generate(_prompts(cfg.vocab_size, [4], seed=9), max_new=4)
    assert out.tokens.shape == (1, 4)


# ---------------------------------------------------------------------------
# Compile-count guarantees
# ---------------------------------------------------------------------------

def test_decode_chunk_compiles_once(model):
    """Ragged tail lengths (max_new % decode_chunk) never retrace decode."""
    cfg, params = model
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   decode_chunk=8, prefill_chunk=8)
    prompts = _prompts(cfg.vocab_size, [5, 9], seed=10)
    for max_new in (8, 11, 3, 13):              # tails 8, 3, 3, 5
        eng.generate(prompts, max_new=max_new)
    assert eng._n_decode_traces == 1


def test_prefill_chunk_compiles_per_bucket_not_per_length(model):
    """Prompt lengths share one jit signature per pow2 wave bucket."""
    cfg, params = model
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=4,
                                   prefill_chunk=8, enable_prefix_cache=False)
    for lens in ([3], [7], [12], [17], [23]):   # 5 lengths, bucket g=1
        eng.generate(_prompts(cfg.vocab_size, lens, seed=11), max_new=2)
    assert eng._n_prefill_traces == 1
    eng.generate(_prompts(cfg.vocab_size, [4, 9, 14], seed=12), max_new=2)
    assert eng._n_prefill_traces == 2           # one more for bucket g=4


# ---------------------------------------------------------------------------
# Namespaced prefix cache (tenant scoping) through the stepped API
# ---------------------------------------------------------------------------

def test_namespaced_requests_never_alias_across_namespaces(model):
    """Identical prompts under different namespaces admitted in ONE wave
    keep fully disjoint pages (no same-wave dedup across the boundary);
    the same namespace still dedups."""
    cfg, params = model
    rng = np.random.RandomState(30)
    prompt = rng.randint(0, cfg.vocab_size, size=16).tolist()
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=3,
                                   prefill_chunk=8, decode_chunk=2)
    for rid, ns in enumerate((("a", None), ("b", None), ("a", None))):
        eng.enqueue(EngineRequest(rid, list(prompt), 4, namespace=ns))
    eng.admit()
    assert eng.live == 3
    pages = {l.req.rid: set(l.pages) for l in eng._live.values()}
    assert not pages[0] & pages[1]          # cross-namespace: disjoint
    assert pages[0] & pages[2]              # same namespace: aliased
    # Only the same-namespace duplicate hit the cache.
    assert 0 < eng.stats["cached_tokens"] <= len(prompt)
    eng._debug_check_refcounts()
    while eng.has_work:
        eng.decode_step()
        eng.admit()
    eng._debug_check_refcounts()
    assert eng.alloc.available() == eng.num_pages - 1


def test_stepped_api_heterogeneous_budgets(model, gold_engine):
    """enqueue/admit/decode_step with per-request max_new matches the
    oracle for each request's own budget."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, [5, 11, 8], seed=31)
    budgets = [3, 7, 5]
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   prefill_chunk=8, decode_chunk=2)
    for rid, (p, m) in enumerate(zip(prompts, budgets)):
        eng.enqueue(EngineRequest(rid, p, m))
    done = {}
    eng.admit()
    while eng.has_work:
        for req, toks in eng.decode_step():
            done[req.rid] = toks
        eng.admit()
    for rid, (p, m) in enumerate(zip(prompts, budgets)):
        gold = gold_engine.generate([p], max_new=m).tokens[0]
        np.testing.assert_array_equal(gold, np.asarray(done[rid]))


def test_abort_returns_requests_and_releases_pages(model, gold_engine):
    """abort() mid-decode hands every live+queued request back and leaves
    the pool clean; re-running them from scratch matches the oracle."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, [6, 9, 12], seed=32)
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   prefill_chunk=8, decode_chunk=2)
    for rid, p in enumerate(prompts):
        eng.enqueue(EngineRequest(rid, p, 8))
    eng.admit()
    eng.decode_step()                       # mid-flight (2 live, 1 queued)
    dropped = eng.abort()
    assert sorted(r.rid for r in dropped) == [0, 1, 2]
    assert not eng.has_work
    assert eng.alloc.available() == eng.num_pages - 1
    eng._debug_check_refcounts()
    gold = _gold(gold_engine, prompts, 8)
    np.testing.assert_array_equal(gold,
                                  eng.generate(prompts, max_new=8).tokens)


# ---------------------------------------------------------------------------
# Trigram draft keys + construction-time validation
# ---------------------------------------------------------------------------

def test_trigram_spec_decode_token_identical(model, gold_engine):
    """spec_ngram=3 (with bigram fallback) emits exactly the greedy
    tokens, via the constructor arg and via the config field."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, [3, 7, 12, 5], seed=33)
    gold = _gold(gold_engine, prompts, 10)
    by_arg = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                      prefill_chunk=8, decode_chunk=4,
                                      enable_spec_decode=True, spec_tokens=4,
                                      spec_ngram=3)
    np.testing.assert_array_equal(gold,
                                  by_arg.generate(prompts, max_new=10).tokens)
    assert by_arg.stats["spec_steps"] > 0
    cfg3 = cfg.replace(spec_ngram=3)
    params3 = params                        # same layout
    by_cfg = ContinuousBatchingEngine(cfg3, params3, max_len=64, max_slots=2,
                                      prefill_chunk=8, decode_chunk=4,
                                      enable_spec_decode=True, spec_tokens=4)
    assert by_cfg.spec_ngram == 3
    np.testing.assert_array_equal(gold,
                                  by_cfg.generate(prompts, max_new=10).tokens)


def test_engine_config_bounds_validated_at_construction(model):
    """Bad spec/slot configs fail at construction with named knobs, not as
    shape errors deep in the verify step / Pallas kernel."""
    cfg, params = model
    mk = lambda c=cfg, **kw: ContinuousBatchingEngine(c, params, max_len=64,
                                                      **kw)
    with pytest.raises(ValueError, match="spec_tokens >= 1"):
        mk(enable_spec_decode=True, spec_tokens=0)
    with pytest.raises(ValueError, match="spec_ngram"):
        mk(enable_spec_decode=True, spec_ngram=4)
    with pytest.raises(ValueError, match="max_slots"):
        mk(max_slots=0)
    with pytest.raises(ValueError, match="page-table window"):
        mk(enable_spec_decode=True, spec_tokens=64)
    # (K+1)*G = 5*2 = 10 rows: not an 8-sublane multiple for the TPU tile.
    with pytest.raises(ValueError, match="multiple of 8"):
        mk(cfg.replace(attn_impl="pallas"), enable_spec_decode=True,
           spec_tokens=4)
    # K=3 -> (K+1)*G = 8: tile fits, construction succeeds.
    mk(cfg.replace(attn_impl="pallas"), enable_spec_decode=True,
       spec_tokens=3)


# ---------------------------------------------------------------------------
# Decode preemption: lossless pause/resume with pinned pages
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", [False, True])
def test_preempt_resume_token_identity_zero_reprefill(model, gold_engine,
                                                      spec):
    """A paused-then-resumed request emits EXACTLY the tokens of a
    never-paused run — with and without speculative decode — and resume
    re-prefills NOTHING (prefill_tokens is asserted flat across it)."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, [5, 9, 13], seed=40)
    gold = _gold(gold_engine, prompts, 10)
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   prefill_chunk=8, decode_chunk=2,
                                   enable_spec_decode=spec, spec_tokens=4)
    for rid, p in enumerate(prompts[:2]):
        eng.enqueue(EngineRequest(rid, list(p), 10))
    eng.admit()
    done = {}
    for req, toks in eng.decode_step():
        done[req.rid] = toks
    slot0 = next(s for s, l in eng._live.items() if l.req.rid == 0)
    paused = eng.preempt(slot0)
    assert 0 < paused.emitted < 10          # genuinely mid-stream
    assert eng.free_slots == 1 and eng.paused == 1
    eng._debug_check_refcounts()            # pinned pages are counted

    # The freed slot admits a new request while rid 0 stays parked.
    eng.enqueue(EngineRequest(2, list(prompts[2]), 10))
    eng.admit()
    assert eng.live == 2
    pf_mark = eng.stats["prefill_tokens"]
    resumed = False
    for _ in range(200):
        for req, toks in eng.decode_step():
            done[req.rid] = toks
        if not resumed and eng.free_slots > 0:
            eng.resume(paused)
            resumed = True
            # Zero re-prefill: resume re-attached pages via the page table.
            assert eng.stats["prefill_tokens"] == pf_mark
            assert eng.stats["resumed"] == 1
        eng._debug_check_refcounts()
        if len(done) == 3 and not eng.has_work:
            break
    assert resumed and len(done) == 3
    got = np.stack([np.asarray(done[i], np.int32) for i in range(3)])
    np.testing.assert_array_equal(gold, got)
    assert eng.alloc.available() == eng.num_pages - 1


def test_preempted_pages_pinned_under_eviction_pressure(model, gold_engine):
    """However hard admissions churn the pool while a request is paused,
    its pinned pages are never reallocated (refcounts >= 1 throughout) and
    its cached prefix entries survive while OTHER retired pages are
    evicted; the resumed request still emits oracle tokens."""
    cfg, params = model
    rng = np.random.RandomState(41)
    donor = rng.randint(0, cfg.vocab_size, size=10).tolist()
    gold_d = _gold(gold_engine, [donor], 6)
    # 8 usable pages: donor (10+6 tok) pins 2; each flusher (20/21+4 tok)
    # takes 3-4, so two flusher rounds must recycle every free page.
    eng = ContinuousBatchingEngine(cfg, params, max_len=32, max_slots=2,
                                   num_pages=8, prefill_chunk=8,
                                   decode_chunk=2)
    eng.enqueue(EngineRequest("donor", list(donor), 6))
    eng.admit()
    eng.decode_step()
    paused = eng.preempt(next(iter(eng._live)))
    pinned = list(paused.pages)
    assert all(eng.alloc.refs[p] == 1 for p in pinned)

    first_flush = None
    for i in range(3):                      # churn: realloc every free page
        flush = rng.randint(0, cfg.vocab_size, size=20 + i % 2).tolist()
        if first_flush is None:
            first_flush = flush
        eng.enqueue(EngineRequest(f"flush{i}", flush, 4))
        eng.admit()
        while eng.live:
            eng.decode_step()
            eng._debug_check_refcounts()
        assert all(eng.alloc.refs[p] >= 1 for p in pinned)  # still pinned
    # Eviction pressure was real: the first flusher's retired pages were
    # reallocated and its cache entries scrubbed ...
    assert eng.prefix_cache.lookup(first_flush)[1] == 0
    # ... while the paused donor's pinned pages stayed hittable.
    assert eng.prefix_cache.lookup(donor)[0] == pinned[:len(
        eng.prefix_cache.lookup(donor)[0])]

    eng.resume(paused)
    done = {}
    while eng.has_work:
        for req, toks in eng.decode_step():
            done[req.rid] = toks
        eng._debug_check_refcounts()
    np.testing.assert_array_equal(gold_d[0], np.asarray(done["donor"]))
    assert eng.alloc.available() == eng.num_pages - 1


def test_preempt_resume_errors_and_abort_releases_pins(model):
    """Bad preempt/resume calls fail typed; abort surrenders paused
    requests and releases their pinned pages."""
    cfg, params = model
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   prefill_chunk=8, decode_chunk=2)
    with pytest.raises(KeyError, match="no live request"):
        eng.preempt(0)
    prompts = _prompts(cfg.vocab_size, [6, 9], seed=42)
    for rid, p in enumerate(prompts):
        eng.enqueue(EngineRequest(rid, p, 8))
    eng.admit()
    eng.decode_step()
    paused = eng.preempt(0)
    eng.resume(paused)
    with pytest.raises(KeyError, match="not paused"):
        eng.resume(paused)                  # double-resume guard
    # Re-preempt and fill every slot: resume must refuse, not clobber.
    paused = eng.preempt(next(iter(eng._live)))
    eng.enqueue(EngineRequest(2, prompts[0], 8))
    eng.enqueue(EngineRequest(3, prompts[1], 8))
    eng.admit()
    assert eng.free_slots == 0
    with pytest.raises(RuntimeError, match="no free slot"):
        eng.resume(paused)
    dropped = eng.abort()                   # paused req included, pins freed
    assert any(r.rid == paused.req.rid for r in dropped)
    assert not eng.has_work and eng.paused == 0
    assert eng.alloc.available() == eng.num_pages - 1
    eng._debug_check_refcounts()


# ---------------------------------------------------------------------------
# PageAllocator / PrefixCache units
# ---------------------------------------------------------------------------

def test_page_allocator_share_revives_free_page():
    al = PageAllocator(5)                       # pages 1..4
    p = al.alloc()
    al.release(p)
    assert al.available() == 4
    al.share(p)                                 # cache hit on a retired page
    assert al.available() == 3
    got = {al.alloc() for _ in range(3)}        # stale free-list entry skipped
    assert p not in got
    with pytest.raises(RuntimeError, match="exhausted"):
        al.alloc()
    al.release(p)
    assert al.alloc() == p


def test_prefix_cache_namespaces_isolated():
    """Entries registered under one namespace are invisible to lookups from
    another; eviction under one namespace leaves the other intact."""
    pc = PrefixCache(4)
    prompt = list(range(8))
    pc.register(prompt, [3, 4], namespace="tenant-a")
    pc.register(prompt, [5, 6], namespace="tenant-b")
    assert pc.lookup(prompt, namespace="tenant-a") == ([3, 4], 8)
    assert pc.lookup(prompt, namespace="tenant-b") == ([5, 6], 8)
    assert pc.lookup(prompt) == ([], 0)         # default namespace: no hit
    pc.evict(3)                                 # scrubs only tenant-a's chain
    assert pc.lookup(prompt, namespace="tenant-a") == ([], 0)
    assert pc.lookup(prompt, namespace="tenant-b") == ([5, 6], 8)
    # Namespace roots are never scrubbed, so eviction must unlink the key
    # from the root's child list too (else it leaks one entry per evict).
    assert pc._root("tenant-a") not in pc._kids


def test_prefix_cache_lookup_register_evict():
    pc = PrefixCache(4)
    prompt = list(range(10))                    # 2 full pages + 2-token tail
    pc.register(prompt, [7, 8, 9])
    chain, match = pc.lookup(prompt + [99])
    assert (chain, match) == ([7, 8, 9], 10)
    chain, match = pc.lookup(prompt[:6])        # only page 7 fully matches
    assert (chain, match) == ([7], 4)
    # Diverging second page: only the first page hits.
    other = prompt[:4] + [55, 56, 57, 58]
    assert pc.lookup(other) == ([7], 4)
    # Evicting the root page must take the whole chain (and partial) with it:
    # entries keyed under page 7 would re-anchor to its future contents.
    pc.evict(7)
    assert pc.lookup(prompt) == ([], 0)
    assert len(pc) == 0


def test_prefix_cache_existing_entries_win():
    pc = PrefixCache(4)
    pc.register(list(range(8)), [3, 4])
    pc.register(list(range(8)), [5, 6])         # same-wave private duplicate
    chain, _ = pc.lookup(list(range(8)))
    assert chain == [3, 4]
    pc.evict(5)                                 # duplicate pages never indexed
    assert pc.lookup(list(range(8)))[0] == [3, 4]


def test_prefix_cache_lookup_at_exact_page_boundaries():
    """Longest-prefix lookup lands exactly on page edges: a prompt that is
    a whole number of pages matches fully with no partial entry, a query
    one token past the boundary gains nothing, and one token short drops a
    whole page (full pages only — no sub-page credit without a partial)."""
    pc = PrefixCache(4)
    prompt = list(range(12))                    # exactly 3 pages
    pc.register(prompt, [5, 6, 7])
    assert pc.lookup(prompt) == ([5, 6, 7], 12)
    assert len(pc) == 3                         # no partial entry created
    # One past the boundary: the extra token is uncached, match stays 12.
    assert pc.lookup(prompt + [99]) == ([5, 6, 7], 12)
    # One short of the boundary: page 3 can't fully match, and with no
    # partial registered the 3 matching tokens earn nothing.
    assert pc.lookup(prompt[:11]) == ([5, 6], 8)
    assert pc.lookup(prompt[:8]) == ([5, 6], 8)
    assert pc.lookup(prompt[:4]) == ([5], 4)
    assert pc.lookup(prompt[:3]) == ([], 0)
    # A partial tail registers only when its page exists: now an 11-token
    # register adds a partial under page 6, and the boundary query walks
    # full pages first, then the partial (copy-on-write source).
    pc.register(prompt[:11], [5, 6, 8])
    chain, match = pc.lookup(prompt[:11])
    assert (chain, match) == ([5, 6, 8], 11)
    assert pc.lookup(prompt) == ([5, 6, 7], 12)  # full chain still preferred


def test_prefix_cache_eviction_on_realloc_under_namespace_churn():
    """The allocator's on_alloc hook scrubs cache entries the moment their
    page is handed out again — churning registrations across namespaces
    never lets a stale entry alias a reused page's new contents."""
    al = PageAllocator(5)                       # pages 1..4
    pc = PrefixCache(4)
    al.on_alloc = pc.evict
    prompt = list(range(8))
    pa = [al.alloc(), al.alloc()]
    pc.register(prompt, pa, namespace="tenant-a")
    pb = [al.alloc(), al.alloc()]
    pc.register(prompt, pb, namespace="tenant-b")
    for p in reversed(pa):                      # tenant-a's request retires
        al.release(p)
    # Still hittable while free (revivable), until someone takes the pages.
    assert pc.lookup(prompt, namespace="tenant-a") == (pa, 8)
    pc2 = al.alloc()                            # tenant-a's root reused...
    assert pc2 == pa[0]                         # (LIFO free list)
    assert pc.lookup(prompt, namespace="tenant-a") == ([], 0)   # whole chain
    assert pc.lookup(prompt, namespace="tenant-b") == (pb, 8)   # b untouched
    # Churn: register the reused pages under a THIRD namespace (the evicted
    # subtree left them clean), release and realloc again — only the latest
    # owner's entry ever resolves.
    pc.register(prompt, [pc2, pa[1]], namespace="tenant-c")
    assert pc.lookup(prompt, namespace="tenant-c") == ([pc2, pa[1]], 8)
    al.release(pc2)
    assert al.alloc() == pc2
    assert pc.lookup(prompt, namespace="tenant-c") == ([], 0)
    assert pc.lookup(prompt, namespace="tenant-b") == (pb, 8)


# ---------------------------------------------------------------------------
# Phase spans and counters
# ---------------------------------------------------------------------------

ADMIT_PHASES = ("admit_plan_seconds", "admit_dispatch_seconds",
                "admit_sync_seconds")
DECODE_PHASES = ("decode_prep_seconds", "decode_dispatch_seconds",
                 "decode_sync_seconds", "decode_post_seconds")


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_phase_counters_by_hand_count(model, spec):
    """Two slots, chunks of 4 steps, prefill chunks of 8. Wave 1 admits A
    (prompt 5, 3 tokens) and B (prompt 11, 6 tokens); C (prompt 7, 2
    tokens) waits behind the full engine through chunk 1, where A's budget
    ends after 3 steps. Wave 2 admits C; B and C both end inside chunk 2
    (2 live steps each)."""
    cfg, params = model
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   prefill_chunk=8, decode_chunk=4,
                                   enable_spec_decode=spec, spec_tokens=3)
    pa, pb, pc = _prompts(cfg.vocab_size, [5, 11, 7], seed=41)
    t_enq = time.perf_counter()
    for rid, (p, m) in enumerate([(pa, 3), (pb, 6), (pc, 2)]):
        eng.enqueue(EngineRequest(rid, p, m))
    assert eng.admit() == 2
    t_wave1 = time.perf_counter()
    st = eng.stats
    wait1, admit1 = st["queue_wait_seconds"], st["admit_seconds"]
    # A and B were admitted by the wave that followed their enqueue.
    assert 0 < wait1 <= 2 * (t_wave1 - t_enq)
    eng.decode_step()
    decode1 = st["decode_seconds"]
    assert eng.admit() == 1
    # C waited through wave 1 and chunk 1, and nothing else waited.
    assert st["queue_wait_seconds"] - wait1 >= admit1 + decode1
    while eng.has_work:
        eng.decode_step()
        eng.admit()

    assert st["admitted"] == 3 and st["admit_waves"] == 2
    assert st["decode_chunks"] == 2
    assert st["decode_slot_steps"] == 2 * 2 * 4
    # Wave 1: a bucket of 2 rows, B's 11 tokens take 2 chunk steps; wave 2:
    # a bucket of 1 row, 1 step.
    assert st["prefill_steps"] == 3
    assert st["prefill_slot_tokens"] == 2 * 2 * 8 + 1 * 1 * 8
    assert st["prefill_tokens"] == 5 + 11 + 7 and st["cached_tokens"] == 0
    if spec:
        # A verify step is live until the slot's budget is emitted, so the
        # live steps are the verify steps, at most the plain count.
        assert st["decode_live_steps"] == st["spec_steps"]
        assert 3 <= st["decode_live_steps"] <= 11
    else:
        assert st["decode_live_steps"] == (3 + 4) + (2 + 2)
    assert sum(st[k] for k in ADMIT_PHASES) <= st["admit_seconds"]
    assert sum(st[k] for k in DECODE_PHASES) <= st["decode_seconds"]
    assert sum(st[k] for k in DECODE_PHASES) >= 0.9 * st["decode_seconds"]


def _events(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns))
    return out


def test_phase_spans_reach_the_profiler(model, tmp_path):
    """One admission wave and one decode chunk under a CPU profiler trace:
    every phase span is in the trace, each inside its parent."""
    cfg, params = model
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   prefill_chunk=8, decode_chunk=4)
    for rid, n in enumerate([5, 11]):
        eng.enqueue(EngineRequest(
            rid, _prompts(cfg.vocab_size, [n], seed=42 + rid)[0], 8))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.admit()
        eng.decode_step()
    finally:
        jax.profiler.stop_trace()
    ev = _events(tmp_path)
    children = {"engine.admit": ("plan", "prefill", "sync"),
                "engine.decode": ("prep", "dispatch", "sync", "post")}
    assert set(ev) == set(children) | {f"{p}.{c}" for p, cs in
                                       children.items() for c in cs}
    for parent, cs in children.items():
        (p0, p1), = ev[parent]
        for c in cs:
            (c0, c1), = ev[f"{parent}.{c}"]
            assert p0 <= c0 <= c1 <= p1, (parent, c)
