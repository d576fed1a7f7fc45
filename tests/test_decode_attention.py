"""Paged flash-decode kernel vs oracle (interpret mode) + engine equivalence:
continuous batching must reproduce the static-batch engine token-for-token."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.kernels import flash_decode, paged_decode_reference, quantize_pool
from repro.models import get_family
from repro.models.params import init_params
from repro.serve import ContinuousBatchingEngine, ServeEngine

# CI runs the kernels lane under both KV-pool layouts (see ci.yml): the
# engine-level fixtures below build their pool from this, so the int8 lane
# exercises quantize-on-scatter + in-kernel dequant through the whole engine.
KV_DTYPE = os.environ.get("REPRO_KV_CACHE_DTYPE", "f32")


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-3, atol=1e-3)


def _paged_case(key, b, h, kv, hd, ps, npages, num_pool_pages, dtype):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, h, hd), dtype)
    kp = jax.random.normal(ks[1], (kv, num_pool_pages, ps, hd), dtype)
    vp = jax.random.normal(ks[2], (kv, num_pool_pages, ps, hd), dtype)
    # each request gets distinct physical pages, shuffled (paging is real)
    perm = jax.random.permutation(ks[3], num_pool_pages)[:b * npages]
    pt = perm.reshape(b, npages).astype(jnp.int32)
    lengths = jax.random.randint(ks[4], (b,), 1, npages * ps + 1)
    return q, kp, vp, pt, lengths.astype(jnp.int32)


@pytest.mark.parametrize("b,h,kv,hd", [
    (2, 4, 4, 32),     # MHA
    (3, 8, 2, 32),     # GQA group=4
    (2, 4, 1, 64),     # MQA
    (1, 6, 3, 16),     # odd head group
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(b, h, kv, hd, dtype):
    ps, npages = 8, 4
    q, kp, vp, pt, lengths = _paged_case(
        jax.random.PRNGKey(0), b, h, kv, hd, ps, npages, 32, dtype)
    out = flash_decode(q, kp, vp, pt, lengths, pages_per_block=2,
                       interpret=True)
    ref = paged_decode_reference(q, kp, vp, pt, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("pages_per_block", [1, 2, 4])
def test_flash_decode_pages_per_block(pages_per_block):
    """The online softmax across blocks is exact for a block of one page,
    of several, and of the whole table."""
    q, kp, vp, pt, lengths = _paged_case(
        jax.random.PRNGKey(1), 2, 8, 2, 32, 8, 4, 16, jnp.float32)
    out = flash_decode(q, kp, vp, pt, lengths,
                       pages_per_block=pages_per_block, interpret=True)
    ref = paged_decode_reference(q, kp, vp, pt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_flash_decode_ragged_lengths():
    """Per-request masking: very short next to pool-filling sequences."""
    b, h, kv, hd, ps, npages = 4, 4, 2, 16, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, h, hd))
    kp = jax.random.normal(ks[1], (kv, b * npages, ps, hd))
    vp = jax.random.normal(ks[2], (kv, b * npages, ps, hd))
    pt = jnp.arange(b * npages, dtype=jnp.int32).reshape(b, npages)
    lengths = jnp.array([1, 5, 17, npages * ps], jnp.int32)
    out = flash_decode(q, kp, vp, pt, lengths, pages_per_block=2,
                       interpret=True)
    ref = paged_decode_reference(q, kp, vp, pt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("b,h,kv,hd", [
    (2, 4, 4, 32),     # MHA
    (3, 8, 2, 32),     # GQA group=4
    (2, 4, 1, 64),     # MQA
])
def test_flash_decode_int8_parity(b, h, kv, hd):
    """Tiered int8 parity. Tier 1 (tight): the kernel's in-tile dequant is
    the same arithmetic as the int8 oracle, so they agree at f32-path
    tolerance. Tier 2 (loose): both sit inside the quantization error band
    of exact f32 attention — per-row symmetric int8 bounds each element's
    pre-softmax error by amax(row)/254."""
    ps, npages = 8, 4
    q, kp, vp, pt, lengths = _paged_case(
        jax.random.PRNGKey(5), b, h, kv, hd, ps, npages, 32, jnp.float32)
    qp = quantize_pool({"k": kp, "v": vp})
    scales = dict(k_scale=qp["k_scale"], v_scale=qp["v_scale"])
    out = flash_decode(q, qp["k"], qp["v"], pt, lengths, pages_per_block=2,
                       interpret=True, **scales)
    ref = paged_decode_reference(q, qp["k"], qp["v"], pt, lengths, **scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)
    exact = paged_decode_reference(q, kp, vp, pt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exact),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("kv,g", [(4, 8), (8, 2)])     # yi-6b's, internlm2's
@pytest.mark.parametrize("pool", ["f32", "bf16", "int8"])
def test_flash_decode_block_boundaries(kv, g, pool):
    """Lengths at every edge of a 4-page block of 8-row pages: one row,
    mid-page, mid-block, exactly on a block boundary, the whole table,
    and an idle slot (an all-zero page-table row, length 1)."""
    ps, npages, hd = 8, 8, 64
    dtype = jnp.bfloat16 if pool == "bf16" else jnp.float32
    lengths = jnp.array([1, 12, 32, 43, npages * ps, 1], jnp.int32)
    b = lengths.shape[0]
    q, kp, vp, pt, _ = _paged_case(jax.random.PRNGKey(6), b, kv * g, kv,
                                   hd, ps, npages, b * npages + 1, dtype)
    pt = pt.at[-1].set(0)
    scales = {}
    if pool == "int8":
        qp = quantize_pool({"k": kp, "v": vp})
        kp, vp = qp["k"], qp["v"]
        scales = dict(k_scale=qp["k_scale"], v_scale=qp["v_scale"])
    out = flash_decode(q, kp, vp, pt, lengths, pages_per_block=4,
                       interpret=True, **scales)
    ref = paged_decode_reference(q, kp, vp, pt, lengths, **scales)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("lengths", [
    (65, 40),          # a slot one row past its table, then a live one
    (40, 23, 65),      # the slot past its table is the last
    (17, 0, 0, 40),    # zero-length slots between live ones
    (0, 65, 0, 9),
])
@pytest.mark.parametrize("pool", ["f32", "int8"])
def test_flash_decode_lengths_off_the_table(lengths, pool):
    """The engine passes ``length = npages * ps + 1`` for a slot whose budget
    ran out mid-chunk, and length 0 for no slot it builds but nothing
    forbids. Neither may fetch another slot's pages or leave a later
    slot's block unfetched: the next slot's several pages must come out
    right. A zero-length slot writes zeros. The TPU interpreter then runs
    the kernel's DMAs and semaphores as the chip would, and raises on a
    read past the page table."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.decode_attention.kernel import flash_decode_fwd
    ps, npages, hd, kv, g = 8, 8, 32, 2, 4
    lengths = jnp.array(lengths, jnp.int32)
    b = lengths.shape[0]
    q, kp, vp, pt, _ = _paged_case(jax.random.PRNGKey(7), b, kv * g, kv,
                                   hd, ps, npages, b * npages, jnp.float32)
    scales = {}
    if pool == "int8":
        qp = quantize_pool({"k": kp, "v": vp})
        kp, vp = qp["k"], qp["v"]
        scales = dict(k_scale=qp["k_scale"], v_scale=qp["v_scale"])
    ref = np.asarray(paged_decode_reference(q, kp, vp, pt, lengths,
                                            **scales))
    live = np.asarray(lengths) > 0
    for interpret in (True, pltpu.InterpretParams()):
        out = np.asarray(flash_decode_fwd(q, kp, vp, pt, lengths,
                                          pages_per_block=4,
                                          interpret=interpret, **scales))
        np.testing.assert_allclose(out[live], ref[live], rtol=1e-3,
                                   atol=1e-3)
        np.testing.assert_array_equal(out[~live], 0)


@pytest.mark.parametrize("kv,quantized,expected", [
    (8, False, 32),     # internlm2.long_decode: bf16 pool
    (4, False, 48),     # yi6b.*: bf16 pool
    (8, True, 24),      # int8 pool: 32-row page tiles + scale rows
    (4, True, 48),
])
def test_choose_block_pages(kv, quantized, expected):
    """The block is the largest divisor of the table width whose
    double-buffered K/V (and scale) blocks fit the VMEM budget."""
    from repro.kernels.decode_attention.kernel import (VMEM_BUDGET,
                                                       choose_block_pages)
    npages, ps, hd = 96, 16, 128          # 1536 rows a slot, page 16
    itemsize = 1 if quantized else 2
    ppb = choose_block_pages(npages, kv, ps, hd, itemsize,
                             quantized=quantized)
    assert ppb == expected
    assert npages % ppb == 0
    # A page in VMEM: 16 bf16 rows fill one tile, 16 int8 rows half of one.
    page = 32 * hd + (8 * 4 * ps if quantized else 0)
    footprint = lambda d: 2 * 2 * kv * d * page
    assert footprint(ppb) <= VMEM_BUDGET
    bigger = [d for d in range(ppb + 1, npages + 1) if npages % d == 0]
    assert all(footprint(d) > VMEM_BUDGET for d in bigger)


def test_default_num_splits_occupancy_adaptive():
    """The verify kernel's split count: more splits at low occupancy,
    fewer at high (batch * splits ~ budget)."""
    from repro.kernels.decode_attention.ops import default_num_splits
    assert default_num_splits(8, batch=1, split_budget=32) == 8
    assert default_num_splits(8, batch=8, split_budget=32) == 4
    assert default_num_splits(8, batch=32, split_budget=32) == 1
    assert default_num_splits(6, batch=4, split_budget=32) == 6  # divisor rule
    assert default_num_splits(8) == 4           # legacy default unchanged


# ---------------------------------------------------------------------------
# Engine-level equivalence
# ---------------------------------------------------------------------------

def _make(arch="yi-6b", **kw):
    kw.setdefault("kv_cache_dtype", KV_DTYPE)
    cfg = get_reduced_config(arch).replace(dtype="float32", page_size=8, **kw)
    fam = get_family(cfg)
    params = init_params(fam.layout(cfg), jax.random.PRNGKey(0),
                         cfg.param_dtype)
    return cfg, params


def _prompts(vocab, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=n).tolist() for n in lens]


def test_continuous_matches_static_equal_lengths():
    """No padding in play: both engines must emit identical tokens."""
    cfg, params = _make()
    prompts = _prompts(cfg.vocab_size, [8, 8, 8])
    a = ServeEngine(cfg, params, max_len=48).generate(prompts, max_new=8)
    b = ContinuousBatchingEngine(cfg, params, max_len=48, max_slots=3) \
        .generate(prompts, max_new=8)
    np.testing.assert_array_equal(a.tokens, b.tokens)
    assert a.prompt_lens == b.prompt_lens


def test_continuous_matches_per_request_gold_mixed_lengths():
    """Ragged batch: continuous batching must match the exact (unpadded,
    single-request) decode — the static engine's left-padding perturbs RoPE
    positions for shorter prompts, so per-request runs are the oracle."""
    cfg, params = _make()
    prompts = _prompts(cfg.vocab_size, [3, 7, 12, 5], seed=1)
    legacy = ServeEngine(cfg, params, max_len=48)
    gold = np.concatenate(
        [legacy.generate([p], max_new=8).tokens for p in prompts])
    out = ContinuousBatchingEngine(cfg, params, max_len=48, max_slots=4) \
        .generate(prompts, max_new=8)
    np.testing.assert_array_equal(gold, out.tokens)


def test_continuous_batching_queues_and_reuses_pages():
    """More requests than slots: eviction frees pages, waiters are admitted,
    and tokens are unchanged vs the all-slots run."""
    cfg, params = _make()
    prompts = _prompts(cfg.vocab_size, [4, 9, 6, 11, 5, 8], seed=2)
    wide = ContinuousBatchingEngine(cfg, params, max_len=32, max_slots=6) \
        .generate(prompts, max_new=6)
    narrow = ContinuousBatchingEngine(cfg, params, max_len=32, max_slots=2,
                                      decode_chunk=4) \
        .generate(prompts, max_new=6)
    np.testing.assert_array_equal(wide.tokens, narrow.tokens)


def test_decode_writes_cross_page_boundaries():
    """Decoded KV rows spill from the prompt page into fresh pages."""
    cfg, params = _make()
    prompts = _prompts(cfg.vocab_size, [6], seed=3)     # page_size=8: crosses
    legacy = ServeEngine(cfg, params, max_len=32)
    gold = legacy.generate(prompts, max_new=12).tokens
    out = ContinuousBatchingEngine(cfg, params, max_len=32, max_slots=1) \
        .generate(prompts, max_new=12)
    np.testing.assert_array_equal(gold, out.tokens)


def test_engine_validates_before_reserving():
    """Bad requests are rejected up front: no slot/page leak, engine reusable."""
    cfg, params = _make()
    eng = ContinuousBatchingEngine(cfg, params, max_len=32, max_slots=2)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.generate([[1, 2, 3], []], max_new=4)
    with pytest.raises(ValueError, match="exceed max_len"):
        eng.generate([[1, 2, 3], list(range(40))], max_new=4)
    assert not eng._active.any()
    assert eng.alloc.available() == eng.num_pages - 1
    out = eng.generate(_prompts(cfg.vocab_size, [4, 6], seed=4), max_new=4)
    assert out.tokens.shape == (2, 4)


def test_paged_decode_rejects_recurrent_families():
    from repro.train.train_step import build_paged_decode_step
    cfg = get_reduced_config("xlstm-350m")
    with pytest.raises(ValueError, match="paged"):
        build_paged_decode_step(cfg)
