"""Block-streaming GQA flash-decode Pallas TPU kernel over a block-paged KV cache.

Decode-step attention is bandwidth-bound: each slot's one query token per
head must stream the slot's whole KV history. The physical pool is
(KV, P, page_size, hd); each request addresses it through a page table, so
ragged batches need no host-side padding or cache compaction. The kernel
moves a *block* of ``ppb`` pages (``bk = ppb * page_size`` rows, all KV
heads) per grid step, because a step that moves one 16-row page spends
its time on per-step overhead, not on HBM:

- Grid = (B, npages // ppb), both sequential. The block axis is innermost,
  so one slot's online-softmax accumulators (m, l, acc, f32) live in VMEM
  scratch across its blocks; the last block normalizes and writes the
  slot's (KV, G, hd) output.
- K/V (and, for an int8 pool, the scale pages) stay in HBM
  (``memory_space=pl.ANY``). Each live page of a block is one
  ``make_async_copy`` of ``pool.at[:, page]`` (all KV heads, one strided
  copy) into page ``j`` of a double-buffered ``(2, KV, ppb, ps, hd)`` VMEM
  buffer. While one block computes, the next live block, in this slot or
  the next slot with a nonzero length, is already in flight.
- Only pages below ``ceil(length / page_size)`` are copied, and a slot
  computes only its ``ceil(length / bk)`` live blocks; later grid steps do
  nothing, and dead page-table entries are never read. Rows at or past
  ``length`` are masked; a length past the table is read as the whole
  table, and a slot of length 0 writes zeros. The V buffer is zeroed once
  per call: rows of pages a block did not copy get probability 0, which
  must not meet a NaN left in VMEM.
- Per block: one batched ``(KV, G, hd) x (KV, bk, hd)`` score matmul, the
  online-softmax update and one batched ``(KV, G, bk) x (KV, bk, hd)``
  matmul, in f32 on the same arithmetic as ``ref.py``. An int8 pool's
  (1, page_size) scale rows scale score column j (K) and probability
  column j (V), as ``kv_quant`` documents.

VMEM: the block size is ``choose_block_pages``'s largest divisor of the
page-table width whose double-buffered K+V(+scales) footprint fits
``VMEM_BUDGET`` (4 MiB). At page_size 16, hd 128 and 96 pages a slot in
bf16 that is 32 pages (512 rows, 4 MiB) at 8 KV heads and 48 pages (768
rows, 3 MiB) at 4; with the f32 copies of one block's K and V the kernel
stays under the 16 MiB scoped default. On a v5e (1 chip) larger blocks
measured faster at both head layouts, up to 48 pages at 8 KV heads.

Numerics are checked against ``ref.py`` in interpret mode
(tests/test_decode_attention.py); tests/test_chip_compile.py compiles it for
a v5e, and ``chip_smoke.py`` compares it with ``ref.py`` on the chip.
``ops.paged_decode_attention`` dispatches here on TPU for
``attn_impl="pallas"``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Bytes of VMEM the double-buffered K/V (and scale) blocks may take.
VMEM_BUDGET = 4 * 1024 * 1024


def choose_block_pages(npages: int, num_kv_heads: int, page_size: int,
                       head_dim: int, itemsize: int, *,
                       quantized: bool = False) -> int:
    """Pages per grid step: the largest divisor of ``npages`` whose
    double-buffered K and V blocks fit ``VMEM_BUDGET``: 2 buffers x 2
    pools x KV heads x a page's rows, padded to the dtype's VMEM tile (8
    rows of f32, 16 of bf16, 32 of int8), x hd x itemsize, plus for an
    int8 pool each row's f32 scale on a lane of an 8-sublane tile. One
    page at least, whatever the budget."""
    tile_rows = 32 // itemsize
    page_rows = -(-page_size // tile_rows) * tile_rows
    page_bytes = 2 * 2 * num_kv_heads * (
        page_rows * head_dim * itemsize + (8 * 4 * page_size if quantized
                                           else 0))
    for ppb in range(npages, 0, -1):
        if npages % ppb == 0 and ppb * page_bytes <= VMEM_BUDGET:
            return ppb
    return 1


def _flash_decode_kernel(len_ref, pt_ref, q_ref, k_hbm, v_hbm, *rest,
                         scale: float, ppb: int, npages: int,
                         quantized: bool):
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sems, state,
         m_scr, l_scr, acc_scr) = rest
        pools = ((k_hbm, k_buf, ks_hbm, ks_buf), (v_hbm, v_buf, vs_hbm, vs_buf))
    else:
        o_ref, k_buf, v_buf, sems, state, m_scr, l_scr, acc_scr = rest
        pools = ((k_hbm, k_buf, None, None), (v_hbm, v_buf, None, None))
    b, i = pl.program_id(0), pl.program_id(1)
    nslots = pl.num_programs(0)
    page_size = k_buf.shape[3]
    bk = ppb * page_size

    def slot_length(slot):
        # A slot whose budget ran out mid-chunk passes one row past its
        # table; every row of the table is then live, and none beyond.
        return jnp.minimum(len_ref[slot], npages * page_size)

    length = slot_length(b)

    def block_copies(pool, slot, blk, buf, op):
        """Start (or wait for) the copies of block ``blk`` of ``slot`` into
        buffer ``buf``: one per live page, and the block's scale row."""
        hbm, vmem, s_hbm, s_vmem = pools[pool]
        first = blk * ppb
        live = jnp.clip(pl.cdiv(slot_length(slot), page_size) - first, 0,
                        ppb)

        def page_copy(j, carry):
            page = pt_ref[slot * npages + first + j]
            getattr(pltpu.make_async_copy(
                hbm.at[:, page], vmem.at[buf, :, j],
                sems.at[pool, buf]), op)()
            return carry

        # A loop, not ppb unrolled branches: lowering time stays flat in ppb.
        jax.lax.fori_loop(0, live, page_copy, 0)
        if s_hbm is not None:
            getattr(pltpu.make_async_copy(
                s_hbm.at[slot, blk], s_vmem.at[buf],
                sems.at[2 + pool, buf]), op)()

    @pl.when((b == 0) & (i == 0))
    def _first_step():
        # state = [buffer of the current block, 1 once a block is in flight]
        state[0] = 0
        state[1] = 0
        v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(i * bk < length)
    def _compute():
        buf = state[0]

        @pl.when(state[1] == 0)
        def _prime():
            for pool in range(2):
                block_copies(pool, b, i, buf, "start")
            state[1] = 1

        # The next live block in grid order: this slot's next, else block 0
        # of the next slot with a positive length (nslots: none). It must
        # be the block whose _compute runs next, or its wait finds copies
        # that were never started.
        in_slot = (i + 1) * bk < length
        next_slot = jax.lax.while_loop(
            lambda s: (s < nslots) & (len_ref[jnp.minimum(s, nslots - 1)]
                                      <= 0),
            lambda s: s + 1, b + 1)
        nb = jnp.where(in_slot, b, next_slot)
        ni = jnp.where(in_slot, i + 1, 0)

        @pl.when(nb < nslots)
        def _prefetch():
            for pool in range(2):
                block_copies(pool, nb, ni, 1 - buf, "start")

        nkv, g, hd = q_ref.shape
        q = q_ref[...].astype(jnp.float32) * scale           # (KV, G, hd)
        block_copies(0, b, i, buf, "wait")
        k = k_buf[buf].astype(jnp.float32).reshape(nkv, bk, hd)
        s = jnp.einsum("kgd,ktd->kgt", q, k,
                       preferred_element_type=jnp.float32)   # (KV, G, bk)
        if quantized:
            s = s * ks_buf[buf, :, :, :bk]
        pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_scr[...]                                  # (KV, G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=2, keepdims=True)
        block_copies(1, b, i, buf, "wait")
        if quantized:
            p = p * vs_buf[buf, :, :, :bk]
        v = v_buf[buf].astype(jnp.float32).reshape(nkv, bk, hd)
        acc_scr[...] = acc_scr[...] * alpha + jnp.einsum(
            "kgt,ktd->kgd", p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        state[0] = 1 - buf

    @pl.when(i == pl.num_programs(1) - 1)
    def _emit():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-20)
                      ).astype(o_ref.dtype)


def _block_scales(scale, page_table, ppb):
    """(KV,P,1,ps) scale pages -> (B, npages // ppb, KV, 1, L): each slot's
    scale rows gathered through its page table, one lane-dense row per
    block and KV head, zero-padded to L, ppb * ps rounded up to 128 lanes. A (1, ps)
    page row cannot be copied into a lane offset of a block row, nor a
    row narrower than a tile copied at all, so the gather runs before the
    kernel; it moves 4 bytes per table row, against hd bytes per row of K
    or V."""
    nkv, _, _, ps = scale.shape
    b, npages = page_table.shape
    rows = scale.astype(jnp.float32)[:, page_table, 0]   # (KV, B, npages, ps)
    rows = rows.reshape(nkv, b, npages // ppb, ppb * ps)
    rows = jnp.pad(rows, ((0, 0),) * 3 + ((0, -ppb * ps % 128),))
    return rows.transpose(1, 2, 0, 3)[:, :, :, None, :]


def flash_decode_fwd(q, k_pages, v_pages, page_table, lengths, *,
                     k_scale=None, v_scale=None,
                     pages_per_block: int | None = None,
                     interpret: bool = False):
    """q: (B,H,hd); k/v_pages: (KV,P,ps,hd); page_table: (B,npages) int32;
    lengths: (B,) int32 -> (B,H,hd). ``k_scale``/``v_scale``: optional
    (KV,P,1,ps) f32 per-row scale pages for an int8 pool — the kernel then
    dequantizes each K/V block in-register (f32 accumulation throughout).
    ``pages_per_block`` overrides the VMEM-budget choice (module doc); it
    must divide the page-table width."""
    b, h, hd = q.shape
    nkv, _, page_size, _ = k_pages.shape
    g = h // nkv
    npages = page_table.shape[1]
    quantized = k_scale is not None
    ppb = pages_per_block or choose_block_pages(
        npages, nkv, page_size, hd, k_pages.dtype.itemsize,
        quantized=quantized)
    if npages % ppb:
        raise ValueError(f"npages {npages} % pages_per_block {ppb}")
    kernel = functools.partial(
        _flash_decode_kernel, scale=1.0 / math.sqrt(hd), ppb=ppb,
        npages=npages, quantized=quantized)

    slot_block = pl.BlockSpec((None, nkv, g, hd),
                              lambda bi, i, ln, pt: (bi, 0, 0, 0))
    in_specs = [slot_block, pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    inputs = [q.reshape(b, nkv, g, hd), k_pages, v_pages]
    scratch = [pltpu.VMEM((2, nkv, ppb, page_size, hd), k_pages.dtype),
               pltpu.VMEM((2, nkv, ppb, page_size, hd), v_pages.dtype)]
    if quantized:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        inputs += [_block_scales(k_scale, page_table, ppb),
                   _block_scales(v_scale, page_table, ppb)]
        scratch += [pltpu.VMEM((2, nkv, 1, -(-ppb * page_size // 128) * 128),
                              jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((4 if quantized else 2, 2)),
        pltpu.SMEM((2,), jnp.int32),              # current buffer, primed
        pltpu.VMEM((nkv, g, 1), jnp.float32),     # running max m
        pltpu.VMEM((nkv, g, 1), jnp.float32),     # running denom l
        pltpu.VMEM((nkv, g, hd), jnp.float32),    # unnormalized accumulator
    ]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, npages // ppb),
            in_specs=in_specs,
            out_specs=slot_block,
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b, nkv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), page_table.astype(jnp.int32).reshape(-1),
      *inputs)
    return out.reshape(b, h, hd)
