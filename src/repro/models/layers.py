"""Shared neural layers: RMSNorm, RoPE, GQA attention (memory-efficient
chunked online-softmax — the FlashAttention dataflow expressed in XLA),
decode attention over KV caches, and (Ge/Swi)GLU FFNs.

All matmuls run in the config's compute dtype with float32 softmax/norm
statistics. ``shard`` consults the active sharding-rule context (see
:mod:`repro.distributed.sharding`) so the same model code lowers on one CPU
device and on a (pod, data, model) production mesh.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.sharding import shard
from .params import ParamSpec

MASK_VALUE = -1e30


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dt)


def norm_spec(d: int) -> ParamSpec:
    return ParamSpec((d,), ("embed",), init="ones", dtype="float32")


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x, positions, theta: float = 10_000.0):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                      # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos, sin = jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _split_gqa(q, num_kv: int):
    """(B,S,H,hd) -> (B,S,KV,G,hd)."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, num_kv, h // num_kv, hd)


def _chunk_body(q_blk, q_pos_blk, k, v, kv_pos, *, causal: bool,
                kv_chunk: int, kv_lo: int, kv_hi: int):
    """Online-softmax over KV chunks [kv_lo, kv_hi) for one Q chunk.

    q_blk: (B, qc, KV, G, hd); k/v: (B, Skv, KV, hd).
    Accumulators are float32 — the FlashAttention recurrence.
    """
    b, qc, nkv, g, hd = q_blk.shape
    scale = 1.0 / math.sqrt(hd)
    nchunks = (kv_hi - kv_lo) // kv_chunk
    m0 = jnp.full((b, nkv, g, qc), MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((b, nkv, g, qc), jnp.float32)
    a0 = jnp.zeros((b, nkv, g, qc, hd), jnp.float32)

    @jax.checkpoint  # flash bwd: recompute each KV tile, save only carries
    def body(carry, idx):
        # named_scope marks this region as Pallas-kernel-eligible: the
        # roofline analysis can model its intermediates as VMEM-resident
        # (see kernels/flash_attention + launch/hlo_analysis).
        with jax.named_scope("flash_tile"):
            m, l, acc = carry
            start = kv_lo + idx * kv_chunk
            k_blk = lax.dynamic_slice_in_dim(k, start, kv_chunk, axis=1)
            v_blk = lax.dynamic_slice_in_dim(v, start, kv_chunk, axis=1)
            pos_blk = lax.dynamic_slice_in_dim(kv_pos, start, kv_chunk, axis=0)
            s = jnp.einsum("bqkgh,bskh->bkgqs", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                mask = q_pos_blk[:, None] >= pos_blk[None, :]  # (qc, kvc)
                s = jnp.where(mask[None, None, None], s, MASK_VALUE)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(nchunks))
    out = acc / jnp.maximum(l, 1e-9)[..., None]               # (B,KV,G,qc,hd)
    return out.transpose(0, 3, 1, 2, 4)                       # (B,qc,KV,G,hd)


def chunked_attention(q, k, v, *, causal: bool = True,
                      q_positions=None, kv_positions=None,
                      q_chunk: int = 512, kv_chunk: int = 1024,
                      block_triangular: bool = False):
    """Memory-efficient GQA attention.

    q: (B,Sq,H,hd), k/v: (B,Skv,KV,hd) -> (B,Sq,H,hd). Never materializes the
    (Sq, Skv) score matrix beyond a (q_chunk, kv_chunk) tile.

    ``block_triangular=True`` unrolls Q chunks in Python and scans only the
    KV chunks at-or-below the diagonal — ~2x fewer attention FLOPs for causal
    self-attention (a §Perf optimization; requires q_positions==kv_positions
    aligned, which holds for self-attention).
    """
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    if sq % q_chunk or skv % kv_chunk:
        raise ValueError(f"seq {sq}/{skv} not divisible by chunks {q_chunk}/{kv_chunk}")
    nkv = k.shape[2]
    qg = _split_gqa(q, nkv)
    if q_positions is None:
        q_positions = jnp.arange(sq)
    if kv_positions is None:
        kv_positions = jnp.arange(skv)

    n_q = sq // q_chunk

    # FlashAttention semantics under autodiff: recompute the (qc, kvc) tiles
    # in the backward pass instead of saving softmax residuals per tile.
    @partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable,
             static_argnums=(1,))
    def one_chunk(i, kv_hi):
        q_blk = lax.dynamic_slice_in_dim(qg, i * q_chunk, q_chunk, axis=1)
        pos_blk = lax.dynamic_slice_in_dim(q_positions, i * q_chunk, q_chunk, 0)
        return _chunk_body(q_blk, pos_blk, k, v, kv_positions, causal=causal,
                           kv_chunk=kv_chunk, kv_lo=0, kv_hi=kv_hi)

    if block_triangular and causal and n_q > 1:
        outs = []
        for i in range(n_q):
            hi = min(skv, ((i + 1) * q_chunk + kv_chunk - 1) // kv_chunk * kv_chunk)
            outs.append(one_chunk(i, hi))
        out = jnp.concatenate(outs, axis=1)
    else:
        idx = jnp.arange(n_q)
        out = lax.map(lambda i: one_chunk(i, skv), idx)       # (n_q,B,qc,KV,G,hd)
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, nkv, h // nkv, hd)
        return out.reshape(b, sq, h, hd).astype(q.dtype)
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def dense_attention(q, k, v, *, causal: bool = True,
                    q_positions=None, kv_positions=None):
    """Reference O(S^2)-memory attention (oracle for tests/kernels)."""
    b, sq, h, hd = q.shape
    nkv = k.shape[2]
    qg = _split_gqa(q, nkv)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    if causal:
        qp = jnp.arange(sq) if q_positions is None else q_positions
        kp = jnp.arange(k.shape[1]) if kv_positions is None else kv_positions
        s = jnp.where((qp[:, None] >= kp[None, :])[None, None, None], s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), v)
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, pos):
    """Single-step attention against a KV cache.

    q: (B,1,H,hd); caches: (B,Smax,KV,hd); pos: (B,) current index (the new
    token's position; cache slots > pos are masked).
    """
    b, _, h, hd = q.shape
    nkv = k_cache.shape[2]
    qg = _split_gqa(q, nkv)[:, 0]                             # (B,KV,G,hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    idx = jnp.arange(k_cache.shape[1])
    mask = idx[None, :] <= pos[:, None]                       # (B,Smax)
    s = jnp.where(mask[:, None, None, :], s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p.astype(v_cache.dtype), v_cache)
    return out.reshape(b, 1, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (projections + RoPE + attention)
# ---------------------------------------------------------------------------

def attention_param_specs(cfg) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed")),
        "norm": norm_spec(d),
    }


def _qkv_proj(cfg, p, x, positions):
    """Pre-norm q/k/v projections with RoPE — shared by the dense and paged
    attention blocks so the projection contract cannot diverge."""
    dt = cfg.cdtype
    xn = rmsnorm(x, p["norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", xn, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", xn, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", xn, p["wv"].astype(dt))
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_block(cfg, p, x, positions, *, cache=None, decode_pos=None):
    """Pre-norm attention residual block.

    Training/prefill: ``cache is None`` → returns (y, (k, v)) so prefill can
    emit the cache. Decode: ``cache=(k_cache, v_cache)``, ``decode_pos=(B,)``
    → returns (y, (k_cache', v_cache')).
    """
    dt = cfg.cdtype
    q, k, v = _qkv_proj(cfg, p, x, positions)
    q = shard(q, ("batch", "attn_seq", "heads", None))
    k = shard(k, ("batch", None, "kv_heads", None))
    if cache is None:
        causal = cfg.causal and not cfg.encoder_only
        from repro.distributed.sharding import current_rules
        rules = current_rules()
        if (cfg.attn_impl == "ring" and rules is not None
                and "model" in rules.mesh.shape):
            # sequence-sharded ring attention over the model axis: fixes the
            # head-count-not-divisible replication (EXPERIMENTS §Perf A4/R1)
            from repro.distributed.ring_attention import ring_attention_sharded
            o = ring_attention_sharded(q, k, v, rules.mesh, causal=causal)
        elif cfg.attn_impl == "dense":
            o = dense_attention(q, k, v, causal=causal)
        else:
            o = chunked_attention(
                q, k, v, causal=causal,
                q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
                block_triangular=cfg.attn_block_triangular)
        new_cache = (k, v)
    else:
        k_cache, v_cache = _scatter_cache(cache, k, v, decode_pos)
        o = decode_attention(q, k_cache, v_cache, decode_pos)
        new_cache = (k_cache, v_cache)
    o = shard(o, ("batch", "attn_seq", "heads", None))
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt))
    return x + y, new_cache


def _scatter_pool(pool, name, rows, page, off):
    """Write KV rows into one pool leaf, quantizing when the pool is int8.

    pool: this layer's pool-slice dict — ``{"k","v"}`` plus, for an int8
    pool, ``{"k_scale","v_scale"}`` per-row scale pages (kernels/kv_quant).
    rows: (KV, ..., hd) new rows; page/off: matching (...,) index arrays.
    Quantize-on-scatter keeps writes O(rows): per-ROW symmetric scales mean
    a louder later row never forces requantizing earlier rows in the page.
    """
    scale_name = name + "_scale"
    if scale_name in pool:
        from repro.kernels.kv_quant import quantize_rows
        q8, s = quantize_rows(rows)
        pool[name] = pool[name].at[:, page, off].set(q8)
        pool[scale_name] = pool[scale_name].at[:, page, 0, off].set(s)
    else:
        pool[name] = pool[name].at[:, page, off].set(
            rows.astype(pool[name].dtype))
    return pool


def _pool_scales(pool):
    return dict(k_scale=pool.get("k_scale"), v_scale=pool.get("v_scale"))


def paged_attention_block(cfg, p, x, *, pool, page_table, pos):
    """Pre-norm attention residual block over a block-paged KV cache.

    x: (B,1,d) new-token activations; pool: this layer's physical pool
    slices — ``{"k","v"}`` of shape (KV,P,ps,hd) plus ``{"k_scale",
    "v_scale"}`` (KV,P,1,ps) when the pool is int8-quantized; page_table:
    (B,npages) int32; pos: (B,) the new token's position per request (cache
    holds [0, pos) valid rows). Returns (y, pool') with the new KV row
    scattered (quantized, for an int8 pool) into the pool page
    ``page_table[b, pos // ps]`` at offset ``pos % ps``.

    ``attn_impl="pallas"`` dispatches the block-streaming decode kernel on TPU
    (see kernels/decode_attention); other impls use the fused-gather oracle.
    Both dequantize int8 tiles with identical f32 arithmetic.
    """
    from repro.kernels.decode_attention import paged_decode_attention
    dt = cfg.cdtype
    b = x.shape[0]
    ps = pool["k"].shape[2]
    q, k, v = _qkv_proj(cfg, p, x, pos[:, None])

    bidx = jnp.arange(b)
    page = page_table[bidx, pos // ps]                  # (B,) physical pages
    off = pos % ps
    pool = dict(pool)
    # (B,1,KV,hd) -> (KV,B,hd) rows written at [kv, page_b, off_b].
    pool = _scatter_pool(pool, "k", k[:, 0].transpose(1, 0, 2), page, off)
    pool = _scatter_pool(pool, "v", v[:, 0].transpose(1, 0, 2), page, off)

    o = paged_decode_attention(q[:, 0], pool["k"], pool["v"], page_table,
                               pos + 1, **_pool_scales(pool),
                               impl=cfg.attn_impl,
                               split_budget=cfg.decode_split_budget)
    y = jnp.einsum("bshk,hkd->bsd", o[:, None].astype(dt), p["wo"].astype(dt))
    return x + y, pool


def paged_verify_attention_block(cfg, p, x, *, pool, page_table,
                                 pos, write_limit):
    """Pre-norm attention residual block for one speculative-verify window.

    x: (B,T,d) activations of the draft window — the already-verified
    current token followed by T-1 drafted candidates, occupying global
    positions ``pos[b] .. pos[b] + T - 1``; pool: this layer's physical pool
    slices (``{"k","v"}`` (KV,P,ps,hd) plus int8 scale pages, see
    ``paged_attention_block``); page_table: (B,npages) int32;
    write_limit: (B,) positions >= write_limit have their KV writes routed
    to the reserved sink page 0 — the engine points it at the slot's token
    budget (prompt_len + max_new), so a draft window running past the
    budget (or a rejected tail re-drafted next step) can never clobber live
    pages through the clamped page-table gather, its own or pages aliased
    from a shared prefix.

    The window's KV rows are scattered into the pool *first* (quantized, for
    an int8 pool); the kernel's positional causal mask (key pos <= query
    pos) then covers both verified history and the in-window lower triangle.
    Rows written for drafts that verification later rejects are simply
    overwritten by the next verify step, which restarts at the first
    rejected position. Returns (y, pool').
    """
    from repro.kernels.verify_attention import paged_verify_attention
    dt = cfg.cdtype
    b, t, _ = x.shape
    ps = pool["k"].shape[2]
    positions = pos[:, None] + jnp.arange(t)[None, :]            # (B, T)
    q, k, v = _qkv_proj(cfg, p, x, positions)

    bidx = jnp.arange(b)[:, None]
    valid = positions < write_limit[:, None]                     # (B, T)
    page = jnp.where(valid, page_table[bidx, positions // ps], 0)
    off = positions % ps
    pool = dict(pool)
    # (B,T,KV,hd) -> (KV,B,T,hd) rows written at [kv, page_bt, off_bt].
    pool = _scatter_pool(pool, "k", k.transpose(2, 0, 1, 3), page, off)
    pool = _scatter_pool(pool, "v", v.transpose(2, 0, 1, 3), page, off)

    o = paged_verify_attention(q, pool["k"], pool["v"], page_table, pos,
                               **_pool_scales(pool),
                               impl=cfg.attn_impl,
                               split_budget=cfg.decode_split_budget)
    y = jnp.einsum("bshk,hkd->bsd", o.astype(dt), p["wo"].astype(dt))
    return x + y, pool


def paged_prefill_attention_block(cfg, p, x, *, pool, page_table,
                                  q_start, kv_len):
    """Pre-norm attention residual block for one paged-prefill chunk.

    x: (B,C,d) chunk activations (C consecutive prompt tokens starting at
    global position ``q_start[b]``); pool: this layer's physical pool slices
    (``{"k","v"}`` (KV,P,ps,hd) plus int8 scale pages, see
    ``paged_attention_block``); page_table: (B,npages) int32; kv_len: (B,)
    the request's true prompt length — chunk positions >= kv_len are padding
    and their KV writes are routed to the reserved sink page 0, so a partial
    tail chunk can never clobber live pages (its own, or pages aliased from a
    shared prefix).

    The chunk's KV rows are scattered into the pool *first* (quantized, for
    an int8 pool); the kernel's positional causal mask (key pos <= query
    pos) then covers both history pages and the in-chunk lower triangle.
    Returns (y, pool').
    """
    from repro.kernels.prefill_attention import paged_prefill_attention
    dt = cfg.cdtype
    b, c, _ = x.shape
    ps = pool["k"].shape[2]
    positions = q_start[:, None] + jnp.arange(c)[None, :]        # (B, C)
    q, k, v = _qkv_proj(cfg, p, x, positions)

    bidx = jnp.arange(b)[:, None]
    valid = positions < kv_len[:, None]                          # (B, C)
    page = jnp.where(valid, page_table[bidx, positions // ps], 0)
    off = positions % ps
    pool = dict(pool)
    # (B,C,KV,hd) -> (KV,B,C,hd) rows written at [kv, page_bc, off_bc].
    pool = _scatter_pool(pool, "k", k.transpose(2, 0, 1, 3), page, off)
    pool = _scatter_pool(pool, "v", v.transpose(2, 0, 1, 3), page, off)

    o = paged_prefill_attention(q, pool["k"], pool["v"], page_table, q_start,
                                **_pool_scales(pool), impl=cfg.attn_impl)
    y = jnp.einsum("bshk,hkd->bsd", o.astype(dt), p["wo"].astype(dt))
    return x + y, pool


def _scatter_cache(cache, k, v, pos):
    """Write one new (k,v) row per batch element at ``pos``."""
    k_cache, v_cache = cache
    b = k.shape[0]
    bidx = jnp.arange(b)
    k_cache = k_cache.at[bidx, pos].set(k[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, pos].set(v[:, 0].astype(v_cache.dtype))
    return k_cache, v_cache


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------

def mlp_param_specs(cfg, d_ff: Optional[int] = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    specs = {
        "w_up": ParamSpec((d, f), ("embed", "mlp")),
        "w_down": ParamSpec((f, d), ("mlp", "embed")),
        "norm": norm_spec(d),
    }
    if cfg.mlp_gated:
        specs["w_gate"] = ParamSpec((d, f), ("embed", "mlp"))
    return specs


def _act(name: str):
    return jax.nn.silu if name == "silu" else partial(jax.nn.gelu, approximate=True)


def mlp_block(cfg, p, x):
    dt = cfg.cdtype
    xn = rmsnorm(x, p["norm"], cfg.norm_eps)
    up = jnp.einsum("bsd,df->bsf", xn, p["w_up"].astype(dt))
    if cfg.mlp_gated:
        gate = jnp.einsum("bsd,df->bsf", xn, p["w_gate"].astype(dt))
        h = _act(cfg.mlp_act)(gate) * up
    else:
        h = _act(cfg.mlp_act)(up)
    h = shard(h, ("batch", None, "mlp"))
    y = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))
    return x + y


# ---------------------------------------------------------------------------
# Embedding / logits / loss
# ---------------------------------------------------------------------------

def embed_param_specs(cfg) -> dict:
    specs = {"embedding": ParamSpec((cfg.vocab_size, cfg.d_model),
                                    ("vocab", "embed"), init="embed")}
    if not cfg.tie_embeddings:
        specs["unembed"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                                     ("embed", "vocab"))
    return specs


def embed_tokens(cfg, p, tokens):
    return p["embedding"].astype(cfg.cdtype)[tokens]


def logits_fn(cfg, p, x):
    dt = cfg.cdtype
    w = (p["embedding"].T if cfg.tie_embeddings else p["unembed"]).astype(dt)
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    return shard(logits, ("batch", None, "vocab"))


def softmax_xent(logits, labels, logit_dtype=jnp.float32):
    """Mean token cross-entropy, stats in float32."""
    lg = logits.astype(logit_dtype)
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def chunked_xent(cfg, p, x, labels, chunk: int):
    """Loss without materializing full-seq logits (lax.map over seq chunks;
    per-chunk logits are recomputed in the backward pass)."""
    b, s, d = x.shape
    chunk = min(chunk, s)
    if s % chunk:
        return softmax_xent(logits_fn(cfg, p, x), labels)
    n = s // chunk

    @partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def one(i):
        xs = lax.dynamic_slice_in_dim(x, i * chunk, chunk, axis=1)
        ys = lax.dynamic_slice_in_dim(labels, i * chunk, chunk, axis=1)
        return softmax_xent(logits_fn(cfg, p, xs), ys) * chunk

    return jnp.sum(lax.map(one, jnp.arange(n))) / s
