"""Model + shape configuration schema.

Every assigned architecture is a ``ModelConfig``; every assigned input shape a
``ShapeConfig``. ``(ModelConfig, ShapeConfig)`` cells drive smoke tests, the
multi-pod dry-run and the roofline table.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional

import jax.numpy as jnp


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | audio | ssm | vlm | hybrid
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // num_heads

    # --- MoE ---------------------------------------------------------------
    num_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 1.25
    moe_group_size: int = 512        # GShard dispatch group size (tokens)
    moe_impl: str = "einsum"         # "einsum" (GShard baseline) | "sort"
    moe_dense_ff: int = 0            # Arctic: parallel dense-residual FFN width
    router_z_loss: float = 1e-3
    load_balance_loss: float = 1e-2

    # --- SSM (Mamba2 / xLSTM) -----------------------------------------------
    ssm_variant: str = ""            # "mamba2" | "xlstm"
    ssm_state: int = 0               # N (d_state)
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_chunk: int = 128             # SSD chunk length
    ssm_conv: int = 4                # short conv window

    # --- hybrid (Zamba2) -----------------------------------------------------
    shared_attn_every: int = 0       # apply the shared attention block every k layers

    # --- FFN ------------------------------------------------------------------
    mlp_gated: bool = True           # SwiGLU/GeGLU vs plain 2-matrix FFN
    mlp_act: str = "silu"            # "silu" | "gelu"

    # --- attention / positions -----------------------------------------------
    causal: bool = True
    encoder_only: bool = False
    rope_theta: float = 10_000.0
    attn_q_chunk: int = 512
    attn_kv_chunk: int = 1024
    attn_impl: str = "chunked"       # "chunked" | "dense" | "pallas"
    attn_block_triangular: bool = False  # skip fully-masked KV chunks (perf opt)

    # --- serving (paged KV cache / continuous batching) ------------------------
    page_size: int = 16              # KV rows per physical cache page
    max_decode_slots: int = 8        # concurrent requests the serve engine admits
    prefill_chunk: int = 32          # query tokens per paged-prefill step
    enable_prefix_cache: bool = True # share prompt-prefix pages copy-on-write
    # Gateway decode preemption: an otherwise-infeasible interactive request
    # may pause the latest-deadline batch-class slot (KV pages pinned,
    # lossless zero-re-prefill resume) when the feasibility walk says the
    # pause meets BOTH deadlines. Consumed by the serving launcher when it
    # builds the gateway's DeadlineCostPolicy; pools should keep page
    # headroom, since a paused request's pages stay allocated while parked.
    enable_decode_preemption: bool = True
    # Self-speculative decode: each engine step drafts spec_tokens candidates
    # per slot by n-gram lookup over the slot's own token history and scores
    # all spec_tokens+1 positions in one paged multi-query verify pass.
    # Greedy outputs are token-identical to the non-speculative path; the win
    # is fewer engine steps per token on repetitive/structured output.
    enable_spec_decode: bool = False
    spec_tokens: int = 4             # drafted tokens per verify step (K)
    # Draft-key order: 2 = trailing bigram (hist[pos-1], cur); 3 = trailing
    # trigram, falling back to the bigram match when the trigram has no
    # earlier occurrence (sharper drafts on structured output, same greedy
    # tokens either way — verification restores exactness).
    spec_ngram: int = 2
    # Batch-adaptive decode tuning: the verify kernel's split-KV count is
    # chosen as ~decode_split_budget / slot_width (the decode kernel has no
    # split axis), where
    # slot_width is the dispatch's static batch dimension (max_slots — NOT
    # the live request count, which would retrace per occupancy level),
    # clamped to a divisor of the page-table width; the decode chunk length
    # targets ~decode_chunk_tokens tokens per on-device chunk dispatch,
    # clamped to
    # [decode_chunk_min, decode_chunk_max] — wide batches amortize the host
    # sync across slots and take shorter chunks, which also re-admits queued
    # requests sooner (lower p95). Under spec decode a step emits up to
    # spec_tokens+1 tokens, so the engine divides both the token target and
    # decode_chunk_min by that window (floor 2): chunks are sized in emitted
    # tokens, not steps.
    decode_split_budget: int = 32    # target batch * num_splits product
    decode_chunk_tokens: int = 256   # target slots * decode_chunk product
    decode_chunk_min: int = 8
    decode_chunk_max: int = 32
    # Paged KV pool storage dtype: "f32" keeps pages in the compute dtype
    # (the exact baseline path); "int8" stores pages as symmetric per-row
    # int8 with an f32 scale per (layer, kv-head, page, row) — the paged
    # attention kernels dequantize inside their K/V tile loads with f32
    # accumulation, so the pool holds ~3.9x the tokens per HBM byte at
    # hd=128 while greedy decode stays token-identical on the parity suite
    # (tests/test_kv_parity.py). Opt-in: "f32" is byte-identical to the
    # pre-quantization engine.
    kv_cache_dtype: str = "f32"
    # Per-slot adaptive speculation: each slot carries an accept-rate EMA
    # and shrinks/grows its draft window within 1..spec_tokens so verify
    # FLOPs track acceptance instead of paying K+1 query rows for slots
    # that accept nothing. Greedy outputs stay token-identical for ANY
    # window schedule (accepted prefixes are exact greedy matches).
    spec_adaptive_k: bool = False

    # --- modality frontend stub (audio / vlm) ---------------------------------
    frontend: str = ""               # "" | "frame" | "patch"
    frontend_dim: int = 0            # 512 (HuBERT features) / 1152 (SigLIP)
    frontend_len: int = 0            # image patches per example (PaliGemma: 256)

    # --- numerics / execution ---------------------------------------------------
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"          # activation/compute dtype
    param_dtype: str = "float32"
    logit_dtype: str = "float32"
    remat: str = "none"              # "none" | "full" | "dots"
    scan_layers: bool = True
    logit_chunk: int = 0             # chunk the loss over seq (0 = off)
    tie_embeddings: bool = False

    # --- per-arch sharding rule overrides (logical axis -> mesh axis name) ------
    sharding_overrides: dict = field(default_factory=dict, hash=False, compare=False)

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.num_heads % max(self.num_kv_heads, 1):
            raise ValueError(f"{self.name}: heads {self.num_heads} not a multiple "
                             f"of kv heads {self.num_kv_heads}")
        if self.kv_cache_dtype not in ("f32", "int8"):
            raise ValueError(f"{self.name}: kv_cache_dtype must be 'f32' or "
                             f"'int8', got {self.kv_cache_dtype!r}")

    # -- dtypes -------------------------------------------------------------
    @property
    def cdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # -- derived sizes ----------------------------------------------------------
    @property
    def d_inner(self) -> int:
        """SSM inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    def param_count(self) -> int:
        """Total parameters (analytic; cross-checked against init in tests)."""
        from repro.models.registry import count_params  # lazy, avoids cycle
        return count_params(self)

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: routed experts only)."""
        from repro.models.registry import count_params
        return count_params(self, active_only=True)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str            # "train" | "prefill" | "decode"
    seq_len: int         # train/prefill: sequence length; decode: KV-cache length
    global_batch: int

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


# The assigned LM-family shape set (same four for every architecture).
TRAIN_4K = ShapeConfig("train_4k", "train", 4_096, 256)
PREFILL_32K = ShapeConfig("prefill_32k", "prefill", 32_768, 32)
DECODE_32K = ShapeConfig("decode_32k", "decode", 32_768, 128)
LONG_500K = ShapeConfig("long_500k", "decode", 524_288, 1)

SHAPES: dict[str, ShapeConfig] = {
    s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
}


def runnable(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Assignment skip rules. Returns (runnable, reason_if_not)."""
    if cfg.encoder_only and shape.is_decode:
        return False, "encoder-only arch has no autoregressive decode step"
    full_attention = cfg.family in ("dense", "moe", "vlm") or (
        cfg.family == "audio")
    if shape.name == "long_500k" and full_attention:
        return False, "pure full-attention arch; long_500k requires sub-quadratic"
    return True, ""
