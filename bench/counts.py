"""Operations and bytes of each kernel call and of each model step.

Everything here is computed from shapes and live lengths, never read from
the program: these are the algorithm's needs, counted so that a share of
the roofline or of the peak cannot exceed 100% unless the time measured
leaves out part of the work. Only useful work counts: rows of a slot that
has spent its budget, pad rows of a prefill bucket and idle slots add
nothing, although the program may compute them.

``m`` is the ``model`` section of a configuration file (bench/configs):
num_layers, d_model, num_heads, num_kv_heads, head_dim, d_ff, vocab_size.
"""
from __future__ import annotations

from dataclasses import dataclass

BF16 = 2


@dataclass
class Work:
    """Operations and bytes of a set of calls, with the least time the
    chip could take for them: per call, the larger of operations over the
    peak rate and bytes over the peak bandwidth."""
    flops: float = 0.0
    bytes: float = 0.0
    bound_s: float = 0.0
    memory_bound_calls: int = 0
    calls: int = 0

    def add(self, flops: float, nbytes: float, peaks: dict,
            calls: int = 1) -> None:
        """Add ``calls`` identical calls of ``flops`` and ``nbytes`` each."""
        t_f = flops / peaks["bf16_flops_per_s"]
        t_b = nbytes / peaks["hbm_bytes_per_s"]
        self.flops += flops * calls
        self.bytes += nbytes * calls
        self.bound_s += max(t_f, t_b) * calls
        self.memory_bound_calls += calls if t_b >= t_f else 0
        self.calls += calls

    @property
    def bound(self) -> str:
        """Which roof bounds most of the calls."""
        if not self.calls:
            return "none"
        return "memory" if 2 * self.memory_bound_calls >= self.calls \
            else "compute"


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies through in one dense GQA layer with a
    gated MLP: q, k, v, o projections and gate, up, down."""
    d, h, kv, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                        m["head_dim"], m["d_ff"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff


def attention_flops(m: dict, q_positions) -> float:
    """QK^T and PV of one layer for queries at ``q_positions``, each
    attending causally to keys 0..position."""
    per_key = 4 * m["num_heads"] * m["head_dim"]
    return float(per_key * sum(p + 1 for p in q_positions))


def decode_attention_call(m: dict, lengths) -> tuple[float, float]:
    """One paged decode-attention kernel call (one layer, one step) for the
    live slots, slot i attending ``lengths[i]`` keys. Returns (flops,
    bytes): bf16 K and V rows read, q read and output written."""
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    keys = sum(lengths)
    flops = 4.0 * h * hd * keys
    nbytes = (2.0 * kv * hd * keys + 2.0 * h * hd * len(lengths)) * BF16
    return flops, nbytes


def prefill_attention_call(m: dict, rows) -> tuple[float, float]:
    """One paged prefill-attention kernel call (one layer, one chunk step)
    for the rows of a wave that have prompt tokens in this chunk; a row is
    (start, n): n new tokens at positions start..start+n-1, attending the
    start+n keys before and within the chunk. Returns (flops, bytes)."""
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    flops = 0.0
    nbytes = 0.0
    for start, n in rows:
        flops += attention_flops(m, range(start, start + n))
        nbytes += (2.0 * kv * hd * (start + n) + 2.0 * h * hd * n) * BF16
    return flops, nbytes


def decode_step_flops(m: dict, lengths) -> float:
    """Model operations of one decode step for the live slots: every layer's
    matmuls and attention, and the lm head, one token per slot."""
    n = len(lengths)
    per_layer = 2.0 * layer_matmul_params(m) * n + attention_flops(
        m, [length - 1 for length in lengths])
    return m["num_layers"] * per_layer + 2.0 * m["d_model"] * \
        m["vocab_size"] * n


def prefill_step_flops(m: dict, rows) -> float:
    """Model operations of one prefill chunk step: every layer's matmuls
    and attention over the new tokens, and the lm head for the rows whose
    last prompt token is in this chunk (``rows``: (start, n, last))."""
    tokens = sum(n for _, n, _ in rows)
    attn = sum(attention_flops(m, range(s, s + n)) for s, n, _ in rows)
    heads = sum(1 for _, _, last in rows if last)
    return m["num_layers"] * (2.0 * layer_matmul_params(m) * tokens + attn) \
        + 2.0 * m["d_model"] * m["vocab_size"] * heads
