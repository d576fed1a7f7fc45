"""jit'd public wrappers for the paged flash-decode kernel.

``flash_decode`` is the raw kernel entry point (interpret-capable for CPU
validation). ``paged_decode_attention`` is what the model decode path calls:
it dispatches to the Pallas kernel on TPU silicon (``attn_impl="pallas"``)
and to the fused-gather jnp reference everywhere else, so the same serving
engine runs on a laptop CPU and a TPU pod slice.
"""
from __future__ import annotations

from functools import partial

import jax

from .kernel import flash_decode_fwd
from .ref import paged_decode_reference


@partial(jax.jit, static_argnames=("pages_per_block", "interpret"))
def flash_decode(q, k_pages, v_pages, page_table, lengths, *,
                 k_scale=None, v_scale=None,
                 pages_per_block: int | None = None, interpret: bool = False):
    return flash_decode_fwd(q, k_pages, v_pages, page_table, lengths,
                            k_scale=k_scale, v_scale=v_scale,
                            pages_per_block=pages_per_block,
                            interpret=interpret)


def default_num_splits(npages: int, target: int = 4, *, batch: int = 0,
                       split_budget: int = 0) -> int:
    """Largest split count <= target that divides the page-table width.

    The split-KV verify kernel (``kernels/verify_attention``) takes its
    split count from here; the decode kernel has no split axis. When
    ``batch`` and ``split_budget`` are given, the target adapts to
    occupancy: holding ``batch * splits`` near the budget gives split
    counts of 32/8/1 at batch 1/4/32 for the default budget (see
    ``ModelConfig.decode_split_budget``). Splits only help a chip whose
    cores the (B, KV) grid leaves idle; a v5e has one TensorCore, where
    splits run one after another and add a partial combine.
    """
    if split_budget and batch:
        target = max(1, split_budget // batch)
    for s in range(min(target, npages), 0, -1):
        if npages % s == 0:
            return s
    return 1


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           k_scale=None, v_scale=None,
                           impl: str = "pallas", split_budget: int = 32):
    """Paged GQA decode attention with backend dispatch (see module doc).

    ``k_scale``/``v_scale``: per-row scale pages for an int8 pool; both
    backends dequantize with identical f32 arithmetic (kernel: per tile
    load; reference: whole pool up front). ``split_budget`` is accepted
    for the verify kernel's sake and not used: the decode kernel has no
    split axis.
    """
    if impl == "pallas" and jax.default_backend() == "tpu":
        return flash_decode_fwd(q, k_pages, v_pages, page_table, lengths,
                                k_scale=k_scale, v_scale=v_scale)
    return paged_decode_reference(q, k_pages, v_pages, page_table, lengths,
                                  k_scale=k_scale, v_scale=v_scale)
