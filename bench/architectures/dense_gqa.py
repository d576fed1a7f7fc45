"""The dense grouped-query-attention decoder of the Llama family (yi-6b,
InternLM2-1.8B): what the harness takes from a configuration whose file
names no ``architecture``.

An architecture module gives the harness everything specific to one kind
of model, under these names:

- ``Reference(m, seed, *, fp8=False)``: the plain forward pass of the
  ``model`` sizes ``m`` with the weights of ``seed``, with
  ``final_hidden(tokens)``, ``unembed()`` and ``logits(rows, u)``
  (:mod:`bench.check`; ``fp8=True`` is the control);
- ``LEAVES``: the weight rule of every leaf path the program's tree has
  (:mod:`bench.weights`);
- ``decode_attention_call``, ``prefill_attention_call``,
  ``decode_step_flops`` and ``prefill_step_flops``: the operations and
  bytes the readers hold kernel and step times to (:mod:`bench.readers`,
  with the signatures of :mod:`bench.counts`).

This one is the reference of :mod:`bench.reference`, the table of
:mod:`bench.weights` and the counts of :mod:`bench.counts`.
"""
from bench.counts import (decode_attention_call, decode_step_flops,
                          prefill_attention_call, prefill_step_flops)
from bench.reference import Reference
from bench.weights import LEAVES

__all__ = ["Reference", "LEAVES", "decode_attention_call",
           "prefill_attention_call", "decode_step_flops",
           "prefill_step_flops"]
