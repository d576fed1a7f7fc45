"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample of
the requests the engine finished, drawn from the run seed and holding the
longest of them, is run through the reference of the configuration's
architecture (``Reference`` of ``bench/architectures/<name>.py``; for the
dense decoder, :mod:`bench.reference`): each prompt followed by the tokens
it was served, once. For every served token the gap by which the
reference's logit for it lies below the reference's best logit at that
position is read, and the widest gap is held to the cell's limit
(``bench/checks/<cell>.json``). Greedy decoding serves the argmax, so a
sound program reads a gap of rounding size only.

The control puts the reference in float8 in the program's place: at each
position of the same prompts and served tokens, the token it ranks first
is read in the float32 reference the same way.

A new architecture brings its reference as a file of its own, with its
``fp8`` control; this comparison, and the limits of
``bench/checks/<cell>.json``, stay as they are.
"""
from __future__ import annotations

import json
import math
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from .reference import Reference

CHECKS_DIR = Path(__file__).resolve().parent / "checks"
ROW_BLOCK = 256


def load_limits(cell: str, directory: Path = CHECKS_DIR) -> dict:
    return json.loads((directory / f"{cell}.json").read_text())


def sample(rec, seed: int, min_tokens: int, max_requests: int) -> list:
    """rids of finished requests: the longest (prompt plus output), then
    others drawn from ``seed`` until ``min_tokens`` served tokens or
    ``max_requests`` requests are in."""
    done = sorted(rec.tokens)
    if not done:
        return []
    size = {r: len(rec.requests[r].prompt) + len(rec.tokens[r])
            for r in done}
    longest = max(done, key=lambda r: (size[r], -r))
    rest = [r for r in done if r != longest]
    order = np.random.default_rng(
        np.random.SeedSequence([int(seed), 4])).permutation(len(rest))
    out, served = [longest], len(rec.tokens[longest])
    for i in order:
        if served >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        served += len(rec.tokens[rest[i]])
    return out


def _batch(rec, rids, rows: int, bucket: int = 256):
    """Token rows (prompt + served tokens but the last), padded to ``rows``
    rows and a multiple of ``bucket`` columns so that runs share compiled
    shapes, and per row the positions whose logits chose the served
    tokens."""
    seqs, wanted = [], []
    for r in rids:
        prompt, toks = list(rec.requests[r].prompt), rec.tokens[r]
        seqs.append(prompt + toks[:-1])
        wanted.append((len(prompt) - 1, toks))
    s = bucket * math.ceil(max(len(x) for x in seqs) / bucket)
    arr = np.zeros((max(rows, len(seqs)), s), np.int32)
    for i, x in enumerate(seqs):
        arr[i, :len(x)] = x
    return arr, wanted


def _rows(hidden, wanted):
    idx_b, idx_t, tokens = [], [], []
    for i, (start, toks) in enumerate(wanted):
        idx_b += [i] * len(toks)
        idx_t += list(range(start, start + len(toks)))
        tokens += toks
    rows = hidden[jnp.asarray(idx_b), jnp.asarray(idx_t)]
    return rows, np.asarray(tokens, np.int64)


@jax.jit
def _gaps(logits, chosen):
    best = jnp.max(logits, axis=-1)
    return best - jnp.take_along_axis(logits, chosen[:, None], -1)[:, 0]


def compare(m: dict, seed: int, rec, rids, *, pad_rows: int = 1,
            control: bool = False, reference=Reference) -> dict:
    """Widest gap of the served tokens (and, with ``control``, of the
    float8 reference's first choices) below the best logit of
    ``reference`` (the architecture's class), the sequences padded to
    ``pad_rows``. Returns the readings with the number of tokens
    compared."""
    t0 = time.perf_counter()
    tokens, wanted = _batch(rec, rids, pad_rows)
    vocab = m["vocab_size"]
    served_all = np.concatenate([np.asarray(t) for _, t in wanted])
    out_of_vocab = int(((served_all < 0) | (served_all >= vocab)).sum())
    ref = reference(m, seed)
    rows, served = _rows(ref.final_hidden(tokens), wanted)
    rows.block_until_ready()
    t1 = time.perf_counter()
    served = np.clip(served, 0, vocab - 1)
    ctrl_rows = None
    if control:
        ctl = reference(m, seed, fp8=True)
        ctrl_rows, _ = _rows(ctl.final_hidden(tokens), wanted)
        ctrl_u = ctl.unembed()
    u = ref.unembed()
    gap = []
    ctrl_gap = []
    for i in range(0, rows.shape[0], ROW_BLOCK):
        lg = ref.logits(rows[i:i + ROW_BLOCK], u)
        gap.append(np.asarray(_gaps(lg, jnp.asarray(served[i:i + ROW_BLOCK]))))
        if control:
            pick = jnp.argmax(ctl.logits(ctrl_rows[i:i + ROW_BLOCK], ctrl_u),
                              axis=-1)
            ctrl_gap.append(np.asarray(_gaps(lg, pick)))
    gap = np.concatenate(gap)
    out = {"max_logit_gap": float(gap.max()),
           "tokens_compared": int(gap.size),
           "requests_compared": len(rids),
           "out_of_vocab": out_of_vocab,
           "timing": {"shape": list(tokens.shape), "hidden_s": t1 - t0,
                      "rest_s": time.perf_counter() - t1}}
    if control:
        out["control_max_logit_gap"] = float(np.concatenate(ctrl_gap).max())
    return out
