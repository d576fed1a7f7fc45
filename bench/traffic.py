"""The one traffic generator: turns a mix's parameter file into requests.

A mix is ``bench/traffic/<name>.json``; its keys:

- ``loop``: ``"open"`` (arrivals on a schedule, whatever the server does)
  or ``"closed"`` (``clients`` callers, each sending its next request when
  the previous one completes).
- ``rate_per_s`` (open): the base arrival rate; optional ``bursts``
  ``{"every_s", "for_s", "factor"}`` multiplies it for ``for_s`` seconds in
  every ``every_s``. Arrivals are a Poisson process, thinned for bursts.
- ``clients`` and ``requests`` (closed): callers, and the size of the
  request list they draw from in order.
- ``tenants`` and ``zipf``: requests are spread over tenants by a Zipf law
  of that exponent (rank 1 heaviest).
- ``prompt`` and ``output``: lognormal lengths ``{"median", "sigma",
  "min", "max"}`` of the unique prompt part and of the tokens to generate.
- ``shared`` (optional): ``{"docs", "tokens", "choose"}``: ``docs``
  documents of ``tokens`` tokens that prompts start with; ``choose`` is
  ``"tenant"`` (tenant i reads document i) or ``"uniform"``.
- ``namespace``: ``"tenant"`` scopes each request's prefix cache to its
  tenant; ``"none"`` shares one namespace.
- ``mix_seed``: fixes the mix itself (the multiset of tenants and lengths,
  and the arrival times), so every run seed serves the same work.

The run seed only orders that work and draws the token ids: the same seed
gives byte-identical requests, and two seeds give the same set of sizes at
the same times in another order.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclass(frozen=True)
class Request:
    """One request as the driver sends it."""
    rid: int
    at_s: float              # scheduled arrival (open loop); 0 for closed
    tenant: int
    doc: int                 # -1: no shared document
    prompt: tuple
    max_new: int


@dataclass(frozen=True)
class Traffic:
    spec: dict
    requests: list           # in sending order
    docs: list               # shared documents, token tuples

    @property
    def open_loop(self) -> bool:
        return self.spec["loop"] == "open"

    def namespace(self, req: Request):
        return req.tenant if self.spec.get("namespace") == "tenant" \
            else None


def load_spec(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    path = directory / f"{name}.json"
    spec = json.loads(path.read_text())
    if spec.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    return spec


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(w) for w in words]))


def lognormal_lengths(rng: np.random.Generator, dist: dict,
                      n: int) -> np.ndarray:
    """``n`` lengths, lognormal around ``median``, rounded and clipped to
    [min, max]."""
    x = np.exp(rng.normal(math.log(dist["median"]), dist["sigma"], n))
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def zipf_ranks(rng: np.random.Generator, n_items: int, alpha: float,
               n: int) -> np.ndarray:
    """``n`` draws of a rank in [0, n_items) with P(r) ∝ (r+1)^-alpha."""
    w = (np.arange(1, n_items + 1, dtype=np.float64)) ** -alpha
    return rng.choice(n_items, size=n, p=w / w.sum())


def rate_at(spec: dict, t: float) -> float:
    """Arrival rate at ``t`` seconds into the window."""
    rate = float(spec["rate_per_s"])
    b = spec.get("bursts")
    if b and (t % b["every_s"]) < b["for_s"]:
        rate *= b["factor"]
    return rate


def arrival_times(spec: dict, seconds: float) -> np.ndarray:
    """Poisson arrivals over [0, seconds), by Lewis thinning: candidates
    at the peak rate, each kept with probability rate(t) / peak. Drawn
    from the mix's own seed, so every run seed sees the same times."""
    rng = _rng(spec["mix_seed"], 1)
    peak = float(spec["rate_per_s"]) * (
        spec["bursts"]["factor"] if spec.get("bursts") else 1.0)
    out = []
    t = 0.0
    while True:
        t += float(rng.exponential(1.0 / peak))
        if t >= seconds:
            return np.asarray(out)
        if float(rng.uniform()) <= rate_at(spec, t) / peak:
            out.append(t)


def generate(spec: dict, seed: int, seconds: float, vocab: int) -> Traffic:
    """The requests of one run of mix ``spec`` with run seed ``seed``."""
    if spec["loop"] == "open":
        times = arrival_times(spec, seconds)
        n = len(times)
    else:
        n = int(spec["requests"])
        times = np.zeros(n)
    mix = _rng(spec["mix_seed"], 2)
    tenants = zipf_ranks(mix, int(spec["tenants"]), float(spec["zipf"]), n)
    plens = lognormal_lengths(mix, spec["prompt"], n)
    outs = lognormal_lengths(mix, spec["output"], n)
    shared = spec.get("shared")
    if shared and shared["choose"] == "tenant":
        if shared["docs"] != spec["tenants"]:
            raise ValueError("choose 'tenant' needs one document per tenant")
        docs_of = tenants
    elif shared:
        docs_of = mix.integers(0, shared["docs"], n)
    else:
        docs_of = np.full(n, -1)

    run = _rng(seed, 3)
    order = run.permutation(n)
    docs = [tuple(int(x) for x in run.integers(0, vocab, shared["tokens"]))
            for _ in range(shared["docs"])] if shared else []
    requests = []
    for i in range(n):
        j = order[i]
        doc = int(docs_of[j])
        tail = tuple(int(x) for x in run.integers(0, vocab, int(plens[j])))
        prompt = (docs[doc] if doc >= 0 else ()) + tail
        requests.append(Request(rid=i, at_s=float(times[i]),
                                tenant=int(tenants[j]), doc=doc,
                                prompt=prompt, max_new=int(outs[j])))
    return Traffic(spec=spec, requests=requests, docs=docs)


def longest_request(spec: dict) -> int:
    """Prompt plus output tokens of the longest request the mix can hold."""
    shared = spec.get("shared")
    return (shared["tokens"] if shared else 0) + spec["prompt"]["max"] + \
        spec["output"]["max"]
