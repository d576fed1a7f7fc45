"""Open-loop and closed-loop drivers over the engine's stepped API.

The loop mirrors the gateway's pump: one admission wave, then one decode
chunk, on ``time.perf_counter``. Arrivals of an open loop are due at fixed
times from the window's opening, whatever the engine does; a closed loop
keeps ``clients`` requests outstanding, each caller sending its next one
when the previous one completes.

The driver keeps its own record of the window: when each request was due,
enqueued, admitted (its first token is on the host when ``admit`` returns)
and finished, the tokens it got, the host spans (admit, decode, wait), and
for each decode chunk and admission wave what the device had to do, from
the driver's own bookkeeping of prompt lengths and tokens delivered.
"""
from __future__ import annotations

import math
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax

DRAIN_LIMIT_S = 120.0


@dataclass
class Chunk:
    """One decode chunk: host times, device trip count, and per live
    request (position at the chunk's start, steps it is live)."""
    t0: float
    t1: float
    steps: int
    seconds: float               # as the engine's on_chunk measured it
    live: list                   # [(pos, budget)]
    delivered: int


@dataclass
class Wave:
    """One admission wave: the (start, prompt length) of each request it
    admitted, start being the cached prefix found before the wave."""
    t0: float
    t1: float
    rows: list                   # [(start, plen)]
    prefill_tokens: int          # the engine's own count for the wave


@dataclass
class Record:
    seconds: float
    due: dict = field(default_factory=dict)         # rid -> s
    enqueued: dict = field(default_factory=dict)
    first: dict = field(default_factory=dict)
    done: dict = field(default_factory=dict)
    tokens: dict = field(default_factory=dict)
    requests: dict = field(default_factory=dict)    # rid -> Request
    spans: list = field(default_factory=list)       # (name, t0, t1)
    chunks: list = field(default_factory=list)
    waves: list = field(default_factory=list)
    stats_open: dict = field(default_factory=dict)
    stats_close: dict = field(default_factory=dict)
    closed_at: float = 0.0       # when the loop stopped taking new work
    drained_at: float = 0.0
    window_chunks: int = 0       # chunks and waves before the close
    window_waves: int = 0


class Driver:
    """Drives ``engine`` with ``traffic`` for ``seconds``."""

    def __init__(self, engine, traffic, seconds: float,
                 annotate: bool = False):
        from repro.serve.engine import EngineRequest
        self._req_cls = EngineRequest
        self.eng = engine
        self.traffic = traffic
        self.seconds = float(seconds)
        self.annotate = annotate
        self.rec = Record(seconds=self.seconds)
        self._queue: deque = deque()        # rids in the engine's queue
        self._live: dict = {}               # rid -> tokens delivered
        self._t0 = 0.0
        self._next = 0                      # next request to send

    # -- helpers ----------------------------------------------------------------
    def now(self) -> float:
        return time.perf_counter() - self._t0

    @contextmanager
    def span(self, name: str):
        t0 = self.now()
        if self.annotate:
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                yield
        else:
            yield
        self.rec.spans.append((name, t0, self.now()))

    def _send(self, req, due: float) -> None:
        r = self.rec
        r.requests[req.rid] = req
        r.due[req.rid] = due
        r.enqueued[req.rid] = self.now()
        self.eng.enqueue(self._req_cls(req.rid, list(req.prompt),
                                       req.max_new,
                                       self.traffic.namespace(req)))
        self._queue.append(req.rid)

    def _admit(self) -> None:
        eng = self.eng
        cache = eng.prefix_cache
        head = list(self._queue)[:eng.free_slots]
        starts = {}
        for rid in head:
            req = self.rec.requests[rid]
            if cache is None:
                starts[rid] = 0
            else:
                _, raw = cache.lookup(list(req.prompt),
                                      self.traffic.namespace(req))
                starts[rid] = min(raw, len(req.prompt) - 1)
        before = eng.stats["prefill_tokens"]
        t0 = self.now()
        with self.span("admit"):
            n = eng.admit()
        t1 = self.now()
        rows = []
        for _ in range(n):
            rid = self._queue.popleft()
            self.rec.first[rid] = t1
            self._live[rid] = 0
            rows.append((starts[rid], len(self.rec.requests[rid].prompt)))
        if n:
            self.rec.waves.append(Wave(t0, t1, rows,
                                       eng.stats["prefill_tokens"] - before))

    def _decode(self) -> list:
        eng = self.eng
        live = []
        for rid, got in self._live.items():
            req = self.rec.requests[rid]
            live.append((len(req.prompt) + got, req.max_new - got))
        seen = []
        t0 = self.now()
        with self.span("decode"):
            finished = eng.decode_step(
                on_chunk=lambda steps, s: seen.append((steps, s)))
        t1 = self.now()
        delivered = 0
        for rid in self._live:
            req = self.rec.requests[rid]
            step = min(eng.decode_chunk, req.max_new - self._live[rid])
            self._live[rid] += step
            delivered += step
        steps, secs = seen[0]
        self.rec.chunks.append(Chunk(t0, t1, steps, secs, live, delivered))
        done = []
        for req, toks in finished:
            self.rec.done[req.rid] = t1
            self.rec.tokens[req.rid] = list(toks)
            del self._live[req.rid]
            done.append(req.rid)
        if len(self._live) != eng.live:
            raise RuntimeError("driver lost track of the engine's requests")
        return done

    # -- loops --------------------------------------------------------------------
    def run(self) -> Record:
        """The measured window; returns its record (not yet drained)."""
        self.rec.stats_open = dict(self.eng.stats)
        self._t0 = time.perf_counter()
        if self.traffic.open_loop:
            self._open_loop()
        else:
            self._closed_loop()
        self.rec.closed_at = self.now()
        self.rec.stats_close = dict(self.eng.stats)
        self.rec.window_chunks = len(self.rec.chunks)
        self.rec.window_waves = len(self.rec.waves)
        return self.rec

    def _open_loop(self) -> None:
        reqs = self.traffic.requests
        while True:
            now = self.now()
            if now >= self.seconds:
                return
            while self._next < len(reqs) and reqs[self._next].at_s <= now:
                self._send(reqs[self._next], reqs[self._next].at_s)
                self._next += 1
            if self._queue and self.eng.free_slots:
                self._admit()
            if self._live:
                self._decode()
            elif not self._queue:
                nxt = reqs[self._next].at_s if self._next < len(reqs) \
                    else self.seconds
                with self.span("wait"):
                    time.sleep(max(0.0, min(nxt, self.seconds) - self.now()))

    def _closed_loop(self) -> None:
        clients = int(self.traffic.spec["clients"])
        for _ in range(clients):
            self._send_next()
        while self.now() < self.seconds:
            if self._queue and self.eng.free_slots:
                self._admit()
            if not self._live:
                raise RuntimeError("closed loop with nothing live")
            for _ in self._decode():
                self._send_next()

    def _send_next(self) -> None:
        reqs = self.traffic.requests
        if self._next >= len(reqs):
            raise RuntimeError(f"closed loop used all {len(reqs)} requests "
                               "of the mix; raise its 'requests'")
        self._send(reqs[self._next], self.now())
        self._next += 1

    def drain(self) -> None:
        """Finish every open-loop request that was due in the window; in a
        closed loop, finish the requests in flight and send no more."""
        reqs = self.traffic.requests
        limit = self.now() + DRAIN_LIMIT_S
        if self.traffic.open_loop:
            while self._next < len(reqs) and \
                    reqs[self._next].at_s < self.seconds:
                self._send(reqs[self._next], reqs[self._next].at_s)
                self._next += 1
        while (self._queue or self._live) and self.now() < limit:
            if self._queue and self.eng.free_slots:
                self._admit()
            if self._live:
                self._decode()
        self.rec.drained_at = self.now()


def prefill_rows(wave: Wave, chunk: int):
    """Per chunk step of a wave: [(start, n, last)] for the rows with
    prompt tokens in that step."""
    steps = max(math.ceil((plen - start) / chunk) for start, plen in
                wave.rows)
    out = []
    for j in range(steps):
        rows = []
        for start, plen in wave.rows:
            s0 = start + j * chunk
            if s0 < plen:
                n = min(chunk, plen - s0)
                rows.append((s0, n, s0 + n == plen))
        out.append(rows)
    return out
