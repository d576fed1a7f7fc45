"""Arithmetic the metric readers in ``bench/metrics/`` share.

A reader is ``bench/metrics/<metric name>.py`` with ``read(run)``, which
returns the metric's value in its unit, or None where the run holds
nothing to read (the harness then leaves the metric out). ``run`` is the
harness's :class:`bench.run.Run`: the driver's record, the traffic, the
configuration's ``model`` sizes and architecture module, the device's
peaks and, in a traced run, the trace :class:`bench.trace.Summary`. The
operations and bytes of kernel calls and model steps come from the
architecture (``decode_attention_call``, ``prefill_attention_call``,
``decode_step_flops``, ``prefill_step_flops``; the dense decoder's are
:mod:`bench.counts`), so a new architecture's readers reuse the arithmetic
below with counts that fit its layers.

Kernel and program names are matched as they appear in the device trace.
"""
from __future__ import annotations

from . import counts
from .drive import prefill_rows
from .stats import percentile

DECODE_PROGRAM = r"decode_chunk_fn"
PREFILL_PROGRAM = r"prefill_chunk_fn"
# The decode program runs one Pallas kernel (paged decode attention), the
# prefill program one (paged prefill attention); neither has a name of its
# own in the trace, so each is known by the program it runs in.
DECODE_KERNEL = r"^tpu_custom_call\[.*decode_chunk_fn\]$"
PREFILL_KERNEL = r"^tpu_custom_call\[.*prefill_chunk_fn\]$"


def due_in_window(run) -> list:
    rec = run.rec
    return [r for r in rec.due if rec.due[r] < rec.seconds]


def ttft_ms(run) -> list:
    """Per request due in the window: first token on the host minus the
    time it was due. A request that never got one counts at the drain's
    end, so it lands in the tail."""
    rec = run.rec
    end = max(rec.drained_at, rec.closed_at)
    return [(rec.first.get(r, end) - rec.due[r]) * 1e3
            for r in due_in_window(run)]


def tpot_ms(run) -> list:
    """Per finished request due in the window with two or more tokens:
    (completion - first token) / (tokens - 1)."""
    rec = run.rec
    out = []
    for r in due_in_window(run):
        n = rec.requests[r].max_new
        if r in rec.done and n > 1:
            out.append((rec.done[r] - rec.first[r]) * 1e3 / (n - 1))
    return out


def p95(xs):
    return percentile(xs, 95) if xs else None


def p80(xs):
    return percentile(xs, 80) if xs else None


def window_chunks(run) -> list:
    return run.rec.chunks[:run.rec.window_chunks]


def window_waves(run) -> list:
    return run.rec.waves[:run.rec.window_waves]


def output_tok_s(run):
    """Tokens delivered by the decode chunks that ended inside the window,
    over the window's seconds."""
    rec = run.rec
    toks = sum(c.delivered for c in window_chunks(run) if c.t1 <= rec.seconds)
    return toks / rec.seconds if toks else None


def decode_step_ms(run):
    """Seconds of every decode chunk of the window over their steps."""
    chunks = window_chunks(run)
    steps = sum(c.steps for c in chunks)
    return sum(c.seconds for c in chunks) * 1e3 / steps if steps else None


def admit_share(run):
    rec = run.rec
    t = sum(t1 - t0 for name, t0, t1 in rec.spans
            if name == "admit" and t0 < rec.closed_at)
    return 100.0 * t / rec.closed_at


def prefix_hit_rate(run):
    a, b = run.rec.stats_open, run.rec.stats_close
    cached = b["cached_tokens"] - a["cached_tokens"]
    new = b["prefill_tokens"] - a["prefill_tokens"]
    return 100.0 * cached / (cached + new) if cached + new else None


def decode_work(run):
    """(kernel Work, model flops) of every decode step in the window."""
    m, peaks, arch = run.m, run.peaks, run.arch
    work, flops = counts.Work(), 0.0
    for c in window_chunks(run):
        for i in range(c.steps):
            lengths = [pos + i + 1 for pos, budget in c.live if i < budget]
            if not lengths:
                continue
            f, b = arch.decode_attention_call(m, lengths)
            work.add(f, b, peaks, calls=m["num_layers"])
            flops += arch.decode_step_flops(m, lengths)
    return work, flops


def prefill_work(run):
    """(kernel Work, model flops) of every prefill step in the window. A
    wave whose rows (from the prefix found before it) hold more new tokens
    than the engine counted is scaled down to the engine's count."""
    m, peaks, arch = run.m, run.peaks, run.arch
    work, flops = counts.Work(), 0.0
    for w in window_waves(run):
        steps = prefill_rows(w, run.prefill_chunk)
        est = sum(n for rows in steps for _, n, _ in rows)
        scale = min(1.0, w.prefill_tokens / est) if est else 0.0
        wave = counts.Work()
        for rows in steps:
            f, b = arch.prefill_attention_call(m, [(s, n) for s, n, _ in
                                                   rows])
            wave.add(f, b, peaks, calls=m["num_layers"])
            flops += scale * arch.prefill_step_flops(m, rows)
        work.flops += scale * wave.flops
        work.bytes += scale * wave.bytes
        work.bound_s += scale * wave.bound_s
        work.memory_bound_calls += wave.memory_bound_calls
        work.calls += wave.calls
    return work, flops


def roofline(work, seconds):
    """Share (%) of the roofline: least time over measured kernel time."""
    if seconds and work.calls:
        return 100.0 * work.bound_s / seconds
    return None


def mfu(flops, seconds, peaks):
    """Share (%) of the peak: model operations over measured time."""
    if seconds and flops:
        return 100.0 * flops / (seconds * peaks["bf16_flops_per_s"])
    return None


def device_idle(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
