"""CPU checks of the readers of the engine's phase counters: each reads
its hand value from chosen counter snapshots and nothing where its
denominator is 0 or the program has no such counter, and on a tiny run
the counters agree with the load loop's own record of the window."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench import drive, model, run, traffic
from bench.tests import tiny

BIG_SEED = 2 ** 31 + 8191

OPEN = {"admitted": 10, "queue_wait_seconds": 0.5, "admit_waves": 5,
        "admit_plan_seconds": 0.1, "admit_dispatch_seconds": 0.2,
        "admit_sync_seconds": 0.4, "prefill_tokens": 100,
        "prefill_slot_tokens": 200, "decode_chunks": 2,
        "decode_slot_steps": 64, "decode_live_steps": 60,
        "decode_prep_seconds": 0.01, "decode_dispatch_seconds": 0.02,
        "decode_sync_seconds": 1.0, "decode_post_seconds": 0.03}
CLOSE = dict(OPEN, admitted=14, queue_wait_seconds=2.5, admit_waves=7,
             admit_plan_seconds=0.13, admit_dispatch_seconds=0.27,
             admit_sync_seconds=9.0, prefill_tokens=400,
             prefill_slot_tokens=600, decode_chunks=4,
             decode_slot_steps=192, decode_live_steps=156,
             decode_prep_seconds=0.013, decode_dispatch_seconds=0.024,
             decode_sync_seconds=5.0, decode_post_seconds=0.033)
# The counters of a program that predates them.
OLD = {"admitted": 14, "prefill_tokens": 400, "cached_tokens": 0,
       "admit_seconds": 3.0}

EXPECTED = {
    "queue_wait_ms.open": 2.0 / 4 * 1e3,
    "admit_host_ms.open": (0.03 + 0.07) / 2 * 1e3,
    "prefill_fill.open": 100.0 * 300 / 400,
    "decode_occupancy.batch": 100.0 * 96 / 128,
    "decode_occupancy.open": 100.0 * 96 / 128,
    "decode_host_ms.batch": (0.003 + 0.004 + 0.003) / 2 * 1e3,
    "decode_host_ms.open": (0.003 + 0.004 + 0.003) / 2 * 1e3,
}


def _run(stats_open, stats_close):
    return SimpleNamespace(rec=SimpleNamespace(stats_open=stats_open,
                                               stats_close=stats_close))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_its_hand_value(name):
    read = run.load_reader(name)
    assert read(_run(OPEN, CLOSE)) == pytest.approx(EXPECTED[name])
    assert read(_run(OPEN, dict(OPEN))) is None
    assert read(_run(OLD, dict(OLD))) is None


def test_each_counter_metric_is_declared_with_its_cells():
    bench = run.load_benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    cells = {c["name"] for c in bench["workloads"]}
    for name in EXPECTED:
        m = declared[name]
        assert m["source"] == "program_counter"
        assert set(m["workloads"]) <= cells


@pytest.mark.parametrize("mix", ["yi6b.decode_batch", "yi6b.chat"])
def test_counters_agree_with_the_window_record(mix):
    """A tiny run on the CPU: the window's live decode steps are exactly
    the tokens its chunks delivered (plain decode), and its chunks, waves
    and prefilled tokens are those the load loop recorded."""
    conf = tiny.conf()
    cfg = model.model_config(conf)
    over = {"rate_per_s": 20.0} if mix == "yi6b.chat" else {}
    t = traffic.generate(tiny.spec(mix, **over), BIG_SEED, 1.0,
                         cfg.vocab_size)
    eng = model.build_engine(conf, cfg, model.draw_params(conf, cfg,
                                                          BIG_SEED))
    rec = drive.Driver(eng, t, 1.0).run()
    a, b = rec.stats_open, rec.stats_close
    delta = {k: b[k] - a[k] for k in b}
    chunks, waves = rec.chunks[:rec.window_chunks], \
        rec.waves[:rec.window_waves]
    assert chunks and waves
    assert delta["decode_live_steps"] == sum(c.delivered for c in chunks)
    assert delta["decode_chunks"] == len(chunks)
    assert delta["decode_slot_steps"] == \
        len(chunks) * eng.max_slots * eng.decode_chunk
    assert delta["admit_waves"] == len(waves)
    assert delta["prefill_tokens"] == sum(w.prefill_tokens for w in waves)
    assert delta["prefill_tokens"] <= delta["prefill_slot_tokens"]
