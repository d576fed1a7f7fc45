"""CPU checks of the benchmark's arithmetic: trace reduction, operation and
byte counts, percentiles, and traffic generation."""
from __future__ import annotations

import json
import statistics

import pytest

from bench import counts, readers, stats, trace, traffic
from bench.trace import Summary, op_name

YI = json.load(open(traffic.TRAFFIC_DIR.parent / "configs/yi-6b.json"))["model"]
LM2 = json.load(open(traffic.TRAFFIC_DIR.parent /
                     "configs/internlm2-1.8b.json"))["model"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# -- trace reduction ----------------------------------------------------------

def _summary():
    # Window 0..100 ns. Busy: [10, 30] and [25, 40] merge to [10, 40];
    # [60, 70]; [95, 120] is clipped to [95, 100]. Idle: 10 + 20 + 25 = 55.
    ops = [[("flash_decode", 10, 30), ("fusion", 25, 40),
            ("flash_decode", 60, 70), ("fusion", 95, 120)]]
    host = [("bench.window", 0, 100), ("bench.admit", 0, 12),
            ("bench.decode", 12, 50), ("bench.wait", 50, 90)]
    return Summary(window=(0, 100), ops=ops,
                   modules=[[("jit_decode_chunk_fn(1)", 10, 72)]], host=host)


def test_busy_union_and_idle_share():
    s = _summary()
    assert s.busy_intervals() == [[10, 40], [60, 70], [95, 100]]
    assert s.busy_s == pytest.approx(45e-9)
    assert s.window_s == pytest.approx(100e-9)


def test_kernel_and_program_seconds():
    s = _summary()
    assert s.op_seconds("flash_decode") == pytest.approx(30e-9)
    assert s.module_seconds("decode_chunk_fn") == pytest.approx(62e-9)
    assert s.op_seconds("no_such_kernel") == 0.0
    assert s.top_ops(1) == [["flash_decode", pytest.approx(30e-9)]]


def test_operation_names_from_hlo_text():
    assert op_name("%fusion.12 = bf16[16,11008]{1,0} fusion(bf16[32] %a), "
                   "kind=kLoop") == ("fusion", False)
    assert op_name("%while.51 = (s32[], bf16[2]) while((s32[]) %t), "
                   "condition=%c") == ("while", True)
    assert op_name('%closed_call.29 = (f32[16,4]) custom-call(s32[16] %x),'
                   ' custom_call_target="tpu_custom_call", frontend_'
                   'attributes={kernel_metadata={}}') == ("tpu_custom_call",
                                                          False)
    assert op_name('%closed_call.3 = f32[4] custom-call(s32[16] %x),'
                   ' custom_call_target="tpu_custom_call", backend_config='
                   '{"kernel_name": "flash_decode"}') == ("flash_decode",
                                                         False)
    assert op_name('%custom-call.10 = bf16[2] custom-call(), '
                   'custom_call_target="AllocateBuffer"') == ("custom-call",
                                                             False)
    assert op_name("%copy-start.7 = (f32[64]) copy-start(f32[64] %c)") == \
        ("copy-start", False)
    assert op_name("jit_decode_chunk_fn(16585827974558114026)") == \
        ("jit_decode_chunk_fn", False)


def test_unnamed_kernels_are_named_for_their_program():
    modules = [("jit_prefill_chunk_fn", 0, 50), ("jit_decode_chunk_fn", 60,
                                                  200)]
    ops = [("tpu_custom_call", 10, 20), ("fusion", 20, 30),
           ("tpu_custom_call", 70, 90), ("tpu_custom_call", 250, 260)]
    assert trace.qualify_kernels(ops, modules) == [
        ("tpu_custom_call[jit_prefill_chunk_fn]", 10, 20),
        ("fusion", 20, 30),
        ("tpu_custom_call[jit_decode_chunk_fn]", 70, 90),
        ("tpu_custom_call", 250, 260)]
    s = Summary(window=(0, 300), ops=[trace.qualify_kernels(ops, modules)],
                modules=[modules])
    assert s.op_seconds(readers.DECODE_KERNEL) == pytest.approx(20e-9)
    assert s.op_seconds(readers.PREFILL_KERNEL) == pytest.approx(10e-9)
    assert s.module_seconds(readers.DECODE_PROGRAM) == pytest.approx(140e-9)


def test_idle_gaps_named_by_host_span():
    s = _summary()
    gaps = s.idle_gaps(10)
    # [70, 95] mostly under wait, [40, 60] straddles decode (10) and wait
    # (10), [0, 10] under admit.
    assert [g[0] for g in gaps] == ["bench.wait", "bench.decode",
                                    "bench.admit"]
    assert [g[1] for g in gaps] == pytest.approx([25e-9, 20e-9, 10e-9])


# -- operations and bytes ---------------------------------------------------------

@pytest.mark.parametrize("m,per_layer", [
    # q, k, v, o projections and the three MLP matrices, by hand.
    (YI, 4096 * 4096 + 2 * 4096 * 512 + 4096 * 4096 + 3 * 4096 * 11008),
    (LM2, 2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048 + 3 * 2048 * 8192),
])
def test_layer_matmul_params(m, per_layer):
    assert counts.layer_matmul_params(m) == per_layer


@pytest.mark.parametrize("m", [YI, LM2], ids=["yi-6b", "internlm2-1.8b"])
def test_decode_attention_call(m):
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    flops, nbytes = counts.decode_attention_call(m, [100, 300])
    # QK and PV: 2 flops per multiply-add, 2 products, per head and key.
    assert flops == 2 * 2 * h * hd * 400
    # K and V rows of both slots in bf16, plus q in and o out per slot.
    assert nbytes == 2 * (2 * kv * hd * 400) + 2 * (2 * h * hd * 2)


@pytest.mark.parametrize("m", [YI, LM2], ids=["yi-6b", "internlm2-1.8b"])
def test_prefill_attention_call(m):
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    flops, nbytes = counts.prefill_attention_call(m, [(1024, 32), (0, 5)])
    keys = sum(range(1025, 1057)) + sum(range(1, 6))
    assert flops == 4 * h * hd * keys
    assert nbytes == 2 * (2 * kv * hd * (1056 + 5) + 2 * h * hd * 37)


@pytest.mark.parametrize("m", [YI, LM2], ids=["yi-6b", "internlm2-1.8b"])
def test_step_flops(m):
    per_layer = counts.layer_matmul_params(m)
    head = 2 * m["d_model"] * m["vocab_size"]
    attn = 4 * m["num_heads"] * m["head_dim"]
    got = counts.decode_step_flops(m, [10, 20])
    assert got == m["num_layers"] * (2 * per_layer * 2 + attn * 30) + \
        2 * head
    got = counts.prefill_step_flops(m, [(0, 4, True), (8, 2, False)])
    want = m["num_layers"] * (2 * per_layer * 6 + attn * (1 + 2 + 3 + 4 +
                                                          9 + 10)) + head
    assert got == want


def test_roofline_bound_picks_the_larger_roof():
    w = counts.Work()
    w.add(197e12, 1.0, PEAKS)              # a second of operations
    w.add(1.0, 819e9 * 2, PEAKS, calls=2)   # two seconds of bytes, twice
    assert w.bound_s == pytest.approx(5.0)
    assert w.bound == "memory"
    assert w.calls == 3


# -- percentiles ---------------------------------------------------------------------

def test_percentile_interpolates():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([1, 3], 50) == 2.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)


@pytest.mark.parametrize("n,beyond", [(200, 10), (100, 5), (20, 1), (0, 0)])
def test_samples_beyond_p95(n, beyond):
    assert stats.samples_beyond(n, 95) == beyond


def test_spread_uses_statistics_quartiles():
    xs = [10.0, 11.0, 12.0, 13.0, 14.0, 100.0]
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / q2)


# -- traffic -------------------------------------------------------------------------------

MIXES = ["yi6b.decode_batch", "yi6b.chat", "internlm2.shared_prefix",
         "internlm2.long_decode"]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    spec = traffic.load_spec(mix)
    big = 2 ** 31 + 977
    a = traffic.generate(spec, big, 30.0, 64000)
    b = traffic.generate(spec, big, 30.0, 64000)
    assert a.requests == b.requests and a.docs == b.docs
    c = traffic.generate(spec, big + 1, 30.0, 64000)
    assert c.requests != a.requests
    # Another seed: the same sizes at the same times, in another order.
    key = lambda t: sorted((len(r.prompt), r.max_new, r.tenant)
                           for r in t.requests)
    assert key(c) == key(a)
    assert [r.at_s for r in c.requests] == [r.at_s for r in a.requests]


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_inside_their_clips(mix):
    spec = traffic.load_spec(mix)
    t = traffic.generate(spec, 7, 30.0, 64000)
    doc = spec["shared"]["tokens"] if "shared" in spec else 0
    for r in t.requests:
        assert spec["prompt"]["min"] <= len(r.prompt) - doc <= \
            spec["prompt"]["max"]
        assert spec["output"]["min"] <= r.max_new <= spec["output"]["max"]
        assert all(0 <= x < 64000 for x in r.prompt)
        if doc:
            assert r.prompt[:doc] == t.docs[r.doc]
    assert max(len(r.prompt) + r.max_new for r in t.requests) <= \
        traffic.longest_request(spec)


def test_open_loop_arrivals_follow_the_rate():
    spec = traffic.load_spec("internlm2.shared_prefix")
    times = traffic.arrival_times(spec, 200.0)
    assert times.min() >= 0 and times.max() < 200.0
    assert list(times) == sorted(times)
    assert len(times) / 200.0 == pytest.approx(spec["rate_per_s"], rel=0.1)


def test_bursts_thin_to_the_base_rate_between_bursts():
    spec = {"loop": "open", "rate_per_s": 5.0, "mix_seed": 3,
            "bursts": {"every_s": 10.0, "for_s": 2.0, "factor": 4.0}}
    times = traffic.arrival_times(spec, 1000.0)
    in_burst = sum(1 for t in times if t % 10.0 < 2.0)
    assert in_burst / 200.0 == pytest.approx(20.0, rel=0.1)
    assert (len(times) - in_burst) / 800.0 == pytest.approx(5.0, rel=0.1)
