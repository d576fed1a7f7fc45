"""The unified observability plane: MetricsRegistry (counters / gauges /
fixed-bucket histograms, labels, collectors), lossless Prometheus text
exposition (parse_exposition round-trips to snapshot equality),
RegistryDict write-through compatibility views (positive-delta counter
semantics across engine stat resets), the open-loop traffic generator,
and gateway integration: one registry serves gateway + engines + router
with per-tenant labels while telemetry streams into a StateStore."""
from dataclasses import replace

import jax
import pytest

from repro.configs import get_reduced_config
from repro.core.clock import VirtualClock
from repro.core.elastic import ScalingPolicy
from repro.core.scheduler import ShardedStateStore, StateStore
from repro.core.security import PolicyEngine, provision_tenant
from repro.models import get_family
from repro.models.params import init_params
from repro.serve import (ContinuousBatchingEngine, DeadlineCostPolicy,
                         KottaServeGateway, MetricsRegistry, RegistryDict,
                         ServiceModel, TrafficConfig, generate_trace,
                         parse_exposition, run_open_loop)
from repro.serve.loadgen import offered_load

MAX_LEN = 48
SLOTS = 4


@pytest.fixture(scope="module")
def model():
    cfg = get_reduced_config("yi-6b").replace(dtype="float32", page_size=8)
    fam = get_family(cfg)
    params = init_params(fam.layout(cfg), jax.random.PRNGKey(0),
                         cfg.param_dtype)
    return cfg, params


# ---------------------------------------------------------------------------
# MetricsRegistry: families, labels, validation
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("jobs_total", "jobs", ("tenant",))
    c.inc(tenant="alice")
    c.inc(2, tenant="alice")
    c.inc(tenant="bob")
    assert reg.value("jobs_total", tenant="alice") == 3
    assert reg.value("jobs_total", tenant="bob") == 1
    assert reg.value("jobs_total", tenant="nobody") == 0.0

    g = reg.gauge("depth")
    g.set(7)
    g.set(3)
    assert reg.value("depth") == 3

    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = reg.snapshot()["families"]["lat_seconds"]["samples"][0]
    # Integral bounds render bare ("1", not "1.0") in le= keys.
    assert snap["buckets"] == {"0.1": 1.0, "1": 2.0, "+Inf": 3.0}
    assert snap["sum"] == pytest.approx(5.55)
    assert snap["count"] == 3


def test_registration_is_idempotent_but_conflicts_raise():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "x", ("t",))
    assert reg.counter("x_total", "x", ("t",)) is a
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("x_total", "x", ("other",))


def test_counters_reject_negative_and_histograms_reject_value_read():
    reg = MetricsRegistry()
    c = reg.counter("n_total")
    with pytest.raises(ValueError, match="must be >= 0"):
        c.inc(-1)
    h = reg.histogram("h_seconds", buckets=(1.0,))
    with pytest.raises(TypeError):
        h.value()


def test_label_validation():
    reg = MetricsRegistry()
    c = reg.counter("l_total", "", ("tenant",))
    with pytest.raises(ValueError):
        c.inc(region="us")          # wrong label name
    with pytest.raises(ValueError):
        c.inc()                     # missing label


def test_collector_refreshes_and_retires_gauge_series():
    reg = MetricsRegistry()
    g = reg.gauge("occ", "", ("replica",))
    live = {"r0": 0.5, "r1": 1.0}

    def collect():
        g.clear()
        for r, v in live.items():
            g.set(v, replica=r)

    reg.register_collector(collect)
    assert reg.value("occ", replica="r1") == 0  # not collected yet
    snap = reg.snapshot()                       # snapshot() collects
    assert len(snap["families"]["occ"]["samples"]) == 2
    del live["r1"]                              # replica retires
    snap = reg.snapshot()
    assert [s["labels"] for s in snap["families"]["occ"]["samples"]] == [
        {"replica": "r0"}]


# ---------------------------------------------------------------------------
# Exposition: valid Prometheus text, lossless round-trip
# ---------------------------------------------------------------------------

def _populated_registry():
    clock = VirtualClock()
    clock.advance(12.5)
    reg = MetricsRegistry(clock=clock)
    c = reg.counter("kotta_requests_total", "Requests", ("tenant", "class"))
    c.inc(3, tenant="alice", **{"class": "interactive"})
    c.inc(1, tenant='quo"te\\back\nline', **{"class": "batch"})
    reg.gauge("kotta_burn", "Burn").set(1.75)
    h = reg.histogram("kotta_ttft_seconds", "TTFT", buckets=(0.5, 2.0),
                      labelnames=("tenant",))
    for v in (0.1, 1.0, 9.0):
        h.observe(v, tenant="alice")
    return reg


def test_exposition_format():
    text = _populated_registry().expose()
    assert "# TYPE kotta_requests_total counter" in text
    assert ('kotta_requests_total{tenant="alice",class="interactive"} 3'
            in text)
    # Label escaping: backslash, quote, newline.
    assert r'tenant="quo\"te\\back\nline"' in text
    assert "# TYPE kotta_ttft_seconds histogram" in text
    assert 'kotta_ttft_seconds_bucket{tenant="alice",le="+Inf"} 3' in text
    assert 'kotta_ttft_seconds_count{tenant="alice"} 3' in text


def test_parse_exposition_round_trips_snapshot_exactly():
    reg = _populated_registry()
    assert parse_exposition(reg.expose())["families"] == \
        reg.snapshot()["families"]


def test_round_trip_is_lossless_for_awkward_floats():
    reg = MetricsRegistry()
    g = reg.gauge("g", "", ("k",))
    for i, v in enumerate((0.1, 1e-12, 1e300, 123456789.000001,
                           float("inf"))):
        g.set(v, k=str(i))
    assert parse_exposition(reg.expose())["families"] == \
        reg.snapshot()["families"]


# ---------------------------------------------------------------------------
# RegistryDict: the dict-compat layer over registry series
# ---------------------------------------------------------------------------

def test_registry_dict_counter_delta_semantics():
    reg = MetricsRegistry()
    c = reg.counter("evt_total", "", ("engine",))
    rd = RegistryDict()
    rd.bind("evt", c, initial=5, engine="e0")
    assert rd["evt"] == 5
    assert reg.value("evt_total", engine="e0") == 5
    rd["evt"] += 3
    assert reg.value("evt_total", engine="e0") == 8
    # A stat reset zeroes the dict view; the Prometheus counter is
    # monotonic and keeps its value (counter-reset semantics).
    rd["evt"] = 0
    assert rd["evt"] == 0
    assert reg.value("evt_total", engine="e0") == 8
    rd["evt"] += 2
    assert reg.value("evt_total", engine="e0") == 10


def test_registry_dict_gauge_and_unbound_keys():
    reg = MetricsRegistry()
    g = reg.gauge("level")
    rd = RegistryDict()
    rd.bind("level", g, initial=4)
    rd.bind("scratch", None, initial=0)      # local-only key
    rd["level"] = 2                          # gauges set outright
    assert reg.value("level") == 2
    rd["scratch"] = 99
    assert rd["scratch"] == 99
    assert dict(rd) == {"level": 2, "scratch": 99}
    assert len(rd) == 2


# ---------------------------------------------------------------------------
# Open-loop traffic generation
# ---------------------------------------------------------------------------

def test_trace_is_deterministic_and_shaped():
    cfg = TrafficConfig(duration_s=20.0, base_rate_rps=10.0, tenants=3,
                        diurnal_amplitude=0.5, diurnal_period_s=20.0,
                        seed=5)
    a, b = generate_trace(cfg), generate_trace(cfg)
    assert a == b                            # byte-identical across runs
    assert generate_trace(replace(cfg, seed=6)) != a
    assert all(0 <= x.tenant_idx < 3 for x in a)
    assert all(a[i].at_s <= a[i + 1].at_s for i in range(len(a) - 1))
    assert 5.0 < offered_load(a, cfg) < 20.0
    # Shared prefix: same-tenant arrivals share their first 16 tokens.
    by_tenant = {}
    for x in a:
        by_tenant.setdefault(x.tenant_idx, []).append(x.prompt[:16])
    for prompts in by_tenant.values():
        assert len(set(prompts)) == 1
    # Zipf skew: the heaviest user outweighs the median user.
    users = [x.user for x in a]
    assert users.count(0) > 1
    # Both classes present, deadlines matched to class.
    assert {x.priority for x in a} == {0, 1}
    for x in a:
        assert x.deadline_s == (cfg.interactive_deadline_s if x.priority == 0
                                else cfg.batch_deadline_s)


def test_trace_config_validation():
    with pytest.raises(ValueError, match="amplitude"):
        generate_trace(TrafficConfig(diurnal_amplitude=1.5))
    with pytest.raises(ValueError, match="zipf"):
        generate_trace(TrafficConfig(zipf_alpha=1.0))


# ---------------------------------------------------------------------------
# ServiceModel calibration
# ---------------------------------------------------------------------------

def test_service_model_calibration_math():
    svc = ServiceModel(prefill_tok_per_s=2048.0, decode_step_s=0.05)
    assumed = svc.assumed_req_per_s(20, 8, 4)
    assert assumed == pytest.approx(4 / (20 / 2048.0 + 8 * 0.05))
    cal = svc.calibrated(assumed / 2, prompt_len=20, max_new=8, slots=4)
    assert cal.overhead == pytest.approx(2.0)
    assert cal.service_s(20, 8) == pytest.approx(2 * svc.service_s(20, 8))
    # Billing inputs are untouched; overhead never "speeds up" the model.
    assert cal.decode_step_s == svc.decode_step_s
    fast = svc.calibrated(assumed * 10, prompt_len=20, max_new=8, slots=4)
    assert fast.overhead == 1.0
    with pytest.raises(ValueError):
        svc.calibrated(0.0, prompt_len=20, max_new=8, slots=4)


# ---------------------------------------------------------------------------
# Gateway integration: one registry, per-tenant labels, telemetry stream
# ---------------------------------------------------------------------------

def _security(n):
    sec = PolicyEngine(clock=VirtualClock())
    tokens = [provision_tenant(sec, f"tenant{i}", f"pw-{i}",
                               data_zones=("public",))
              for i in range(n)]
    return sec, tokens


def _gateway(model, sec, **kw):
    cfg, params = model
    svc = ServiceModel(decode_step_s=0.05)
    kw.setdefault("admission", DeadlineCostPolicy(model=svc))
    kw.setdefault("scaling", ScalingPolicy.none(1, market="on_demand"))
    kw.setdefault("service_model", svc)
    kw.setdefault("idle_tick_s", 0.05)
    return KottaServeGateway(
        lambda: ContinuousBatchingEngine(cfg, params, max_len=MAX_LEN,
                                         max_slots=SLOTS, prefill_chunk=8,
                                         decode_chunk=4),
        sec, **kw)


def _small_trace(cfg, tenants, **kw):
    kw.setdefault("duration_s", 6.0)
    kw.setdefault("base_rate_rps", 4.0)
    return TrafficConfig(tenants=tenants, vocab_size=cfg.vocab_size,
                         prefix_tokens=16, interactive_max_new=4,
                         batch_max_new=4, seed=3, **kw)


@pytest.fixture(scope="module")
def served(model):
    """One open-loop run shared by the integration assertions below."""
    cfg, _ = model
    sec, tokens = _security(3)
    store = StateStore(clock=sec.clock, write_capacity=200.0)
    gw = _gateway(model, sec, telemetry_store=store, telemetry_flush_s=1.0)
    trace = generate_trace(_small_trace(cfg, 3))
    rounds = run_open_loop(gw, tokens, trace)
    gw.flush_telemetry()
    return gw, store, trace, rounds


def test_one_registry_serves_gateway_engine_and_router(served):
    gw, _, trace, rounds = served
    reg = gw.registry
    fams = set(reg.families())
    assert {"kotta_requests_total", "kotta_request_ttft_seconds",
            "kotta_engine_admitted_total", "kotta_routing_decisions_total",
            "kotta_gateway_rounds_total", "kotta_slo_burn_rate",
            "kotta_engine_decode_sync_seconds_total",
            "kotta_engine_queue_wait_seconds_total"} <= fams
    # Per-tenant labels: every tenant that submitted has its own series.
    seen = {t for t in ("tenant0", "tenant1", "tenant2")
            if reg.value("kotta_requests_total", tenant=t,
                         **{"class": "interactive"})
            + reg.value("kotta_requests_total", tenant=t,
                        **{"class": "batch"}) > 0}
    assert seen == {f"tenant{a.tenant_idx}" for a in trace}
    # The registry's counters agree with the legacy dict views.
    assert reg.value("kotta_gateway_rounds_total") == gw.stats["rounds"] \
        == rounds
    assert reg.value("kotta_engine_admitted_total", engine="e0") \
        == gw.metrics()["completed"] == len(trace)
    # The engine's phase counters reach the registry as it runs.
    assert reg.value("kotta_engine_decode_sync_seconds_total",
                     engine="e0") > 0
    assert reg.value("kotta_engine_queue_wait_seconds_total",
                     engine="e0") > 0


def test_gateway_exposition_round_trips(served):
    gw, _, _, _ = served
    reg = gw.registry
    assert parse_exposition(reg.expose())["families"] == \
        reg.snapshot()["families"]


def test_latency_histograms_observe_every_completion(served):
    gw, _, trace, _ = served
    snap = gw.registry.snapshot()["families"]
    for fam in ("kotta_request_ttft_seconds", "kotta_request_tpot_seconds",
                "kotta_request_queue_wait_seconds"):
        assert sum(s["count"] for s in snap[fam]["samples"]) == len(trace)
    cost = sum(s["value"]
               for s in snap["kotta_tenant_cost_usd_total"]["samples"])
    assert cost > 0


def test_telemetry_stream_lands_in_statestore(served):
    gw, store, trace, _ = served
    jobs = store.scan("servejob/")
    assert len(jobs) == len(trace)
    assert all(j["status"] == "done" for j in jobs.values())
    assert {j["tenant"] for j in jobs.values()} == \
        {f"tenant{a.tenant_idx}" for a in trace}
    audits = store.scan("audit/")
    assert len(audits) == len(gw.security.audit.records())
    snaps = store.scan("metrics/")
    assert len(snaps) == gw.stats["telemetry_flushes"] + 1  # + end drain
    # Snapshots are full registry states, orderable by key.
    last = snaps[max(snaps)]
    assert "kotta_gateway_rounds_total" in last["families"]
    assert gw.stats["telemetry_writes"] == store.write_count


def test_throttled_store_counts_and_sharding_recovers(model):
    cfg, _ = model

    def run(store_factory):
        sec, tokens = _security(2)
        store = store_factory(sec.clock)
        gw = _gateway(model, sec, telemetry_store=store,
                      telemetry_flush_s=0.5)
        trace = generate_trace(_small_trace(cfg, 2, base_rate_rps=8.0))
        run_open_loop(gw, tokens, trace)
        gw.flush_telemetry()
        assert len(store.scan("servejob/")) == len(trace)  # drained anyway
        return gw.stats["statestore_throttled"], store.throttled_writes

    gw_thr, st_thr = run(lambda c: StateStore(clock=c, write_capacity=4.0))
    assert st_thr > 0 and gw_thr == st_thr
    gw_thr4, st_thr4 = run(
        lambda c: ShardedStateStore(4, clock=c, write_capacity=4.0))
    assert st_thr4 < st_thr


def test_metrics_dict_compat_keys_survive(served):
    gw, _, _, _ = served
    m = gw.metrics()
    for key in ("completed", "shed", "sla_rate", "deadline_hit_rate",
                "slo_burn_rate", "telemetry_flushes", "telemetry_writes",
                "telemetry_dropped", "statestore_throttled", "routing",
                "per_replica"):
        assert key in m
    assert m["slo_burn_rate"] == 0.0         # nothing missed in this run
