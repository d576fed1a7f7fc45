"""One run of one benchmark cell on the chip.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json`` and finds by name its configuration
(``bench/configs/<config>.json``), the architecture that configuration
names (``bench/architectures/<architecture>.py``, ``dense_gqa`` where it
names none: the reference, the weight rules and the operation counts of
that kind of model), its traffic mix (``bench/traffic/<traffic>.json``),
the limits of its check (``bench/checks/<cell>.json``) and one reader per
metric (``bench/metrics/<metric>.py``). A cell of a new architecture adds
its configuration file, its architecture module, its traffic, its check
limits and any metric readers of its own; adding a cell adds files and
edits none. An architecture that is named but missing, or lacks one of the
names the harness takes from it, fails at set-up.

Set-up, from process start to the window's opening: the fit check, the
weights drawn on the device from the seed, the engine and its pool, and a
warm-up of every program the traffic runs (the compile cache lives in
``<checkout>/.jax_cache``). The window then drives the engine for
``--seconds``; ``--trace 1`` records a profiler trace of it and reports the
per-layer metrics instead of the end-to-end ones. After the window, open
loops are drained, device memory is read, the program's state is freed,
and a sample of the finished requests is compared with the reference
(:mod:`bench.check`).

The last line of standard output is one JSON object; the numbers compared
and their limits are the last lines of standard error and the last key of
that object. Without a TPU, or with fewer chips than the cell asks for,
the run exits with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH_DIR = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


@dataclass
class Run:
    """What a metric reader is given (see bench/readers.py)."""
    cell: dict
    conf: dict
    m: dict
    arch: object                 # the configuration's architecture module
    traffic: object
    rec: object
    peaks: dict
    trace: object
    setup_s: float
    prefill_chunk: int


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def metrics_for(bench: dict, cell: dict, trace: bool) -> list:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer
    ones."""
    name = cell["name"]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in reported
                             else [])]


def load_reader(name: str, directory: Path = BENCH_DIR / "metrics"):
    path = directory / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       "bench/peaks.json")
    return table[kind]


def _listen():
    """Counts of programs lowered and compiled since the last reset."""
    import jax
    seen = {LOWERING_EVENT: 0, COMPILE_EVENT: 0}

    def on(event, duration, **_):
        if event in seen:
            seen[event] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    return seen


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True,
             conf: dict | None = None, spec: dict | None = None,
             limits: dict | None = None, arch=None,
             t_start: float = T_PROCESS, cache_dir: Path | None = None,
             breakage=None) -> dict:
    """One run of ``cell_name``; returns the result object. Compiled
    programs are kept in ``cache_dir``. ``conf``, ``spec``, ``limits`` and
    ``arch`` replace the files found by name, and ``breakage(engine)`` may
    break the engine after set-up (tests)."""
    import jax
    from . import check, model, trace as tracing
    from .drive import Driver
    from .traffic import generate, load_spec

    cell = next((c for c in bench["workloads"] if c["name"] == cell_name),
                None)
    if cell is None:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json")
    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu" or
                         len(devices) < cell["chips"]):
        raise NoChip(f"need {cell['chips']} TPU chip(s), JAX found "
                     f"{len(devices)} {dev.platform} device(s)")
    if cache_dir is not None:
        cache_dir.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = _listen()

    conf = conf or model.load_config(cell["config"])
    arch = arch or model.architecture(conf)
    spec = spec or load_spec(cell["traffic"])
    limits = limits or check.load_limits(cell_name)
    peaks = load_peaks(dev.device_kind) if require_chip else \
        {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    cfg = model.model_config(conf)
    slots, max_len = cfg.max_decode_slots, conf["serving"]["max_len"]
    limit_bytes = (dev.memory_stats() or {}).get("bytes_limit")
    fit = model.fit_check(cfg, slots, max_len, limit_bytes)
    log(f"info fit: decode step needs {fit['need']} B (temp {fit['temp']}) "
        f"of {limit_bytes} B")
    params = model.draw_params(conf, cfg, seed, arch)
    engine = model.build_engine(conf, cfg, params)
    del params
    traffic = generate(spec, seed, seconds, cfg.vocab_size)
    warm = model.warm_up(engine, traffic, seed)
    if breakage is not None:
        breakage(engine)
    driver = Driver(engine, traffic, seconds, annotate=trace)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t_start
    lowered0 = dict(counter)
    with (jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN) if trace
          else contextlib.nullcontext()):
        rec = driver.run()
    lowered = {k: counter[k] - lowered0[k] for k in counter}
    if trace:
        jax.profiler.stop_trace()
    if traffic.open_loop or len(rec.done) < limits["sample_requests"]:
        driver.drain()
    stats = (dev.memory_stats() or {})
    peak = stats.get("peak_bytes_in_use")
    summary = None
    if trace:
        summary = tracing.load(trace_dir, devices=cell["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)

    run = Run(cell=cell, conf=conf, m=conf["model"], arch=arch,
              traffic=traffic, rec=rec, peaks=peaks, trace=summary,
              setup_s=setup_s, prefill_chunk=engine.prefill_chunk)
    metrics = {}
    for mdef in metrics_for(bench, cell, trace):
        value = load_reader(mdef["name"])(run)
        if value is not None:
            metrics[mdef["name"]] = {"value": value, "unit": mdef["unit"]}

    from .readers import due_in_window, tpot_ms, ttft_ms
    from .stats import samples_beyond
    if traffic.open_loop:
        due = due_in_window(run)
        attempted = len(due)
        failed = sum(1 for r in due if r not in rec.done)
        late = [rec.enqueued[r] - rec.due[r] for r in due]
        log(f"info ttft_ms {json.dumps(ttft_ms(run))}")
        log(f"info tpot_ms {json.dumps(tpot_ms(run))}")
        log(f"info samples: ttft {len(ttft_ms(run))} "
            f"({samples_beyond(len(ttft_ms(run)), 80)} beyond p80), tpot "
            f"{len(tpot_ms(run))} ({samples_beyond(len(tpot_ms(run)), 80)} "
            f"beyond p80); generator lateness max {max(late, default=0.0)} s"
            f"; drained {rec.drained_at - rec.closed_at} s after the close")
    else:
        attempted = len(rec.due)
        failed = 0
        in_window = sum(1 for r in rec.done.values() if r <= rec.closed_at)
        log(f"info samples: {in_window} of {attempted} requests finished "
            f"in the window, {rec.window_chunks} decode chunks; "
            f"{len(rec.done) - in_window} more in a drain of "
            f"{max(0.0, rec.drained_at - rec.closed_at)} s")
    if summary is not None:
        from .readers import decode_work, prefill_work
        log(f"info roofline bound: decode attention {decode_work(run)[0].bound}"
            f", prefill attention {prefill_work(run)[0].bound}")
    log(f"info window: {lowered[LOWERING_EVENT]} programs lowered and "
        f"{lowered[COMPILE_EVENT]} compiled inside the window; set-up "
        f"{setup_s} s; warm-up {warm}; peak_bytes_in_use {peak}")

    model.free(engine)
    del engine, driver
    rids = check.sample(rec, seed, limits["sample_tokens"],
                        limits["sample_requests"])
    t_ref = time.perf_counter()
    got = check.compare(conf["model"], seed, rec, rids,
                        pad_rows=limits["sample_requests"],
                        reference=arch.Reference) if rids else \
        {"max_logit_gap": None, "tokens_compared": 0,
         "requests_compared": 0, "out_of_vocab": 0}
    log(f"info reference: {got['requests_compared']} requests, "
        f"{got['tokens_compared']} served tokens compared in "
        f"{time.perf_counter() - t_ref} s ({got.get('timing')})")
    checks = {
        "max_logit_gap": {"value": got["max_logit_gap"],
                          "limit": limits["max_logit_gap"]["limit"]},
        "out_of_vocab": {"value": got["out_of_vocab"], "limit": 0},
        "unfinished": {"value": failed, "limit": 0},
    }
    correct = got["tokens_compared"] > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices[:cell["chips"]]),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_gaps(10)}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log("bench: the program (src/repro) is not in this checkout")
        return 3
    sys.path.insert(0, str(ROOT / "src"))
    try:
        result = run_cell(load_benchmark(), args.workload, args.seed,
                          args.seconds, bool(args.trace),
                          cache_dir=CACHE_DIR)
    except NoChip as e:
        log(f"bench: {e}; there is no CPU fallback")
        return 3
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
