"""Readings that set a cell's limits and rate, on the chip, in one process.

    python3 -m bench.calibrate --workload <cell> --seeds 1,2,3 --seconds 20
    python3 -m bench.calibrate --workload <cell> --seeds 7 --seconds 20 \
        --rates 2,4,6,8

With ``--seeds``: for each seed, the cell's set-up, a window of
``--seconds`` at the cell's own load, the drain, and then the comparison
of ``bench.check`` with the control: the program's widest gap and the
float8 reference's, on the same requests. Those readings are what
``bench/checks/<cell>.json`` records as ``lower`` and ``upper``. The
benchmark's own runs never run the control.

With ``--rates``: the open-loop mix of the cell at each rate in turn, on
one engine: time to first token and between tokens, with how far the
queue grew, to find the highest rate the engine sustains.

One JSON object per reading is printed on standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from .run import CACHE_DIR, ROOT, load_benchmark, log


def _setup(cell, conf, spec, seed, seconds):
    from . import model
    from .traffic import generate
    cfg = model.model_config(conf)
    engine = model.build_engine(conf, cfg,
                                model.draw_params(conf, cfg, seed))
    traffic = generate(spec, seed, seconds, cfg.vocab_size)
    model.warm_up(engine, traffic, seed)
    return engine, traffic


def seeds(cell, conf, spec, limits, seed_list, seconds):
    from . import check, model
    from .drive import Driver
    reference = model.architecture(conf).Reference
    for seed in seed_list:
        t0 = time.perf_counter()
        engine, traffic = _setup(cell, conf, spec, seed, seconds)
        d = Driver(engine, traffic, seconds)
        rec = d.run()
        d.drain()
        model.free(engine)
        rids = check.sample(rec, seed, limits["sample_tokens"],
                            limits["sample_requests"])
        t1 = time.perf_counter()
        got = check.compare(conf["model"], seed, rec, rids, control=True,
                            pad_rows=limits["sample_requests"],
                            reference=reference)
        got["compare_s"] = time.perf_counter() - t1
        got.update(seed=seed, finished=len(rec.done), seconds=
                   time.perf_counter() - t0)
        print(json.dumps(got), flush=True)


def rates(cell, conf, spec, rate_list, seconds, seed):
    from . import model
    from .drive import Driver
    from .readers import p95
    from .run import Run
    from .readers import tpot_ms, ttft_ms
    from .traffic import generate
    arch = model.architecture(conf)
    engine, _ = _setup(cell, conf, spec, seed, seconds)
    for rate in rate_list:
        s = dict(spec, rate_per_s=rate)
        traffic = generate(s, seed, seconds, engine.cfg.vocab_size)
        d = Driver(engine, traffic, seconds)
        rec = d.run()
        backlog = engine.queued
        d.drain()
        engine.abort()              # what the drain's limit left
        run = Run(cell=cell, conf=conf, m=conf["model"], arch=arch,
                  traffic=traffic, rec=rec, peaks={}, trace=None, setup_s=0.0,
                  prefill_chunk=engine.prefill_chunk)
        ttft = ttft_ms(run)
        half = len(ttft) // 2
        print(json.dumps({
            "rate": rate, "requests": len(ttft),
            "ttft_p95_ms": p95(ttft), "tpot_p95_ms": p95(tpot_ms(run)),
            "ttft_p95_first_half_ms": p95(ttft[:half]),
            "ttft_p95_second_half_ms": p95(ttft[half:]),
            "queued_at_close": backlog,
            "drain_s": rec.drained_at - rec.closed_at}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        log("calibrate: no TPU found")
        return 3
    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from . import check, model
    from .traffic import load_spec
    bench = load_benchmark()
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    conf = model.load_config(cell["config"])
    spec = load_spec(cell["traffic"])
    if args.rates:
        rates(cell, conf, spec, [float(r) for r in args.rates.split(",")],
              args.seconds, int(args.seeds.split(",")[0] or 1))
    else:
        seeds(cell, conf, spec, check.load_limits(cell["name"]),
              [int(s) for s in args.seeds.split(",")], args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
