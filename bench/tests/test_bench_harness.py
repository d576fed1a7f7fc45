"""CPU checks of the harness: the drivers, the weights, the comparison that
decides ``correct`` with its control and a planted fault, the refusal to
run without a chip, and the lookup of cells' files by name."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, drive, run, traffic, weights
from bench.tests import tiny

ROOT = tiny.ROOT
BIG_SEED = 2 ** 31 + 4099


# -- drivers over a stand-in engine ------------------------------------------------

class FakeEngine:
    """The stepped API with fixed timing: a chunk of ``chunk`` steps emits
    one token a step to each live request."""

    def __init__(self, slots=4, chunk=4):
        self.max_slots, self.decode_chunk = slots, chunk
        self.prefix_cache = None
        self.stats = {"prefill_tokens": 0, "cached_tokens": 0}
        self._queue, self._live = [], {}
        self.outstanding = []

    @property
    def free_slots(self):
        return self.max_slots - len(self._live)

    @property
    def live(self):
        return len(self._live)

    @property
    def queued(self):
        return len(self._queue)

    def enqueue(self, req):
        self._queue.append(req)

    def admit(self):
        n = 0
        while self._queue and self.free_slots:
            r = self._queue.pop(0)
            self._live[r.rid] = [r, []]
            self.stats["prefill_tokens"] += len(r.prompt)
            n += 1
        return n

    def decode_step(self, on_chunk=None):
        self.outstanding.append(len(self._live) + len(self._queue))
        time.sleep(0.002)
        if on_chunk:
            on_chunk(self.decode_chunk, 0.001)
        done = []
        for rid, (r, toks) in list(self._live.items()):
            toks.extend([1] * self.decode_chunk)
            if len(toks) >= r.max_new:
                done.append((r, toks[:r.max_new]))
                del self._live[rid]
        return done


def test_closed_loop_keeps_its_clients():
    spec = tiny.spec("yi6b.decode_batch", clients=3)
    t = traffic.generate(spec, 5, 1.0, 256)
    eng = FakeEngine(slots=4)
    d = drive.Driver(eng, t, 0.2)
    rec = d.run()
    assert set(eng.outstanding) == {3}
    assert len(rec.done) > 3
    assert sum(c.delivered for c in rec.chunks) == \
        sum(len(v) for v in rec.tokens.values()) + \
        sum(d._live.values())


def test_open_loop_sends_on_schedule_and_drains():
    spec = tiny.spec("yi6b.chat", rate_per_s=50.0)
    t = traffic.generate(spec, 5, 0.3, 256)
    eng = FakeEngine(slots=2)
    d = drive.Driver(eng, t, 0.3)
    rec = d.run()
    d.drain()
    assert sorted(rec.due) == [r.rid for r in t.requests]
    assert all(rec.enqueued[r] >= rec.due[r] for r in rec.due)
    assert set(rec.done) == set(rec.due)
    assert all(rec.first[r] <= rec.done[r] for r in rec.done)


def test_prefill_rows_split_a_wave_into_chunk_steps():
    wave = drive.Wave(0, 0, [(0, 20), (16, 40)], 44)
    assert drive.prefill_rows(wave, 8) == [
        [(0, 8, False), (16, 8, False)], [(8, 8, False), (24, 8, False)],
        [(16, 4, True), (32, 8, True)]]


# -- weights ------------------------------------------------------------------------------

def test_a_layer_drawn_alone_equals_its_slice_of_the_tree():
    from bench import model
    conf = tiny.conf()
    cfg = model.model_config(conf)
    params = model.draw_params(conf, cfg, BIG_SEED * 3)
    key = weights.base_key(BIG_SEED * 3)
    m = conf["model"]
    for path, got in [("layers/attn/wk", params["layers"]["attn"]["wk"][1]),
                      ("layers/ffn/norm", params["layers"]["ffn"]["norm"][0])]:
        again = weights.draw_layer_leaf(m, key, path, 1 if "wk" in path
                                        else 0, got.shape, got.dtype)
        np.testing.assert_array_equal(np.asarray(again), np.asarray(got))
    emb = weights.draw_layer_leaf(m, key, "embedding", None,
                                  params["embedding"].shape, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(emb),
                                  np.asarray(params["embedding"]))
    other = model.draw_params(conf, cfg, BIG_SEED * 3 + 1)
    assert not np.array_equal(np.asarray(other["unembed"]),
                              np.asarray(params["unembed"]))


# -- the comparison that decides `correct` ---------------------------------------------------

def _tiny_run(cell, seed=BIG_SEED, seconds=1.5, breakage=None, arch=None,
              **over):
    """A run of ``cell`` (or of the mix of that name, for a mix that has
    files but no cell yet) at the tiny size on the CPU, with the
    architecture module ``arch`` in place of the one found by name."""
    bench = run.load_benchmark()
    if not any(c["name"] == cell for c in bench["workloads"]):
        bench["workloads"].append({"name": cell, "config": "yi-6b",
                                   "traffic": cell, "chips": 1, "why": ""})
    mix = next(c for c in bench["workloads"] if c["name"] == cell)["traffic"]
    if traffic.load_spec(mix)["loop"] == "open":
        over.setdefault("rate_per_s", 20.0)
    return run.run_cell(bench, cell, seed, seconds, False,
                        require_chip=False, conf=tiny.conf(),
                        spec=tiny.spec(mix, **over), limits=tiny.LIMITS,
                        arch=arch, breakage=breakage)


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_sound_runs_are_correct(cell):
    res = _tiny_run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert "setup_s" in res["metrics"]


def _alter_tokens(engine):
    """Plant a fault: the engine hands out one token changed, where it is
    produced (the decode chunk's output)."""
    step = engine.decode_step

    def altered(on_chunk=None):
        out = step(on_chunk=on_chunk)
        return [(req, toks[:-1] + [(toks[-1] + 1) % engine.cfg.vocab_size])
                for req, toks in out]

    engine.decode_step = altered


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_an_altered_token_is_not_correct(cell):
    res = _tiny_run(cell, breakage=_alter_tokens)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        tiny.LIMITS["max_logit_gap"]["limit"]


def test_the_float8_control_is_not_correct():
    """The control, at a size a test holds: the reference in float8 in the
    program's place reads a gap above the limit, where the program does
    not."""
    from bench import model
    conf = tiny.conf()
    cfg = model.model_config(conf)
    spec = tiny.spec("yi6b.chat", rate_per_s=30.0)
    t = traffic.generate(spec, BIG_SEED, 1.0, cfg.vocab_size)
    eng = model.build_engine(conf, cfg, model.draw_params(conf, cfg,
                                                          BIG_SEED))
    model.warm_up(eng, t, BIG_SEED)
    d = drive.Driver(eng, t, 1.0)
    rec = d.run()
    d.drain()
    rids = check.sample(rec, BIG_SEED, 200, 16)
    got = check.compare(conf["model"], BIG_SEED, rec, rids, control=True)
    limit = tiny.LIMITS["max_logit_gap"]["limit"]
    assert got["max_logit_gap"] <= limit < got["control_max_logit_gap"]


# -- no chip, no program ------------------------------------------------------------------------

def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "yi6b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_to_run_without_a_tpu():
    p = _cli(ROOT, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no CPU fallback" in p.stderr


def test_refuses_to_run_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# -- cells' files are found by name --------------------------------------------------------------

def test_new_traffic_and_metric_files_are_found_by_name(tmp_path):
    (tmp_path / "new_mix.json").write_text(json.dumps(
        tiny.spec("yi6b.chat", rate_per_s=9.0)))
    (tmp_path / "queue_depth.open.py").write_text(
        "def read(run):\n    return float(len(run.rec.due))\n")
    assert traffic.load_spec("new_mix", tmp_path)["rate_per_s"] == 9.0
    reader = run.load_reader("queue_depth.open", tmp_path)
    assert reader(SimpleNamespace(rec=SimpleNamespace(due={1: 0, 2: 0}))) \
        == 2.0
    bench = run.load_benchmark()
    bench["workloads"].append({"name": "new.cell", "config": "yi-6b",
                               "traffic": "new_mix", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "queue_depth.open", "unit": "n",
                               "better": "lower", "source": "host_clock",
                               "layer": "engine admission",
                               "moves": "ttft_p95_ms",
                               "workloads": ["new.cell"]})
    cell = bench["workloads"][-1]
    names = [m["name"] for m in run.metrics_for(bench, cell, True)]
    assert names == ["queue_depth.open"]
    e2e = [m["name"] for m in run.metrics_for(bench, cell, False)]
    assert e2e == ["setup_s"]


def test_every_metric_has_a_reader_and_every_cell_its_files():
    bench = run.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.load_reader(m["name"]))
    for c in bench["workloads"]:
        assert traffic.load_spec(c["traffic"])
        assert check.load_limits(c["name"])["max_logit_gap"]["limit"] > 0
        conf = json.loads((ROOT / f"bench/configs/{c['config']}.json")
                          .read_text())
        assert traffic.longest_request(traffic.load_spec(c["traffic"])) <= \
            conf["serving"]["max_len"]
    assert jax.devices()[0].platform == "cpu"
