"""A configuration names its architecture, and the harness takes the
reference, the weight rules and the operation counts from that module."""
from __future__ import annotations

import json
import textwrap
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import check, counts, drive, model, readers, reference, run, \
    weights
from bench.tests import tiny
from bench.tests.test_bench_harness import BIG_SEED, _tiny_run

# A copy of the dense decoder under a name of its own: the reference
# imported, the weight rules as functions of the sizes, the counts as
# functions of its own.
TOY = """
from bench import counts, weights
from bench.reference import Reference

LEAVES = {path: (lambda m, k=rule: m[k]) if rule in ("d_model", "d_ff")
          else rule for path, rule in weights.LEAVES.items()}


def decode_attention_call(m, lengths):
    return counts.decode_attention_call(m, lengths)


def prefill_attention_call(m, rows):
    return counts.prefill_attention_call(m, rows)


def decode_step_flops(m, lengths):
    return counts.decode_step_flops(m, lengths)


def prefill_step_flops(m, rows):
    return counts.prefill_step_flops(m, rows)
"""

# The dense decoder with one thing changed in its reference.
ROPE_DOUBLED = """
from bench.counts import (decode_attention_call, decode_step_flops,
                          prefill_attention_call, prefill_step_flops)
from bench.reference import Reference as _Dense
from bench.weights import LEAVES


class Reference(_Dense):
    def __init__(self, m, seed, *, fp8=False):
        super().__init__(dict(m, rope_theta=2 * m["rope_theta"]), seed,
                         fp8=fp8)
"""


def _write(directory, name: str, body: str):
    (directory / f"{name}.py").write_text(textwrap.dedent(body))


# -- found by name, or a loud failure at set-up ---------------------------------------------

def test_a_file_without_the_key_is_the_dense_decoder():
    arch = model.architecture(tiny.conf())
    assert "architecture" not in tiny.conf()
    assert arch.Reference is reference.Reference
    assert arch.LEAVES is weights.LEAVES
    for name in ("decode_attention_call", "prefill_attention_call",
                 "decode_step_flops", "prefill_step_flops"):
        assert getattr(arch, name) is getattr(counts, name)


def test_an_architecture_is_found_by_name(tmp_path):
    _write(tmp_path, "toy_decoder", TOY)
    arch = model.architecture({"architecture": "toy_decoder"}, tmp_path)
    assert arch.Reference is reference.Reference
    assert callable(arch.LEAVES["layers/ffn/w_down"])
    assert arch.decode_attention_call(tiny.TINY_MODEL, [3, 5]) == \
        counts.decode_attention_call(tiny.TINY_MODEL, [3, 5])


def test_a_missing_architecture_fails_at_set_up():
    conf = dict(tiny.conf(), architecture="no_such_architecture")
    opened = []
    with pytest.raises(FileNotFoundError, match="no_such_architecture"):
        run.run_cell(run.load_benchmark(), "yi6b.chat", BIG_SEED, 1.0, False,
                     require_chip=False, conf=conf,
                     spec=tiny.spec("yi6b.chat"), limits=tiny.LIMITS,
                     breakage=opened.append)
    assert opened == []


@pytest.mark.parametrize("missing", model.ARCHITECTURE_NAMES)
def test_an_architecture_without_a_name_fails_naming_it(tmp_path, missing):
    _write(tmp_path, "partial", TOY + f"\ndel {missing}\n")
    with pytest.raises(AttributeError, match=f"lacks {missing}$"):
        model.architecture({"architecture": "partial"}, tmp_path)


# -- the reference -------------------------------------------------------------------------

def test_the_architecture_s_reference_decides_correct(tmp_path):
    _write(tmp_path, "rope_doubled", ROPE_DOUBLED)
    dense = model.architecture(tiny.conf())
    doubled = model.architecture({"architecture": "rope_doubled"}, tmp_path)
    assert _tiny_run("yi6b.chat", arch=dense)["correct"]
    res = _tiny_run("yi6b.chat", arch=doubled)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        tiny.LIMITS["max_logit_gap"]["limit"]


def test_a_new_architecture_runs_to_correct_from_its_own_files(tmp_path):
    """A configuration and its architecture as new files, nothing in bench/
    edited: the tiny engine runs the cell to a correct result."""
    (tmp_path / "configs").mkdir()
    conf = dict(tiny.conf(), name="toy-6b", architecture="toy_decoder")
    (tmp_path / "configs" / "toy-6b.json").write_text(json.dumps(conf))
    _write(tmp_path, "toy_decoder", TOY)
    conf = model.load_config("toy-6b", tmp_path / "configs")
    bench = run.load_benchmark()
    bench["workloads"].append({"name": "toy.chat", "config": "toy-6b",
                               "traffic": "yi6b.chat", "chips": 1,
                               "why": "x"})
    res = run.run_cell(bench, "toy.chat", BIG_SEED + 7, 1.5, False,
                       require_chip=False, conf=conf,
                       spec=tiny.spec("yi6b.chat", rate_per_s=20.0),
                       limits=tiny.LIMITS,
                       arch=model.architecture(conf, tmp_path))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def _fixed_rec(vocab: int, seed: int = 3):
    rng = np.random.default_rng(seed)
    reqs, toks = {}, {}
    for r, (plen, n) in enumerate([(20, 6), (9, 12), (31, 3)]):
        reqs[r] = SimpleNamespace(prompt=rng.integers(0, vocab, plen).tolist())
        toks[r] = rng.integers(0, vocab, n).tolist()
    return SimpleNamespace(requests=reqs, tokens=toks)


def test_the_dense_decoder_compares_as_before():
    m = tiny.conf()["model"]
    rec = _fixed_rec(m["vocab_size"])
    before = check.compare(m, BIG_SEED, rec, [0, 1, 2], control=True)
    after = check.compare(m, BIG_SEED, rec, [0, 1, 2], control=True,
                          reference=model.architecture(tiny.conf()).Reference)
    before.pop("timing"), after.pop("timing")
    assert after == before


# -- weight rules ---------------------------------------------------------------------------

def test_the_dense_decoder_draws_as_before():
    conf = tiny.conf()
    cfg = model.model_config(conf)
    got = model.draw_params(conf, cfg, BIG_SEED)
    want = weights.program_params(conf["model"], model.abstract_params(cfg),
                                  BIG_SEED)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


MOE_LEAVES = {"layers/ffn/router": "d_model", "layers/ffn/we_gate": "d_model",
              "layers/ffn/we_up": "d_model",
              "layers/ffn/we_down": lambda m: m["d_ff"]}


def test_weight_rules_come_from_the_architecture():
    from repro.configs.olmoe_1b_7b import reduced
    cfg = reduced()
    m = {k: getattr(cfg, k) for k in ("num_layers", "d_model", "num_heads",
                                      "num_kv_heads", "head_dim", "d_ff",
                                      "vocab_size", "num_experts")}
    tree = model.abstract_params(cfg)
    with pytest.raises(KeyError, match="no weight rule for leaf "
                                       "'layers/ffn/router'"):
        weights.program_params(m, tree, BIG_SEED)
    params = weights.program_params(m, tree, BIG_SEED,
                                    dict(weights.LEAVES, **MOE_LEAVES))
    down = params["layers"]["ffn"]["we_down"]
    assert down.shape == (cfg.num_layers, cfg.num_experts, cfg.d_ff,
                          cfg.d_model)
    alone = weights.draw_layer_leaf(m, weights.base_key(BIG_SEED),
                                    "layers/ffn/we_down", 1, down.shape[1:],
                                    down.dtype,
                                    dict(weights.LEAVES, **MOE_LEAVES))
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(down[1]))
    by_key = weights.program_params(
        m, tree, BIG_SEED,
        {**weights.LEAVES, **MOE_LEAVES, "layers/ffn/we_down": "d_ff"})
    np.testing.assert_array_equal(np.asarray(by_key["layers"]["ffn"]
                                             ["we_down"]), np.asarray(down))


# -- operation counts ---------------------------------------------------------------------

PEAKS = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}


def _fixed_counts(seen):
    def call(m, arg):
        seen.append(list(arg))
        return 5.0, 7.0
    return SimpleNamespace(decode_attention_call=call,
                           prefill_attention_call=call,
                           decode_step_flops=lambda m, lengths: 11.0,
                           prefill_step_flops=lambda m, rows: 13.0)


def test_decode_counts_come_from_the_architecture():
    seen = []
    chunk = drive.Chunk(0.0, 1.0, steps=2, seconds=1.0,
                        live=[(10, 2), (20, 1)], delivered=3)
    fake = SimpleNamespace(m={"num_layers": 3}, peaks=PEAKS,
                           arch=_fixed_counts(seen),
                           rec=SimpleNamespace(chunks=[chunk],
                                               window_chunks=1))
    work, flops = readers.decode_work(fake)
    assert seen == [[11, 21], [12]]
    assert (work.flops, work.bytes, work.calls) == (30.0, 42.0, 6)
    assert flops == 22.0


def test_prefill_counts_come_from_the_architecture():
    seen = []
    wave = drive.Wave(0.0, 1.0, rows=[(0, 20)], prefill_tokens=20)
    fake = SimpleNamespace(m={"num_layers": 3}, peaks=PEAKS,
                           arch=_fixed_counts(seen), prefill_chunk=8,
                           rec=SimpleNamespace(waves=[wave], window_waves=1))
    work, flops = readers.prefill_work(fake)
    assert seen == [[(0, 8)], [(8, 8)], [(16, 4)]]
    assert (work.flops, work.bytes, work.calls) == (45.0, 63.0, 9)
    assert flops == 39.0
