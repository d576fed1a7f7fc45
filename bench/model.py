"""The system under test, as one configuration file says to build it.

A configuration is ``bench/configs/<name>.json``: ``model`` holds the
published sizes as they are run, ``serving`` the engine's geometry and
dtypes. The program's own config registry entry (``registry``) is
replaced by both, key by key, so the file is what runs. Its
``architecture`` (``dense_gqa`` where the file names none) is the module
``bench/architectures/<architecture>.py`` that holds the reference, the
weight rules and the operation counts of that kind of model.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

CONFIGS_DIR = Path(__file__).resolve().parent / "configs"
ARCHITECTURES_DIR = Path(__file__).resolve().parent / "architectures"
DEFAULT_ARCHITECTURE = "dense_gqa"
# What an architecture module provides (bench/architectures/dense_gqa.py).
ARCHITECTURE_NAMES = ("Reference", "LEAVES", "decode_attention_call",
                      "prefill_attention_call", "decode_step_flops",
                      "prefill_step_flops")
ENGINE_KEYS = ("max_len",)          # serving keys that are engine arguments


def load_config(name: str, directory: Path = CONFIGS_DIR) -> dict:
    return json.loads((directory / f"{name}.json").read_text())


def load_architecture(name: str, directory: Path = ARCHITECTURES_DIR):
    """The module ``<directory>/<name>.py``; raises, naming what is
    missing, where there is no such file or it lacks one of
    ``ARCHITECTURE_NAMES``."""
    path = directory / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"architecture {name!r}: no module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_architecture_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [n for n in ARCHITECTURE_NAMES if not hasattr(mod, n)]
    if missing:
        raise AttributeError(f"architecture {name!r} ({path}) lacks "
                             f"{', '.join(missing)}")
    return mod


def architecture(conf: dict, directory: Path = ARCHITECTURES_DIR):
    """The architecture module the configuration names."""
    return load_architecture(conf.get("architecture", DEFAULT_ARCHITECTURE),
                             directory)


def model_config(conf: dict):
    """The program's ``ModelConfig`` with every key of the file's ``model``
    and ``serving`` sections."""
    from repro.configs import get_config
    base = get_config(conf["registry"])
    keys = {f.name for f in dataclasses.fields(base)}
    over = dict(conf["model"])
    over.update({k: v for k, v in conf["serving"].items()
                 if k not in ENGINE_KEYS})
    unknown = sorted(set(over) - keys)
    if unknown:
        raise KeyError(f"{conf['name']}: keys the program does not have: "
                       f"{unknown}")
    return base.replace(**over)


def abstract_params(cfg):
    from repro.models import get_family
    from repro.models.params import abstract_params as abstract
    return abstract(get_family(cfg).layout(cfg), cfg.param_dtype)


def fit_check(cfg, slots: int, max_len: int, bytes_limit) -> dict:
    """Compile one paged decode step at the served sizes and compare what
    it needs with the device's limit; a configuration that does not fit is
    refused, never cut."""
    from repro.models import get_family
    from repro.train.train_step import build_paged_decode_step
    fam = get_family(cfg)
    npp = math.ceil(max_len / cfg.page_size)
    pool = jax.eval_shape(lambda: fam.paged_pool(cfg, slots * npp + 1))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    batch = {"tokens": i32(slots, 1), "pos": i32(slots),
             "page_table": i32(slots, npp)}
    step = jax.jit(build_paged_decode_step(cfg), donate_argnums=(2,))
    ma = step.lower(abstract_params(cfg), batch,
                    pool).compile().memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    out = {"need": int(need), "temp": int(ma.temp_size_in_bytes),
           "limit": bytes_limit}
    if bytes_limit is not None and need > bytes_limit:
        raise RuntimeError(f"{cfg.name}: one decode step needs {need} B, the "
                           f"device holds {bytes_limit} B; the configuration "
                           "does not fit and is not cut")
    return out


def draw_params(conf: dict, cfg, seed: int, arch=None):
    """The program's weights by the rules of ``arch`` (by default the
    architecture the configuration names)."""
    arch = arch or architecture(conf)
    params = weights.program_params(conf["model"], abstract_params(cfg), seed,
                                    arch.LEAVES)
    return jax.block_until_ready(params)


def build_engine(conf: dict, cfg, params):
    from repro.serve import ContinuousBatchingEngine
    return ContinuousBatchingEngine(
        cfg, params, max_len=conf["serving"]["max_len"],
        max_slots=cfg.max_decode_slots)


def free(engine) -> None:
    """Delete the engine's device arrays so the reference has the chip."""
    for leaf in jax.tree_util.tree_leaves((engine.pool, engine.params)):
        leaf.delete()
    engine.pool = engine.params = None


def warm_up(engine, traffic, seed: int) -> dict:
    """Compile or load every program the cell's traffic runs, and leave the
    cell's shared documents resident, once in each namespace that reads
    them.

    Each power-of-two admission bucket up to the slot count is run once
    with one-chunk prompts, as many waves live at once as the slots hold,
    then decode chunks retire them. Documents are then admitted, one
    request each, beside filler requests that finish one chunk later: the
    fillers' pages end on top of the free list, where the window's first
    allocations find them, and the documents' pages stay cached below
    them."""
    from repro.serve.engine import EngineRequest
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 5]))
    vocab = engine.cfg.vocab_size
    slots = engine.max_slots
    chunk = engine.decode_chunk
    ns = ("bench-warm-up",)
    rid = iter(range(10 ** 9, 2 * 10 ** 9))

    def admit(reqs):
        for r in reqs:
            engine.enqueue(r)
        engine.admit()
        if engine.queued:
            raise RuntimeError("warm-up wave did not fit")

    def retire():
        while engine.live:
            engine.decode_step()

    def toks(n):
        return rng.integers(0, vocab, n).tolist()

    gp, live = 1, 0
    while gp <= slots:
        if live + gp > slots:
            retire()
            live = 0
        admit([EngineRequest(next(rid), toks(engine.prefill_chunk), 1, ns)
               for _ in range(gp)])
        live += gp
        gp *= 2
    retire()
    reads = sorted({(r.doc, traffic.namespace(r)) for r in traffic.requests
                    if r.doc >= 0}, key=repr)
    if len(reads) > slots:
        raise ValueError("more shared documents to hold than slots")
    if reads:
        reqs = [EngineRequest(next(rid), list(traffic.docs[d]) +
                              toks(engine.page_size), chunk, namespace)
                for d, namespace in reads]
        filler = max(1, engine.pages_per_seq * engine.page_size // 2
                     - 2 * chunk)
        reqs += [EngineRequest(next(rid), toks(filler), 2 * chunk, ns)
                 for _ in range(slots - len(reads))]
        admit(reqs)
        retire()
    return {"docs": len(reads),
            "cached_tokens": engine.stats["cached_tokens"]}

