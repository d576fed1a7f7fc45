#!/usr/bin/env python3
"""Chip smoke test: the served path at yi-6b's published widths on a TPU.

    python chip_smoke.py               # one chip (the default)
    python chip_smoke.py --four-chips  # 4 one-chip replicas behind FleetRouter

One chip: yi-6b at d_model 4096, 32 q / 4 kv heads, head_dim 128, d_ff 11008,
vocab 64000 in bf16, random weights from ``--seed``, Pallas attention,
page_size 16, 8 decode slots, max_len 1024. Phases, in order:

1. size: compile one paged decode step and keep all layers if its
   ``memory_analysis()`` fits the device; otherwise cut whole layers.
2. gateway: one tenant provisioned through ``PolicyEngine``, 8 mixed-length
   requests through ``KottaServeGateway`` on one on-demand replica; every
   job must end DONE with in-vocab tokens, the prefill program must give
   finite logits, and the decode chunk and prefill step it ran must contain
   the Pallas kernels (``tpu_custom_call``).
3. spec: a self-speculative engine (K=4) on the same prompts.
4. int8: an int8-pool engine on the same prompts.
5. kernels: each paged kernel against its jnp reference at these widths
   (decode at 1 page a block and at the block the kernel chooses, prefill,
   verify at 1 split and at the engine's split count; bf16 and int8).

``--four-chips`` runs only the replica phase: 4 unified replicas with
affinity routing, replica i with its params (and so its pool and its
dispatches) on device i, against the same requests through a one-replica
gateway in the same process; per-request tokens must match.

Exits non-zero, printing no result, when JAX finds no TPU: there is no CPU
fallback. The last line on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.clock import VirtualClock  # noqa: E402
from repro.core.elastic import ScalingPolicy  # noqa: E402
from repro.core.security import PolicyEngine, provision_tenant  # noqa: E402
from repro.kernels import kv_quant  # noqa: E402
from repro.kernels.decode_attention.kernel import flash_decode_fwd  # noqa: E402
from repro.kernels.decode_attention.ops import default_num_splits  # noqa: E402
from repro.kernels.decode_attention.ref import paged_decode_reference  # noqa: E402
from repro.kernels.prefill_attention.kernel import flash_prefill_fwd  # noqa: E402
from repro.kernels.prefill_attention.ref import paged_prefill_reference  # noqa: E402
from repro.kernels.verify_attention.kernel import flash_verify_fwd  # noqa: E402
from repro.kernels.verify_attention.ref import paged_verify_reference  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import get_family  # noqa: E402
from repro.models.params import abstract_params, init_params  # noqa: E402
from repro.serve import (ContinuousBatchingEngine, JobState,  # noqa: E402
                         KottaServeGateway)
from repro.train.train_step import build_paged_decode_step  # noqa: E402

SLOTS = 8
MAX_LEN = 1024
MAX_NEW = 32
SPEC_TOKENS = 4
PROMPT_LENS = (64, 128, 192, 256, 384, 512, 640, 768)
# Kernel vs reference: both accumulate in f32 from the same bf16/int8
# inputs and round the output to bf16, so the difference is bf16 output
# rounding plus MXU operand rounding of the f32 probabilities.
KERNEL_ATOL = KERNEL_RTOL = 2e-2


def smoke_config():
    """yi-6b at its published widths, as the chip serves it."""
    return get_config("yi-6b").replace(
        dtype="bfloat16", param_dtype="bfloat16", page_size=16,
        attn_impl="pallas", max_decode_slots=SLOTS)


def log(msg: str) -> None:
    print(msg, flush=True)


def make_prompts(cfg, lens, seed: int) -> list[list[int]]:
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, size=n).tolist() for n in lens]


def _pool_pages(cfg, slots: int, max_len: int) -> int:
    return slots * math.ceil(max_len / cfg.page_size) + 1


def fit_layers(cfg, *, slots: int, max_len: int):
    """Keep all layers if one compiled decode step fits the device with the
    pool; otherwise cut whole layers. Returns the config to serve."""
    device = jax.devices()[0]
    limit = (device.memory_stats() or {}).get("bytes_limit")
    fam = get_family(cfg)
    npp = math.ceil(max_len / cfg.page_size)
    params = abstract_params(fam.layout(cfg), cfg.param_dtype)
    pool = jax.eval_shape(
        lambda: fam.paged_pool(cfg, _pool_pages(cfg, slots, max_len)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    batch = {"tokens": i32(slots, 1), "pos": i32(slots),
             "page_table": i32(slots, npp)}
    step = jax.jit(build_paged_decode_step(cfg), donate_argnums=(2,))
    ma = step.lower(params, batch, pool).compile().memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    log(f"size: decode step needs {need} B (args {ma.argument_size_in_bytes}"
        f", temp {ma.temp_size_in_bytes}); device limit {limit} B")
    if limit is None or need <= limit:
        log(f"size: keeping all {cfg.num_layers} layers")
        return cfg
    stacked = sum(math.prod(x.shape) * x.dtype.itemsize for x in
                  jax.tree_util.tree_leaves(params["layers"]))
    pool_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(pool))
    per_layer = (stacked + pool_bytes) // cfg.num_layers
    layers = (limit - (need - per_layer * cfg.num_layers)) // per_layer
    if layers < 1:
        raise RuntimeError(f"not even one layer fits: need {need} B of "
                           f"{limit} B")
    log(f"size: CUT {cfg.num_layers} -> {layers} layers: the decode step "
        f"needs {need} B but the device holds {limit} B "
        f"({per_layer} B per layer of params + KV pool)")
    return cfg.replace(num_layers=int(layers))


def build_params(cfg, seed: int):
    """Random weights from ``seed``, drawn on the default device."""
    params = init_params(get_family(cfg).layout(cfg),
                         jax.random.PRNGKey(seed), cfg.param_dtype)
    return jax.block_until_ready(params)


def _check_tokens(cfg, toks, max_new: int, what: str) -> None:
    toks = np.asarray(toks)
    if toks.shape[-1] != max_new:
        raise AssertionError(f"{what}: {toks.shape[-1]} tokens, want "
                             f"{max_new}")
    if toks.min() < 0 or toks.max() >= cfg.vocab_size:
        raise AssertionError(f"{what}: token outside [0, {cfg.vocab_size})")


def serve_gateway(cfg, params_per_replica, prompts, *, max_new: int,
                  max_len: int, slots: int):
    """Send ``prompts`` as one tenant through a gateway with one on-demand
    replica per entry of ``params_per_replica`` (replica i serves from
    wherever its params live). Returns ({prompt index: tokens}, engines)."""
    sec = PolicyEngine(clock=VirtualClock())
    token = provision_tenant(sec, "smoke-tenant", "smoke-secret",
                             data_zones=("public",))
    engines: list[ContinuousBatchingEngine] = []

    def factory():
        p = params_per_replica[len(engines) % len(params_per_replica)]
        eng = ContinuousBatchingEngine(cfg, p, max_len=max_len,
                                       max_slots=slots)
        engines.append(eng)
        return eng

    gw = KottaServeGateway(
        factory, sec, routing="affinity",
        scaling=ScalingPolicy.none(len(params_per_replica),
                                   market="on_demand"))
    rids = [gw.submit(token, p, max_new=max_new) for p in prompts]
    gw.drain()
    out = {}
    for i, rid in enumerate(rids):
        job = gw.jobs[rid]
        if job.status is not JobState.DONE:
            raise AssertionError(f"request {i}: {job.status.value} "
                                 f"({job.error})")
        out[i] = list(gw.result(rid))
        _check_tokens(cfg, out[i], max_new, f"gateway request {i}")
    log(f"gateway: {len(rids)} requests DONE on {len(engines)} replica(s), "
        f"tokens in [0, {cfg.vocab_size})")
    return out, engines


def _prefill_batch(eng, prompts):
    """A batch for the engine's own prefill program: the first chunk of each
    prompt, padded to the engine's power-of-two bucket, written to pages
    1.. of the pool (running it spends the engine's cached prefixes)."""
    gp = 1 << (len(prompts) - 1).bit_length()
    c, npp = eng.prefill_chunk, eng.pages_per_seq
    n = min(c, min(len(p) for p in prompts))
    npages = math.ceil(n / eng.page_size)
    toks = np.zeros((gp, c), np.int32)
    pt = np.zeros((gp, npp), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :n] = p[:n]
        pt[i, :npages] = 1 + i * npp + np.arange(npages)
    return {"tokens": jnp.asarray(toks),
            "q_start": jnp.zeros(gp, jnp.int32),
            "kv_len": jnp.full(gp, n, jnp.int32),
            "page_table": jnp.asarray(pt),
            "logit_idx": jnp.full(gp, n - 1, jnp.int32)}


def check_engine_programs(cfg, eng, prompts, *, expect_kernels: bool):
    """The decode chunk and prefill step this engine ran contain the Pallas
    kernels, and the prefill program yields finite logits of vocab width."""
    budget = np.zeros(eng.max_slots, np.int32)
    chunk = eng._chunk.lower(
        eng.params, jnp.asarray(eng._cur), jnp.asarray(eng._pos),
        jnp.asarray(eng._page_table), jnp.asarray(eng._active),
        jnp.asarray(budget), eng.pool).compile().as_text()
    batch = _prefill_batch(eng, prompts)
    prefill = eng._prefill_chunked.lower(eng.params, batch,
                                         eng.pool).compile().as_text()
    found = {"decode chunk": "tpu_custom_call" in chunk,
             "prefill step": "tpu_custom_call" in prefill}
    log(f"programs: tpu_custom_call found: {found}")
    if expect_kernels and not all(found.values()):
        raise AssertionError(f"Pallas kernels missing from {found}")
    _, logits, eng.pool = eng._prefill_chunked(eng.params, batch, eng.pool)
    logits = np.asarray(logits[:len(prompts)], np.float32)
    if logits.shape != (len(prompts), cfg.vocab_size):
        raise AssertionError(f"logits shape {logits.shape}")
    if not np.isfinite(logits).all():
        raise AssertionError("non-finite prefill logits")
    log(f"programs: prefill logits {logits.shape} finite, "
        f"|max| {float(np.abs(logits).max())}")


def free_pool(eng) -> None:
    for leaf in jax.tree_util.tree_leaves(eng.pool):
        leaf.delete()
    eng.pool = None


def run_engine(cfg, params, prompts, reference, *, max_new: int,
               max_len: int, slots: int, what: str, **engine_kw):
    """Greedy-decode ``prompts`` on a fresh engine; tokens must be in vocab
    and the first token (from the shared prefill program for bf16 pools)
    is compared with ``reference``."""
    eng = ContinuousBatchingEngine(cfg, params, max_len=max_len,
                                   max_slots=slots, **engine_kw)
    toks = eng.generate(prompts, max_new=max_new).tokens
    _check_tokens(cfg, toks, max_new, what)
    same = [int(np.argmin(np.append(toks[i] == reference[i], False)))
            for i in range(len(prompts))]
    log(f"{what}: {len(prompts)} requests done; tokens agreeing with the "
        f"gateway run before the first difference: {same}")
    free_pool(eng)
    return toks, same


def _paged_inputs(key, *, b, rows, h, kv, hd, ps, npages, kv_dtype):
    ks = jax.random.split(key, 5)
    pool_pages = b * npages + 1
    q = jax.random.normal(ks[0], (b,) + rows + (h, hd), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (kv, pool_pages, ps, hd), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (kv, pool_pages, ps, hd), jnp.bfloat16)
    perm = 1 + jax.random.permutation(ks[3], pool_pages - 1)[:b * npages]
    pt = perm.reshape(b, npages).astype(jnp.int32)
    if kv_dtype == "int8":
        qp = kv_quant.quantize_pool({"k": kp, "v": vp})
        kp, vp = qp["k"], qp["v"]
        scales = dict(k_scale=qp["k_scale"], v_scale=qp["v_scale"])
    else:
        scales = {}
    return q, kp, vp, pt, ks[4], scales


def check_kernels(cfg, *, slots: int, max_len: int, interpret: bool):
    """Each paged kernel against its jnp reference at the serving widths.
    Returns {case: max abs error}."""
    h, kv, hd, ps = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, \
        cfg.page_size
    npages = math.ceil(max_len / ps)
    splits = default_num_splits(npages, batch=slots,
                                split_budget=cfg.decode_split_budget)
    key = jax.random.PRNGKey(7)
    window, chunk = SPEC_TOKENS + 1, cfg.prefill_chunk
    errors = {}

    def compare(name, out, ref):
        out = np.asarray(out, np.float32)
        ref = np.asarray(ref, np.float32)
        err = float(np.abs(out - ref).max())
        bound = KERNEL_ATOL + KERNEL_RTOL * np.abs(ref)
        ok = bool(np.isfinite(out).all() and (np.abs(out - ref) <= bound).all())
        log(f"kernel {name}: max abs err {err} "
            f"({'ok' if ok else 'FAIL'} at atol {KERNEL_ATOL} "
            f"rtol {KERNEL_RTOL})")
        errors[name] = err
        if not ok:
            raise AssertionError(f"kernel {name} disagrees with its "
                                 f"reference: max abs err {err}")

    highest = lambda: jax.default_matmul_precision("highest")
    for kv_dtype in ("bf16", "int8"):
        dims = dict(h=h, kv=kv, hd=hd, ps=ps, npages=npages,
                    kv_dtype=kv_dtype)
        q, kp, vp, pt, k2, sc = _paged_inputs(key, b=slots, rows=(), **dims)
        lengths = jax.random.randint(k2, (slots,), 1, npages * ps + 1)
        # The engine passes one row past the table for a slot whose budget
        # ran out mid-chunk: here the first slot, before a live one, and
        # the last.
        lengths = lengths.at[0].set(npages * ps + 1).at[-1].set(
            npages * ps + 1)
        with highest():
            ref = jax.jit(paged_decode_reference)(q, kp, vp, pt, lengths,
                                                  **sc)
        for ppb in (1, None):
            out = jax.jit(lambda *a, ppb=ppb: flash_decode_fwd(
                *a[:5], **a[5], pages_per_block=ppb, interpret=interpret))(
                q, kp, vp, pt, lengths, sc)
            compare(f"decode/{kv_dtype}/pages_per_block={ppb or 'chosen'}",
                    out, ref)

        q, kp, vp, pt, k2, sc = _paged_inputs(key, b=2, rows=(chunk,),
                                              **dims)
        q_start = jax.random.randint(k2, (2,), 0, npages * ps - chunk + 1)
        with highest():
            ref = jax.jit(paged_prefill_reference)(q, kp, vp, pt, q_start,
                                                   **sc)
        out = jax.jit(lambda *a: flash_prefill_fwd(
            *a[:5], **a[5], interpret=interpret))(q, kp, vp, pt, q_start, sc)
        compare(f"prefill/{kv_dtype}/C={chunk}", out, ref)

        q, kp, vp, pt, k2, sc = _paged_inputs(key, b=slots, rows=(window,),
                                              **dims)
        pos = jax.random.randint(k2, (slots,), 0, npages * ps - window + 1)
        with highest():
            ref = jax.jit(paged_verify_reference)(q, kp, vp, pt, pos, **sc)
        for s in sorted({1, splits}):
            out = jax.jit(lambda *a, s=s: flash_verify_fwd(
                *a[:5], **a[5], num_splits=s, interpret=interpret))(
                q, kp, vp, pt, pos, sc)
            compare(f"verify/{kv_dtype}/T={window}/splits={s}", out, ref)
    return errors


def run_one_chip(cfg, params, *, seed: int, slots: int, max_len: int,
                 max_new: int, prompt_lens, expect_kernels: bool,
                 interpret: bool):
    """Phases 2-5 (see the module docstring) on ``cfg`` with ``params``."""
    prompts = make_prompts(cfg, prompt_lens, seed)
    kw = dict(max_new=max_new, max_len=max_len, slots=slots)
    t = time.perf_counter()
    tokens, engines = serve_gateway(cfg, [params], prompts, **kw)
    check_engine_programs(cfg, engines[0], prompts,
                          expect_kernels=expect_kernels)
    for eng in engines:
        free_pool(eng)
    t = _phase_done("gateway", t)
    reference = [tokens[i] for i in range(len(prompts))]
    _, spec_same = run_engine(cfg, params, prompts, reference, what="spec",
                              enable_spec_decode=True,
                              spec_tokens=SPEC_TOKENS, **kw)
    if min(spec_same) < 1:
        raise AssertionError("spec decode changed a first token, which "
                             "comes from the same prefill program")
    t = _phase_done("spec", t)
    run_engine(cfg, params, prompts, reference, what="int8",
               kv_cache_dtype="int8", **kw)
    t = _phase_done("int8", t)
    errors = check_kernels(cfg, slots=slots, max_len=max_len,
                           interpret=interpret)
    _phase_done("kernels", t)
    return errors


def _phase_done(name: str, t0: float) -> float:
    """Log a phase's wall seconds (compiles included); returns now."""
    now = time.perf_counter()
    log(f"phase {name}: {now - t0} s")
    return now


def run_replicas(cfg, params_per_replica, prompts, *, max_new: int,
                 max_len: int, slots: int):
    """The same requests through a gateway with one replica per entry of
    ``params_per_replica`` and through a one-replica gateway on the first;
    per-request tokens must match. Returns, per fleet replica, the devices
    holding its pool after serving and the requests it admitted."""
    kw = dict(max_new=max_new, max_len=max_len, slots=slots)
    fleet, engines = serve_gateway(cfg, params_per_replica, prompts, **kw)
    served = [eng.stats["admitted"] for eng in engines]
    pool_devices = [{d for leaf in jax.tree_util.tree_leaves(eng.pool)
                     for d in leaf.devices()} for eng in engines]
    for eng in engines:
        free_pool(eng)
    single, one = serve_gateway(cfg, params_per_replica[:1], prompts, **kw)
    for eng in one:
        free_pool(eng)
    diff = [i for i in fleet if fleet[i] != single[i]]
    log(f"replicas: requests admitted per replica {served}; pools on "
        f"{[sorted(str(d) for d in ds) for ds in pool_devices]}; "
        f"{len(prompts) - len(diff)}/{len(prompts)} requests token-identical "
        "to one replica")
    if diff:
        raise AssertionError(f"requests {diff} differ between the fleet "
                             "and one replica")
    return pool_devices, served


def _device_line(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-replica FleetRouter phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    cache_dir = enable_compile_cache()
    compile_s = [0.0, 0]

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration
            compile_s[1] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    devices = jax.devices()
    dev = _device_line(devices)
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        log("chip_smoke: no TPU found; there is no CPU fallback")
        return 1
    log(f"compile cache: {cache_dir}")

    cfg = smoke_config()
    log(f"config: {cfg.name} d_model {cfg.d_model} heads {cfg.num_heads}/"
        f"{cfg.num_kv_heads} head_dim {cfg.head_dim} d_ff {cfg.d_ff} vocab "
        f"{cfg.vocab_size} dtype {cfg.dtype}/{cfg.param_dtype} page_size "
        f"{cfg.page_size} attn {cfg.attn_impl} slots {SLOTS} max_len "
        f"{MAX_LEN}")
    cfg = fit_layers(cfg, slots=SLOTS, max_len=MAX_LEN)
    log(f"layers: {cfg.num_layers}")

    if args.four_chips:
        if len(devices) < 4:
            log(f"chip_smoke: --four-chips needs 4 devices, found "
                f"{len(devices)}")
            return 1
        devices = devices[:4]
        t = time.perf_counter()
        # Draw once and copy: compiled programs are keyed by device, so
        # drawing on each chip would compile every leaf's draw four times.
        params = [build_params(cfg, args.seed)]
        params += [jax.block_until_ready(jax.device_put(params[0], d))
                   for d in devices[1:]]
        nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params[0]))
        log(f"params: {nbytes} B per replica on {len(devices)} devices")
        t = _phase_done("params", t)
        prompts = make_prompts(cfg, PROMPT_LENS[:4] * (len(devices) * SLOTS
                                                       // 4), args.seed)
        pools, served = run_replicas(cfg, params, prompts, max_new=MAX_NEW,
                                     max_len=MAX_LEN, slots=SLOTS)
        _phase_done("replicas", t)
        if [{d} for d in devices] != pools or min(served) < 1:
            raise AssertionError("want replica i's pool on device i and "
                                 f"every replica serving: pools {pools}, "
                                 f"admitted {served}")
    else:
        t = time.perf_counter()
        params = build_params(cfg, args.seed)
        nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
        log(f"params: {nbytes} B")
        _phase_done("params", t)
        run_one_chip(cfg, params, seed=args.seed, slots=SLOTS,
                     max_len=MAX_LEN, max_new=MAX_NEW,
                     prompt_lens=PROMPT_LENS, expect_kernels=True,
                     interpret=False)

    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    log(f"peak_bytes_in_use: {peak}")
    log(f"compile seconds: {compile_s[0]} over {compile_s[1]} programs")
    log(f"total seconds: {time.perf_counter() - t_start}")
    print(json.dumps({"ok": True, "device": _device_line(devices)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
