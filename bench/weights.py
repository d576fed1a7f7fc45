"""Random weights from the run seed, the same for the program and the
reference.

Every value is a function of (seed, leaf path, layer) alone: the program's
whole tree is drawn on the device in one jitted call, in the dtypes it
serves, and the reference draws any one layer again by itself, so it never
takes an array the program has held. Matrices are uniform with standard
deviation 1/sqrt(fan-in) (the embedding 1); norm weights are uniform in
[0.5, 1.5], so that a norm applied wrongly shows.

Each leaf path has a rule in the table of its configuration's architecture
(``LEAVES`` of ``bench/architectures/<name>.py``): the size key of ``m``
that is its fan-in, a function of ``m`` that gives the fan-in, or one of
the kinds "norm", "embedding" and "heads_x_head_dim". The table below is
the dense decoder's, and the default.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

# Leaf path -> the model size its fan-in is, or "norm", or "embedding"
# (the dense GQA decoder's leaves).
LEAVES = {
    "embedding": "embedding",
    "unembed": "d_model",
    "final_norm": "norm",
    "layers/attn/wq": "d_model",
    "layers/attn/wk": "d_model",
    "layers/attn/wv": "d_model",
    "layers/attn/wo": "heads_x_head_dim",
    "layers/attn/norm": "norm",
    "layers/ffn/w_gate": "d_model",
    "layers/ffn/w_up": "d_model",
    "layers/ffn/w_down": "d_ff",
    "layers/ffn/norm": "norm",
}


def base_key(seed: int):
    """A PRNG key from any non-negative whole number (wider than 32 bits
    too): its low and high words are folded in one after the other."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, seed >> 64):
        key = jax.random.fold_in(key, np.uint32(word & 0xFFFFFFFF))
    return key


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, np.uint32(zlib.crc32(path.encode())))


def _scale(m: dict, path: str, leaves: dict) -> tuple[str, float]:
    rule = leaves.get(path)
    if rule is None:
        raise KeyError(f"no weight rule for leaf {path!r}")
    if callable(rule):
        fan_in = rule(m)
    elif rule in ("norm", "embedding"):
        return rule, 1.0
    elif rule == "heads_x_head_dim":
        fan_in = m["num_heads"] * m["head_dim"]
    else:
        fan_in = m[rule]
    return "matrix", fan_in ** -0.5


def _draw(key, shape, kind: str, std: float, dtype):
    u = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
    if kind == "norm":
        return (1.0 + 0.5 * u).astype(dtype)
    return (u * (std * math.sqrt(3.0))).astype(dtype)


def draw_layer_leaf(m: dict, key, path: str, layer: int, shape, dtype,
                    leaves: dict = LEAVES):
    """Layer ``layer`` of a stacked leaf (``shape`` without the layer
    axis), or a whole unstacked leaf when ``layer`` is None, by the rule
    ``leaves`` gives its path."""
    kind, std = _scale(m, path, leaves)
    k = _leaf_key(key, path)
    if layer is not None:
        k = jax.random.fold_in(k, jnp.asarray(layer, jnp.uint32))
    return _draw(k, tuple(shape), kind, std, dtype)


def program_params(m: dict, abstract_tree, seed: int, leaves: dict = LEAVES):
    """The program's whole parameter tree, drawn on the default device in
    one jitted call by the rules of ``leaves``. ``abstract_tree`` gives
    each leaf's shape and dtype; leaves under ``layers`` carry a leading
    layer axis."""

    def build(key):
        def go(node, path):
            if isinstance(node, dict):
                return {k: go(v, f"{path}/{k}" if path else k)
                        for k, v in node.items()}
            kind, std = _scale(m, path, leaves)
            k = _leaf_key(key, path)
            if path.startswith("layers/"):
                keys = jax.vmap(lambda i: jax.random.fold_in(k, i))(
                    jnp.arange(node.shape[0], dtype=jnp.uint32))
                return jax.vmap(lambda kk: _draw(
                    kk, node.shape[1:], kind, std, node.dtype))(keys)
            return _draw(k, node.shape, kind, std, node.dtype)
        return go(abstract_tree, "")

    return jax.jit(build)(base_key(seed))
