"""The plain reference: the model's forward pass in float32 ``jax.numpy``.

It follows the published description of a Llama-style dense decoder, the
family both configurations belong to: pre-norm RMSNorm, rotary position
embedding on the two halves of each head (``rotate_half``), grouped-query
attention with query head ``h`` reading key/value head ``h // G``, causal
softmax, a SiLU-gated MLP, a final RMSNorm and an untied output matrix. No
kernels, no cache, no batching beyond padding: every sequence is run whole.

It imports nothing of the program and takes nothing the program made: the
weights are drawn again from the run seed (:mod:`bench.weights`), one layer
at a time, in the dtype they are served in and then widened to float32.
Matrix products run at ``precision="highest"``.

``fp8=True`` is the control: the same computation with every matmul input
(weights per tensor, activations per row) and the keys and values rounded
to float8 e4m3 with a scale, the precision below the bfloat16 the
configurations state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
LAYER_LEAVES = ("attn/wq", "attn/wk", "attn/wv", "attn/wo", "attn/norm",
                "ffn/w_gate", "ffn/w_up", "ffn/w_down", "ffn/norm")


def _shapes(m: dict) -> dict:
    d, h, kv, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                        m["head_dim"], m["d_ff"])
    return {"attn/wq": (d, h, hd), "attn/wk": (d, kv, hd),
            "attn/wv": (d, kv, hd), "attn/wo": (h, hd, d),
            "attn/norm": (d,), "ffn/w_gate": (d, ff), "ffn/w_up": (d, ff),
            "ffn/w_down": (ff, d), "ffn/norm": (d,)}


def _f8(x, axis=None):
    """Round to float8 e4m3 with a scale that maps the largest magnitude
    (of the tensor, or of each slice along ``axis``) to the format's
    largest value; returns float32."""
    amax = jnp.max(jnp.abs(x)) if axis is None else \
        jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (S, H, hd) at positions pos (S,): rotate the two halves."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv                # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class Reference:
    """Forward passes of configuration ``m`` with the weights of ``seed``."""

    def __init__(self, m: dict, seed: int, *, fp8: bool = False):
        self.m = m
        self.fp8 = fp8
        self.key = W.base_key(seed)
        shapes = _shapes(m)
        bf16 = jnp.bfloat16

        def draw_layer(key, layer):
            out = {}
            for name in LAYER_LEAVES:
                dt = jnp.float32 if name.endswith("norm") else bf16
                out[name] = W.draw_layer_leaf(
                    m, key, "layers/" + name, layer, shapes[name],
                    dt).astype(jnp.float32)
            return out

        self._draw_layer = jax.jit(draw_layer)
        self._layer = jax.jit(self._layer_fn)

    # -- one layer over a padded batch --------------------------------------
    def _mm(self, spec, x, w, w_q):
        if self.fp8:
            x = _f8(x, axis=-1)
            w = w_q
        return jnp.einsum(spec, x, w, precision=HI)

    def _layer_fn(self, h, w):
        m = self.m
        eps, theta = m["norm_eps"], m["rope_theta"]
        kv, g = m["num_kv_heads"], m["num_heads"] // m["num_kv_heads"]
        hd = m["head_dim"]
        wq = {k: (_f8(v) if self.fp8 and not k.endswith("norm") else v)
              for k, v in w.items()}

        def one(x_row):                                   # (S, d)
            s = x_row.shape[0]
            pos = jnp.arange(s)
            x = _rms(x_row, w["attn/norm"], eps)
            q = self._mm("sd,dhk->shk", x, w["attn/wq"], wq["attn/wq"])
            k = self._mm("sd,dhk->shk", x, w["attn/wk"], wq["attn/wk"])
            v = self._mm("sd,dhk->shk", x, w["attn/wv"], wq["attn/wv"])
            q, k = _rope(q, pos, theta), _rope(k, pos, theta)
            if self.fp8:
                k, v = _f8(k, axis=-1), _f8(v, axis=-1)
            q = q.reshape(s, kv, g, hd)
            sc = jnp.einsum("qkgd,skd->kgqs", q, k,
                            precision=HI) / jnp.sqrt(jnp.float32(hd))
            sc = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :],
                           sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)
            o = o.reshape(s, kv * g, hd)
            x_row = x_row + self._mm("shk,hkd->sd", o, w["attn/wo"],
                                     wq["attn/wo"])
            x = _rms(x_row, w["ffn/norm"], eps)
            gate = self._mm("sd,df->sf", x, w["ffn/w_gate"], wq["ffn/w_gate"])
            up = self._mm("sd,df->sf", x, w["ffn/w_up"], wq["ffn/w_up"])
            return x_row + self._mm("sf,fd->sd", jax.nn.silu(gate) * up,
                                    w["ffn/w_down"], wq["ffn/w_down"])

        return jax.lax.map(one, h)

    # -- the whole model -------------------------------------------------------
    def final_hidden(self, tokens: np.ndarray):
        """tokens (B, S) int -> (B, S, d) float32 after the final norm.
        Rows are independent; positions past a row's length are padding
        that causal attention keeps from earlier positions."""
        m = self.m
        emb = W.draw_layer_leaf(m, self.key, "embedding", None,
                                (m["vocab_size"], m["d_model"]),
                                jnp.bfloat16)
        h = emb[jnp.asarray(tokens)].astype(jnp.float32)
        del emb
        for layer in range(m["num_layers"]):
            h = self._layer(h, self._draw_layer(self.key, np.uint32(layer)))
        fn = W.draw_layer_leaf(m, self.key, "final_norm", None,
                               (m["d_model"],), jnp.float32)
        return _rms(h, fn, m["norm_eps"])

    def unembed(self):
        m = self.m
        u = W.draw_layer_leaf(m, self.key, "unembed", None,
                              (m["d_model"], m["vocab_size"]), jnp.bfloat16)
        u = u.astype(jnp.float32)
        return _f8(u) if self.fp8 else u

    def logits(self, hidden_rows, u):
        """(N, d) hidden rows -> (N, V) float32 logits."""
        x = _f8(hidden_rows, axis=-1) if self.fp8 else hidden_rows
        return jnp.einsum("nd,dv->nv", x, u, precision=HI)
