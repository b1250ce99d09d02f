"""Llama-family decoder (Yi-6B, arXiv:2403.04652), plain float32 forward.

Token embedding with no position table; pre-norm blocks of RMSNorm,
grouped-query causal attention with rotary positions (the half-split
rotation, frequencies ``rope_theta ** (-2i / head_dim)``), and a SwiGLU
MLP ``down(silu(gate(x)) * up(x))``; a final RMSNorm and an untied
output head.  ``rms_norm_eps`` is the one the configuration file
states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .common import causal_attention, ein, linear, run_rows

FIELDS = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
          "n_heads": "num_attention_heads",
          "n_kv_heads": "num_key_value_heads", "hd": "head_dim",
          "d_ff": "intermediate_size", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta"}


def rms_norm(x, p, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def rotary(x, theta: float):
    """x (N, H, hd): rotate each half-split pair by position * freq."""
    n, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _forward(conf, w, tokens, rows, mode):
    n = tokens.shape[0]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, eps = conf["head_dim"], conf["rms_norm_eps"]
    theta = conf["rope_theta"]
    x = w["embed"]["table"][tokens]

    def block(x, p):
        a = rms_norm(x, p["ln1"], eps)
        q = rotary(linear(a, p["attn"]["wq"], mode).reshape(n, hq, hd), theta)
        k = rotary(linear(a, p["attn"]["wk"], mode).reshape(n, hkv, hd), theta)
        v = linear(a, p["attn"]["wv"], mode).reshape(n, hkv, hd)
        o = causal_attention(q, k, v, mode).reshape(n, hq * hd)
        x = x + linear(o, p["attn"]["wo"], mode)
        m = rms_norm(x, p["ln2"], eps)
        gate = linear(m, p["mlp"]["gate"], mode)
        up = linear(m, p["mlp"]["up"], mode)
        return x + linear(jax.nn.sigmoid(gate) * gate * up,
                          p["mlp"]["down"], mode), None

    x, _ = lax.scan(block, x, w["scan"][0])
    x = rms_norm(x[rows], w["final_norm"], eps)
    return ein("rd,dv->rv", x, w["lm_head"]["w"], mode)


def logits_at(conf: dict, weights, tokens, rows, mode: str = "highest"):
    return run_rows("llama", _forward, conf, weights, tokens, rows, mode)
