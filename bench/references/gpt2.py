"""GPT-2 (Radford et al. 2019), plain float32 forward.

Learned absolute positions added to the token embedding; pre-norm
blocks of LayerNorm, causal multi-head attention with biases, and an
MLP with the tanh GELU (``gelu_new``); a final LayerNorm and the
embedding table as the output head.  ``layer_norm_epsilon`` is the one
the configuration file states.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .common import causal_attention, ein, linear, run_rows

FIELDS = {"n_layers": "n_layer", "d_model": "n_embd", "n_heads": "n_head",
          "n_kv_heads": "n_head", "d_ff": "n_inner",
          "vocab_size": "vocab_size", "max_seq": "n_positions"}


def layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                     * (x + 0.044715 * x ** 3)))


def _forward(conf, w, tokens, rows, mode):
    n, h = tokens.shape[0], conf["n_head"]
    d = conf["n_embd"]
    hd, eps = d // h, conf["layer_norm_epsilon"]
    x = w["embed"]["table"][tokens] + w["pos_embed"]["table"][:n]

    def block(x, p):
        a = layer_norm(x, p["ln1"], eps)
        q = linear(a, p["attn"]["wq"], mode).reshape(n, h, hd)
        k = linear(a, p["attn"]["wk"], mode).reshape(n, h, hd)
        v = linear(a, p["attn"]["wv"], mode).reshape(n, h, hd)
        o = causal_attention(q, k, v, mode).reshape(n, d)
        x = x + linear(o, p["attn"]["wo"], mode)
        m = layer_norm(x, p["ln2"], eps)
        u = gelu_new(linear(m, p["mlp"]["up"], mode))
        return x + linear(u, p["mlp"]["down"], mode), None

    x, _ = lax.scan(block, x, w["scan"][0])
    x = layer_norm(x[rows], w["final_norm"], eps)
    return ein("rd,vd->rv", x, w["embed"]["table"], mode)


def logits_at(conf: dict, weights, tokens, rows, mode: str = "highest"):
    return run_rows("gpt2", _forward, conf, weights, tokens, rows, mode)
