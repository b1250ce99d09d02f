"""Matrix products at a stated precision, and causal attention.

``mode`` is the precision of every product in a reference pass:

* ``highest``: float32 products (``Precision.HIGHEST``);
* ``high``: ``Precision.HIGH``, which a TPU computes as three bfloat16
  passes (a CPU computes it in float32, like ``highest``);
* ``bf16``: bfloat16 operands, float32 sums (a TPU's default).

The lower modes serve as the control that the correctness check has to
refuse.  ``high`` is left to the compiler and not written out as a
split into high and low bfloat16 parts: XLA may drop the float32 ->
bfloat16 -> float32 round trip such a split is made of (it allows
excess precision), which turns the split into one bfloat16 pass.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

MODES = ("highest", "high", "bf16")


def ein(spec: str, a, b, mode: str):
    f32 = jnp.float32
    if mode in ("highest", "high"):
        prec = (lax.Precision.HIGHEST if mode == "highest"
                else lax.Precision.HIGH)
        return jnp.einsum(spec, a, b, precision=prec,
                          preferred_element_type=f32)
    if mode == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16), preferred_element_type=f32)
    raise ValueError(f"unknown precision mode {mode!r}")


def linear(x, p, mode: str):
    """x (N, d_in) @ p['w'] (d_in, d_out) (+ p['b'])."""
    y = ein("nd,de->ne", x, p["w"], mode)
    return y + p["b"] if "b" in p else y


def causal_attention(q, k, v, mode: str, qblock: int = 512):
    """Softmax attention of q (N, Hq, hd) over k, v (N, Hkv, hd), query
    ``i`` seeing columns ``0..i``; query head ``h`` reads key/value
    head ``h // (Hq / Hkv)``.  Queries go in blocks of ``qblock`` so
    the score tensor stays small at long contexts."""
    n, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(n, hkv, g, hd) * (hd ** -0.5)
    outs = []
    for s in range(0, n, qblock):
        e = min(n, s + qblock)
        sc = ein("qkgd,mkd->kgqm", qg[s:e], k[:e], mode)
        mask = (np.arange(s, e)[:, None] >= np.arange(e)[None, :])
        sc = jnp.where(mask, sc, -jnp.inf)
        sc = sc - sc.max(-1, keepdims=True)
        p = jnp.exp(sc)
        p = p / p.sum(-1, keepdims=True)
        o = ein("kgqm,mkd->qkgd", p, v[:e], mode)
        outs.append(o.reshape(e - s, hq, hd))
    return jnp.concatenate(outs, axis=0)


def bucket(n: int, step: int) -> int:
    return -(-n // step) * step


ROWS = 512                             # logits rows per reference call
_JITS: dict = {}


def run_rows(key, fn, conf: dict, weights, tokens, rows, mode: str):
    """Run ``fn(weights, tokens, rows)`` jitted per (family, mode) at
    few shapes: tokens padded up to a multiple of the configuration's
    ``reference_tokens`` (its longest sequence, so one shape serves
    every request), rows in calls of ``ROWS``.  Padding tokens go at
    the end, where a causal pass never sees them; padding rows repeat
    row 0 and are dropped."""
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    nt = bucket(len(tokens), int(conf["reference_tokens"]))
    tk = np.zeros(nt, np.int32)
    tk[:len(tokens)] = tokens
    # the configuration's contents, not its name: a caller may read
    # the same model with a value replaced
    jkey = (key, json.dumps(conf, sort_keys=True), mode)
    if jkey not in _JITS:
        _JITS[jkey] = jax.jit(lambda w, t, r: fn(conf, w, t, r, mode))
    out = []
    for i in range(0, len(rows), ROWS):
        part = rows[i:i + ROWS]
        rw = np.zeros(ROWS, np.int32)
        rw[:len(part)] = part
        got = _JITS[jkey](weights, jnp.asarray(tk), jnp.asarray(rw))
        out.append(np.asarray(got)[:len(part)])
    return np.concatenate(out)
