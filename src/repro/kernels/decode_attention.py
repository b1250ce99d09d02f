"""Pallas TPU kernel: fused single-token (flash-decode) GQA attention
over the local KV-cache shard, with the PRISM means columns folded in.

This is the serving hot path: the continuous-batching engine calls it
once per layer per generated token.  Design points:

  * **Partial stats out, not outputs.**  The kernel emits the running
    softmax statistics ``(m, l, acc)`` — O(B·Hq·hd), independent of the
    cache capacity — so the existing ``pmax``/``psum`` cross-shard
    combine in ``runtime/serve.py`` is untouched and the exact
    distributed flash-decode stays *exact*.
  * **Per-row validity.**  Continuous-batching slots decode at
    independent depths; ``valid (B, M)`` carries each row's column
    visibility (idle slots: all-False).  A row with no valid column
    anywhere yields ``l = 0`` (its exp terms are re-zeroed), which the
    combine maps to a finite zero output.
  * **Prism means in-kernel.**  In ``prism`` decode mode the cached
    Segment-Means K/V ride along as extra K-blocks with a ``+log g``
    column bias (Eq. 14 as an additive logit term) — the per-step
    ``jnp.concatenate`` of the cache shard with the means cache (a
    cache-capacity-sized HBM allocation per layer per token) disappears.
  * **GQA in the grid.**  Grid (B, Hkv, K-blocks): each program attends
    the ``grp = Hq/Hkv`` query heads of one KV head against one K/V
    tile, so grouped heads share tiles without materializing the repeat.

``decode_stats_reference`` is the pure-jnp oracle — the same two-pass
(local columns, then means columns) stat merge, also concatenate-free,
and what ``backend='jnp'`` serves with on CPU/GPU.  See EXPERIMENTS.md
§Perf for the measured win.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.attention import _gqa_logits, _gqa_output
from .ops import _pad_to
from .prism_attention import NEG
from .dispatch import default_interpret


# --------------------------------------------------------------------------
# jnp oracle: two-pass partial stats + merge (no concatenate)
# --------------------------------------------------------------------------

def partial_softmax_stats(q, k, v, bias, scale):
    """Softmax partial stats over one column set.  q (B,1,Hq,hd);
    k,v (B,M,Hkv,hd); bias (B,M) additive logits (NEG = dead column).
    Returns m, l: (B,Hq,1,1) f32 and acc: (B,1,Hq,hd) f32.  Rows with
    every column dead come back as (m=NEG, l=0, acc=0)."""
    s = _gqa_logits(q, k, scale).astype(jnp.float32)      # (B,Hq,1,M)
    s = s + bias[:, None, None, :].astype(jnp.float32)
    m_p = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m_p)
    p = jnp.where(s > NEG / 2, p, 0.0)                    # all-dead -> l=0
    l_p = jnp.sum(p, axis=-1, keepdims=True)
    acc_p = _gqa_output(p.astype(v.dtype), v).astype(jnp.float32)
    return m_p, l_p, acc_p


def merge_stats(a, b):
    """Combine two partial-stat triples over disjoint column sets —
    the associative flash-softmax merge (what lax.pmax/psum do across
    shards, here across the local/means passes of one shard).  Shape-
    generic over the query count: m, l (B,Hq,Nq,1), acc (B,Nq,Hq,hd)
    — the chunked-prefill pass merges Nq > 1 queries at once."""
    m_a, l_a, acc_a = a
    m_b, l_b, acc_b = b
    m = jnp.maximum(m_a, m_b)
    c_a = jnp.exp(m_a - m)
    c_b = jnp.exp(m_b - m)
    l = l_a * c_a + l_b * c_b
    acc = (acc_a * jnp.swapaxes(c_a[..., 0], 1, 2)[..., None]
           + acc_b * jnp.swapaxes(c_b[..., 0], 1, 2)[..., None])
    return m, l, acc


def chunk_softmax_stats(q, k, v, bias, scale):
    """Multi-query softmax partial stats with a *per-query* additive
    bias — the intra-chunk pass of chunked prefill (each chunk query
    sees a different causal prefix of the chunk's own columns).

    q (B,C,Hq,hd); k,v (B,M,Hkv,hd); bias (B,C,M) additive logits
    (NEG = dead column).  Returns m, l: (B,Hq,C,1) f32 and
    acc: (B,C,Hq,hd) f32 — merge_stats/``_combine_exact`` compatible."""
    s = _gqa_logits(q, k, scale).astype(jnp.float32)      # (B,Hq,C,M)
    s = s + bias[:, None].astype(jnp.float32)
    m_p = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m_p)
    p = jnp.where(s > NEG / 2, p, 0.0)                    # all-dead -> l=0
    l_p = jnp.sum(p, axis=-1, keepdims=True)
    acc_p = _gqa_output(p.astype(v.dtype), v).astype(jnp.float32)
    return m_p, l_p, acc_p


def decode_stats_reference(q, k, v, valid, log_gz=None, kz=None, vz=None,
                           *, scale):
    """jnp oracle for ``flash_decode_stats``: local columns masked by
    ``valid`` (g=1), then the optional means columns with their
    per-row ``log_gz`` bias, merged without ever concatenating K/V."""
    bias = jnp.where(valid, 0.0, NEG)
    stats = partial_softmax_stats(q, k, v, bias, scale)
    if kz is not None:
        stats = merge_stats(stats, partial_softmax_stats(
            q, kz.astype(k.dtype), vz.astype(v.dtype), log_gz, scale))
    return stats


# --------------------------------------------------------------------------
# Pallas kernel
# --------------------------------------------------------------------------

def _decode_kernel(valid_ref, q_ref, k_ref, v_ref,
                   *rest, scale, nk_loc, nk):
    """One (batch row, KV head) flash-decode pass.  K-blocks are the
    innermost grid dim: indices [0, nk_loc) stream the local cache
    shard, [nk_loc, nk) the means columns (when present)."""
    if nk > nk_loc:
        loggz_ref, kz_ref, vz_ref = rest[:3]
        m_out, l_out, acc_out, m_scr, l_scr, acc_scr = rest[3:]
    else:
        m_out, l_out, acc_out, m_scr, l_scr, acc_scr = rest
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...]                                   # (grp, hd)

    def update(s, v):                                # s (grp, blk_k)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(s > NEG / 2, p, 0.0)           # dead cols -> l=0
        l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if nk_loc > 0:
        @pl.when(ki < nk_loc)
        def _local():
            k = k_ref[...]                           # (blk_k, hd)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            ok = valid_ref[...] != 0                 # (1, blk_k)
            update(jnp.where(ok, s, NEG), v_ref[...])

    if nk > nk_loc:
        @pl.when(ki >= nk_loc)
        def _means():
            kz = kz_ref[...]
            s = jax.lax.dot_general(
                q, kz, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            logg = loggz_ref[...].astype(jnp.float32)   # (1, blk_k)
            update(jnp.maximum(s + logg, NEG), vz_ref[...])

    @pl.when(ki == nk - 1)
    def _fin():
        m_out[...] = m_scr[...]
        l_out[...] = l_scr[...]
        acc_out[...] = acc_scr[...]


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret"))
def flash_decode_stats(
    q,                # (B, 1, Hq, hd) — the single decode token per slot
    k,                # (B, M, Hkv, hd) local cache shard
    v,                # (B, M, Hkv, hd)
    valid,            # (B, M) bool — per-row column visibility
    log_gz=None,      # (B, m) f32 — per-row means-column log repeat
                      #   counts; NEG on dead columns (own shard / future)
    kz=None,          # (B, m, Hkv, hd) Segment-Means K cache
    vz=None,          # (B, m, Hkv, hd)
    *,
    scale: float,
    block_k: int = 128,
    interpret: bool | None = None,
):
    """Fused flash-decode partial stats.  Returns (m, l, acc) with the
    ``flash_decode_combine`` shapes — m, l: (B,Hq,1,1) f32,
    acc: (B,1,Hq,hd) f32 — ready for the cross-shard pmax/psum combine
    (or, in prism mode, local normalization + owner select)."""
    interpret = default_interpret(interpret)
    b, nq, hq, hd = q.shape
    assert nq == 1, f"decode kernel is single-token (got Nq={nq})"
    _, m_loc, hkv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    grp = hq // hkv
    # one column tile for both column sets: below 128 lanes it must
    # span each padded set whole, so size it to the longer of the two
    m_max = max(m_loc, kz.shape[1] if kz is not None else 0)
    block_k = min(block_k, max(8, 1 << (m_max - 1).bit_length()))

    qk = q[:, 0].reshape(b, hkv, grp, hd)
    kt = _pad_to(k.swapaxes(1, 2), block_k, 2)       # (B,Hkv,M',hd)
    vt = _pad_to(v.swapaxes(1, 2), block_k, 2)
    # per-row vectors ride as (B, 1, M') so each block's last two dims
    # are (1, block_k): the full extent of the unit axis and a lane-
    # aligned column tile — a (1, block_k) block over (B, M') is refused
    # by the TPU compiler once B > 1
    validp = _pad_to(valid.astype(jnp.int32), block_k, 1)[:, None]
    nk_loc = kt.shape[2] // block_k

    has_means = kz is not None
    if has_means:
        kzt = _pad_to(kz.astype(k.dtype).swapaxes(1, 2), block_k, 2)
        vzt = _pad_to(vz.astype(v.dtype).swapaxes(1, 2), block_k, 2)
        lgz = _pad_to(log_gz.astype(jnp.float32), block_k, 1,
                      value=NEG)[:, None]
        nk_means = kzt.shape[2] // block_k
    else:
        nk_means = 0
    nk = nk_loc + nk_means

    def loc(ki):
        return jnp.minimum(ki, nk_loc - 1)

    def mns(ki):
        return jnp.clip(ki - nk_loc, 0, max(nk_means - 1, 0))

    in_specs = [
        pl.BlockSpec((None, 1, block_k),
                     lambda bi, h, ki: (bi, 0, loc(ki))),
        pl.BlockSpec((None, None, grp, hd), lambda bi, h, ki: (bi, h, 0, 0)),
        pl.BlockSpec((None, None, block_k, hd),
                     lambda bi, h, ki: (bi, h, loc(ki), 0)),
        pl.BlockSpec((None, None, block_k, hd),
                     lambda bi, h, ki: (bi, h, loc(ki), 0)),
    ]
    args = [validp, qk, kt, vt]
    if has_means:
        in_specs += [
            pl.BlockSpec((None, 1, block_k),
                         lambda bi, h, ki: (bi, 0, mns(ki))),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda bi, h, ki: (bi, h, mns(ki), 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda bi, h, ki: (bi, h, mns(ki), 0)),
        ]
        args += [lgz, kzt, vzt]

    stat_spec = pl.BlockSpec((None, None, grp, 1),
                             lambda bi, h, ki: (bi, h, 0, 0))
    acc_spec = pl.BlockSpec((None, None, grp, hd),
                            lambda bi, h, ki: (bi, h, 0, 0))
    m_o, l_o, acc_o = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale,
                          nk_loc=nk_loc, nk=nk),
        grid=(b, hkv, nk),
        in_specs=in_specs,
        out_specs=[stat_spec, stat_spec, acc_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, grp, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, grp, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, grp, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((grp, 1), jnp.float32),       # running max m
            pltpu.VMEM((grp, 1), jnp.float32),       # normalizer l
            pltpu.VMEM((grp, hd), jnp.float32),      # output accumulator
        ],
        interpret=interpret,
        name="flash_decode_stats",
    )(*args)

    m_p = m_o.reshape(b, hq)[:, :, None, None]
    l_p = l_o.reshape(b, hq)[:, :, None, None]
    acc_p = acc_o.reshape(b, hq, hd)[:, None]        # (B,1,Hq,hd)
    return m_p, l_p, acc_p
