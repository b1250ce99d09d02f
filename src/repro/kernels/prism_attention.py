"""Pallas TPU kernel: PRISM scaling-aware flash attention.

The paper's restructured softmax (Eq. 13–15) folded into a streaming
(flash) softmax:

  * the repeat-count scaling ``Ψ ⊙ g`` becomes a ``+log g`` additive column
    bias inside the running max/sum — duplicates are never materialized, so
    K/V tiles stay ``N_p + (P-1)·L`` long (the entire compute saving);
  * the partition-aware causal mask (Eq. 17) is evaluated *positionally*
    from per-column (lo, hi) global-position ranges — no (Nq, M) mask array
    ever touches HBM;
  * ``g = 0`` (log g = -1e30) doubles as the padding mask for ragged tiles.

Tiling: grid (B·Hq, Nq/blk_q, M/blk_k), K innermost and sequential; the
running max ``m``, normalizer ``l`` and accumulator live in VMEM scratch
across K steps.  Block shapes default to 128 (MXU-aligned); hd up to 256
keeps q/k/v tiles ≤ 128·256·4B = 128 KiB each, comfortably inside the
~16 MiB v5e VMEM alongside scores and accumulator.

GQA is handled in the K/V BlockSpec index maps (query head → KV head), so
grouped heads share K/V tiles without materializing the repeat.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(rowpos_ref, collo_ref, colhi_ref, logg_ref,
            q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr,
            *, scale, causal, prefix_len, window, nk):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...]                                   # (blk_q, hd)
    k = k_ref[...]                                   # (blk_k, hd)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (blk_q, blk_k)

    row = rowpos_ref[...].astype(jnp.int32)          # (blk_q, 1)
    lo = collo_ref[...].astype(jnp.int32)            # (1, blk_k)
    hi = colhi_ref[...].astype(jnp.int32)
    logg = logg_ref[...].astype(jnp.float32)         # (1, blk_k)

    if causal:
        vis = hi <= row                              # (blk_q, blk_k)
        if prefix_len > 0:
            vis = vis | (hi < prefix_len)
    else:
        vis = jnp.ones(s.shape, bool)
    if window is not None:
        vis = vis & (lo > row - window)

    s = jnp.where(vis, s + logg, NEG)

    m_prev = m_scr[...]                              # (blk_q, 1)
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                           # (blk_q, blk_k)
    # fully-masked tiles: m_new == NEG makes exp(NEG-NEG)=1 — re-zero so
    # such rows end with l=0 and a 0 output instead of uniform garbage
    p = jnp.where(s > NEG / 2, p, 0.0)
    l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[...],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _fin():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def prism_flash_attention(
    q,            # (B, Hq, Nq, hd)
    k,            # (B, Hkv, M, hd)
    v,            # (B, Hkv, M, hd)
    log_g,        # (1, M) float32; NEG on padding columns
    col_lo,       # (1, M) int32
    col_hi,       # (1, M) int32
    row_pos,      # (Nq, 1) int32
    *,
    causal: bool,
    prefix_len: int = 0,
    window: int | None = None,
    scale: float,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    b, hq, nq, hd = q.shape
    _, hkv, m, _ = k.shape
    assert hq % hkv == 0
    grp = hq // hkv
    assert nq % block_q == 0 and m % block_k == 0, (nq, m, block_q, block_k)
    nqb, nkb = nq // block_q, m // block_k
    grid = (b * hq, nqb, nkb)

    def q_map(bh, qi, ki):
        return (bh // hq, bh % hq, qi, 0)

    def kv_map(bh, qi, ki):
        return (bh // hq, (bh % hq) // grp, ki, 0)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, prefix_len=prefix_len,
        window=window, nk=nkb)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, 1), lambda bh, qi, ki: (qi, 0)),
            pl.BlockSpec((1, block_k), lambda bh, qi, ki: (0, ki)),
            pl.BlockSpec((1, block_k), lambda bh, qi, ki: (0, ki)),
            pl.BlockSpec((1, block_k), lambda bh, qi, ki: (0, ki)),
            pl.BlockSpec((None, None, block_q, hd), q_map),
            pl.BlockSpec((None, None, block_k, hd), kv_map),
            pl.BlockSpec((None, None, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((b, hq, nq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # normalizer l
            pltpu.VMEM((block_q, hd), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
        name="prism_flash_attention",
    )(row_pos, col_lo, col_hi, log_g, q, k, v)
