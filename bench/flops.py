"""Algorithmic operation and byte counts: the work the equations
require, whatever implements it.

Counts are for a decoder of ``layers`` blocks, each with attention
projections (``Hq`` query and ``Hkv`` key/value heads of ``head_dim``)
and an MLP of width ``d_ff`` (three matrices when gated, two
otherwise).  A multiply-add is 2 FLOPs.  Nothing is counted twice:
no padding, no recomputation, and the output head only for a position
whose token is sampled.  A query at position ``p`` (0-based) attends to
the ``p + 1`` columns ``0..p``.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Shape:
    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool
    bytes_per_value: int = 4

    @classmethod
    def of(cls, cfg, bytes_per_value: int = 4) -> "Shape":
        """From the program's model config (``repro.models.config``)."""
        return cls(cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                   cfg.hd, cfg.d_ff, cfg.vocab_size,
                   cfg.mlp_kind in ("swiglu", "geglu"), bytes_per_value)


def matmul_flops(s: Shape) -> int:
    """Per token per layer: Q, K, V, O projections and the MLP."""
    d, hd = s.d_model, s.head_dim
    attn = d * (s.n_heads * hd) * 2 + d * (s.n_kv_heads * hd) * 2
    mlp = (3 if s.gated else 2) * d * s.d_ff
    return 2 * (attn + mlp)


def attention_flops(s: Shape, columns: int) -> int:
    """Per query per layer: scores and weighted values over
    ``columns`` cached positions."""
    return 4 * s.n_heads * s.head_dim * columns


def head_flops(s: Shape) -> int:
    return 2 * s.d_model * s.vocab


def span_flops(s: Shape, start: int, end: int) -> int:
    """Positions ``start..end-1`` of one sequence through every layer
    (no output head): each attends to its own prefix."""
    n = end - start
    cols = (start + end + 1) * n // 2          # sum of (p + 1)
    return s.layers * (n * matmul_flops(s) + 4 * s.n_heads * s.head_dim
                       * cols)


def token_flops(s: Shape, pos: int, sampled: bool = True) -> int:
    """One decode position ``pos``, with its output head if sampled."""
    return span_flops(s, pos, pos + 1) + (head_flops(s) if sampled else 0)


def kv_bytes(s: Shape, columns: int) -> int:
    """Bytes of keys and values of ``columns`` positions, all layers."""
    return (s.layers * columns * s.n_kv_heads * s.head_dim * 2
            * s.bytes_per_value)


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> tuple:
    """(least seconds, 'compute' | 'memory'): the larger of the two
    bounds, and which one it is."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
