"""Pallas TPU kernel: Segment Means reduction (paper Alg. 2).

Memory-bound: one pass (N_p, D) → (L, D).  Exists to fuse the per-block
compression with the residual-stream write — on TPU the block output is
already streaming through VMEM, so computing the means there saves a full
HBM round-trip over a separate jnp.mean (see EXPERIMENTS.md §Perf).

Grid (B, L, D/blk_d): each program mean-reduces one (segment ×
feature-block) tile.  Ragged partitions (N_p % L != 0) follow the
paper's Eq. 8 split — the first L-1 even segments stream through the
kernel, the oversized last segment is mean-reduced in jnp with one
static slice (matching `repro.core.segment_means`, which is also the
kernel's oracle).
``interpret=None`` auto-detects the platform (``kernels.dispatch``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import default_interpret


def _kernel(x_ref, o_ref):
    o_ref[...] = jnp.mean(
        x_ref[...].astype(jnp.float32), axis=0, keepdims=True
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("L", "block_d", "interpret"))
def segment_means_op(x, *, L: int, block_d: int = 512,
                     interpret: bool | None = None):
    """x (B, N_p, D) -> (B, L, D) segment means, any 1 <= L <= N_p."""
    interpret = default_interpret(interpret)
    b, n, d = x.shape
    assert 1 <= L <= n, (L, n)
    if n % L:
        # Eq. 8 ragged split: L-1 even segments + one oversized tail.
        s = n // L
        tail = jnp.mean(x[:, s * (L - 1):].astype(jnp.float32), axis=1,
                        keepdims=True).astype(x.dtype)
        if L == 1:
            return tail
        head = segment_means_op(x[:, : s * (L - 1)], L=L - 1,
                                block_d=block_d, interpret=interpret)
        return jnp.concatenate([head, tail], axis=1)
    seg = n // L
    # a feature tile narrower than d must be a multiple of the 128-lane
    # width (768 with the default 512 -> 384); without such a divisor
    # one full-width tile is the legal choice
    block_d = next((c for c in range(min(block_d, d), 127, -1)
                    if d % c == 0 and c % 128 == 0), d)

    # segments as their own axis, (B, L, seg, D) -> (B, L, 1, D): every
    # block's last two dims are (whole segment | unit axis, feature tile)
    out = pl.pallas_call(
        _kernel,
        grid=(b, L, d // block_d),
        in_specs=[pl.BlockSpec((None, None, seg, block_d),
                               lambda i, l, j: (i, l, 0, j))],
        out_specs=pl.BlockSpec((None, None, 1, block_d),
                               lambda i, l, j: (i, l, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, L, 1, d), x.dtype),
        interpret=interpret,
        name="segment_means",
    )(x.reshape(b, L, seg, d))
    return out[:, :, 0]
