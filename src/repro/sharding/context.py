"""ShardedPrismContext — the PRISM protocol under ``shard_map``.

Runs inside a shard_map body whose activations are sharded
(batch over pod×data, sequence over ``model``).  The per-block exchange is:

  * PRISM:   ``lax.all_gather`` of the (B, L, D) segment means over
             ``model`` — (P-1)·L·D elements of useful payload per device
             per block (paper §IV-C);
  * Voltage: ``lax.all_gather`` of the full (B, N/P, D) partition —
             (P-1)·N·D/P elements (baseline [20]);
  * window:  ring ``ppermute`` halo of the last W tokens (gemma3 local
             layers need no Segment Means — DESIGN.md §6);
  * SSM:     constant-size state handoff via all_gather of (logA, U)
             chunk summaries;
  * MoE:     expert-parallel double ``all_to_all``;
  * sLSTM:   full-sequence gather (PRISM-inapplicable, DESIGN.md §6).

Own-partition segment means are *included* in the gathered tensor (static
shapes) but neutralized with g=0 — mathematically identical to the paper's
concat-of-others (Eq. 6) because a zero repeat count contributes nothing
to the scaling-aware softmax.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core.segment_means import segment_means, segment_sizes, segment_bounds
from ..core.protocol import PrismConfig
from ..models.context import SeqContext, AugmentedKV
from ..models.layers import AttnSpec


class ShardedPrismContext(SeqContext):
    def __init__(self, cfg: PrismConfig, *, axis: str = "model",
                 n_shards: int, seq_shards: tuple = (),
                 prefix_len: int = 0, global_start: int = 0):
        """``axis``: mesh axis carrying PRISM's P (= ``n_shards``).
        ``seq_shards``: extra mesh axes the sequence is sharded over
        *in addition* to ``axis`` (long_500k shards sequence over
        data×model).  The combined shard count is P for the protocol."""
        # bind Eq. 16's P to the actual shard count: L = N/(CR·P) must see
        # the mesh's sequence parallelism, not the caller's placeholder P
        self.cfg = cfg.with_(P=n_shards) if cfg.P != n_shards else cfg
        self.axis = axis
        self.seq_axes = tuple(seq_shards) + (axis,)
        self.P = n_shards
        self.prefix_len = prefix_len
        self.global_start = global_start

    # -- helpers -----------------------------------------------------------

    def _index(self):
        idx = lax.axis_index(self.seq_axes[0])
        for a in self.seq_axes[1:]:
            idx = idx * lax.axis_size(a) + lax.axis_index(a)
        return idx

    def _gather(self, x):
        """all_gather over the (combined) sequence axes -> leading shard dim."""
        g = lax.all_gather(x, self.seq_axes[-1], axis=0, tiled=False)
        for a in reversed(self.seq_axes[:-1]):
            g = lax.all_gather(g, a, axis=0, tiled=True)
        return g                                   # (P, ...)

    # -- attention ---------------------------------------------------------

    def augment(self, x, spec: AttnSpec):
        b, n_loc, d = x.shape
        p = self.P
        p_idx = self._index()
        start = self.global_start + p_idx * n_loc
        row_pos = start + jnp.arange(n_loc)

        if spec.window is not None:
            return self._augment_window(x, spec, n_loc, start, row_pos)
        if self.cfg.mode == "voltage":
            return self._augment_voltage(x, spec, n_loc, row_pos)
        return self._augment_prism(x, spec, n_loc, p_idx, start, row_pos)

    def _augment_voltage(self, x, spec, n_loc, row_pos):
        b = x.shape[0]
        xg = self._gather(x)                       # (P, B, n_loc, D)
        n = self.P * n_loc
        x_hat = jnp.moveaxis(xg, 0, 1).reshape(b, n, x.shape[-1])
        col = jnp.arange(n) + self.global_start
        vis = self._vis(row_pos, col, col, spec)
        return x, AugmentedKV(x_hat, None, vis, row_pos, col,
                              col_lo=col, col_hi=col)

    def _augment_prism(self, x, spec, n_loc, p_idx, start, row_pos):
        b, _, d = x.shape
        cfg = self.cfg
        n_global = self.P * n_loc
        L = cfg.landmarks(n_global)
        z = segment_means(x, L)                    # (B, L, D)
        zg = self._gather(z)                       # (P, B, L, D)
        z_all = jnp.moveaxis(zg, 0, 1).reshape(b, self.P * L, d)
        x_hat = jnp.concatenate([x, z_all], axis=1)    # (B, n_loc + P·L, D)

        sizes = jnp.asarray(segment_sizes(n_loc, L), jnp.float32)
        lo0, hi0 = segment_bounds(n_loc, L)        # per-partition template
        shard_of = jnp.repeat(jnp.arange(self.P), L)
        offs = self.global_start + jnp.repeat(jnp.arange(self.P) * n_loc, L)
        z_lo = jnp.tile(jnp.asarray(lo0), self.P) + offs
        z_hi = jnp.tile(jnp.asarray(hi0), self.P) + offs
        # own-partition means: g = 0 (exact local columns already present)
        z_g = jnp.where(shard_of == p_idx, 0.0, jnp.tile(sizes, self.P))

        col_lo = jnp.concatenate([row_pos, z_lo])
        col_hi = jnp.concatenate([row_pos, z_hi])
        g = jnp.concatenate([jnp.ones((n_loc,), jnp.float32), z_g])
        col_pos = jnp.concatenate(
            [row_pos.astype(jnp.float32), (z_lo + z_hi) / 2.0])
        vis = self._vis(row_pos, col_lo, col_hi, spec)
        vis = vis & (g > 0)[None, :]
        # g = 0 columns need no mask entry for the kernel: log g = -inf
        # already zeroes them, so (col_lo, col_hi) alone reproduce vis
        return x, AugmentedKV(x_hat, g, vis, row_pos, col_pos,
                              col_lo=col_lo, col_hi=col_hi)

    def _augment_window(self, x, spec, n_loc, start, row_pos):
        """Ring halo: gather the previous ceil(W / n_loc) shards' tokens."""
        b, _, d = x.shape
        w = spec.window
        hops = min(self.P - 1, -(-w // n_loc))     # ceil
        tails = []
        for h in range(hops, 0, -1):
            perm = [(s, s + h) for s in range(self.P - h)]
            tails.append(self._ring_permute(x, perm))
        x_hat = jnp.concatenate(tails + [x], axis=1)
        m = (hops + 1) * n_loc
        col = start - hops * n_loc + jnp.arange(m)
        vis = self._vis(row_pos, col, col, spec)
        vis = vis & (col >= 0)[None, :]            # halo beyond seq start
        return x, AugmentedKV(x_hat, None, vis, row_pos,
                              jnp.maximum(col, 0))

    def _ring_permute(self, x, perm):
        """ppermute over the combined sequence axes (flattened index)."""
        if len(self.seq_axes) == 1:
            return lax.ppermute(x, self.seq_axes[0], perm)
        # combined-axis permute: gather then select is wasteful; for the
        # multi-axis case (long_500k) halo hops stay within the minor axis
        # except at boundaries — implement as permute on the minor axis and
        # a corrective permute on the major axis for the wrap column.
        minor = self.seq_axes[-1]
        major = self.seq_axes[0]
        pm = lax.axis_size(minor)
        # shift-by-h on the flattened index decomposes into minor shift and
        # major carry; for h < pm (always true here) one carry at most.
        h = perm[0][1] - perm[0][0]
        shifted = lax.ppermute(
            x, minor, [(s, s + h) for s in range(pm - h)])
        carried = lax.ppermute(
            x, minor, [(pm - h + i, i) for i in range(h)])
        carried = lax.ppermute(
            carried, major,
            [(s, s + 1) for s in range(lax.axis_size(major) - 1)])
        idx_minor = lax.axis_index(minor)
        return jnp.where(idx_minor < h, carried, shifted)

    def _vis(self, row_pos, col_lo, col_hi, spec):
        r = row_pos[:, None]
        if spec.causal:
            vis = col_hi[None, :] <= r
            if self.prefix_len > 0:
                vis = vis | (col_hi[None, :] < self.prefix_len)
        else:
            vis = jnp.ones((row_pos.shape[0], col_lo.shape[0]), bool)
        if spec.window is not None:
            vis = vis & (col_lo[None, :] > r - spec.window)
        return vis

    # -- SSM ----------------------------------------------------------------

    def state_handoff(self, log_a_tot, u_tot):
        la = self._gather(log_a_tot)               # (P, B, H)
        u = self._gather(u_tot)                    # (P, B, H, dk, dv)
        p_idx = self._index()

        def step(carry, xs):
            la_q, u_q = xs
            new = jnp.exp(la_q)[..., None, None] * carry + u_q
            return new, carry                      # emit EXCLUSIVE prefix
        _, prefixes = lax.scan(step, jnp.zeros_like(u[0]), (la, u))
        return jnp.take(prefixes, p_idx, axis=0)   # (B, H, dk, dv)

    def gather_sequence(self, x):
        g = self._gather(x)                        # (P, B, n_loc, D)
        return jnp.moveaxis(g, 0, 1).reshape(
            x.shape[0], -1, x.shape[-1])

    def take_local(self, y_full):
        n_loc = y_full.shape[1] // self.P
        start = self._index() * n_loc
        return lax.dynamic_slice_in_dim(y_full, start, n_loc, axis=1)

    def prev_tail(self, x, size: int):
        tail = x[:, -size:]
        perm = [(s, s + 1) for s in range(self.P - 1)]
        return self._ring_permute_simple(tail, perm)

    def last_shard(self, x):
        """Broadcast the final shard's value to all shards (psum of a
        one-hot-masked value — one small collective per decode-cache leaf)."""
        sel = (self._index() == self.P - 1)
        masked = jnp.where(sel, x.astype(jnp.float32), 0.0)
        for a in self.seq_axes:
            masked = lax.psum(masked, a)
        return masked.astype(x.dtype)

    def _ring_permute_simple(self, x, perm):
        if len(self.seq_axes) == 1:
            return lax.ppermute(x, self.seq_axes[0], perm)
        return self._ring_permute(x, perm)

    # -- MoE -----------------------------------------------------------------

    def expert_exchange(self, buf):
        """(E, cap, D) -> (E_local, P·cap, D) via tiled all_to_all."""
        ax = self.axis
        p = lax.axis_size(ax)
        out = lax.all_to_all(buf, ax, split_axis=0, concat_axis=1, tiled=True)

        def undo(y):
            return lax.all_to_all(y, ax, split_axis=1, concat_axis=0,
                                  tiled=True)
        return out, undo
