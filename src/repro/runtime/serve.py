"""Sharded serving runtime: prefill + single-token decode with a
sequence-sharded KV cache.

The paper evaluates PRISM teacher-forced (full sequences — our *prefill*
path, where Segment-Means exchange replaces the Voltage all-gather).  For
incremental decode we add the two modes a TPU deployment needs:

  * ``exact``  — distributed flash-decoding: the cache is sharded over the
    sequence axes; each shard attends to its local cache shard and the
    partial softmax statistics (m, l, acc — O(B·H·hd), independent of N)
    are combined with pmax/psum.  This is the hardware adaptation of the
    paper's goal (never all-gather activations; per-device attention work
    is N/P, not N) and is *exact*.
  * ``prism``  — paper-faithful: each shard attends to its exact local
    cache plus the cached Segment-Means K/V of all remote shards
    (scaling-aware softmax); the output is the view of the shard that owns
    the newest position (the paper's device-owns-its-partition rule).
    On edge hardware this avoids any per-token collective; on TPU the
    owner-select psum costs the same as the exact combine, so ``exact``
    dominates for decode — recorded as a finding in EXPERIMENTS.md §Perf.

Cache layout (per layer, by block kind):
  attn/moe/shared_attn  {"k","v": (B, cap_l, Hkv, hd)} sharded over the
                        sequence axes on dim 1; prism mode adds
                        {"kz","vz": (B, P·L, Hkv, hd)} replicated means-KV.
  attn_local            {"k","v": (B, W, Hkv, hd)} ring buffer over the
                        window, replicated over ``model`` (W ≪ N/P).
  mlstm                 {"s": (B, H, dk, dv+1) f32} constant-size state.
  slstm                 {"s": (B, 3, H, hd) f32}.
  mamba                 {"s": (B, H, d_state, hd) f32,
                         "tail": (B, conv-1, d_in)} conv halo.

SSM/hybrid decode is attention-free: O(1) state per token — the reason
long_500k runs natively for xlstm/zamba2; dense archs earn it through the
PRISM-compressed (or sliding-window) cache.

Continuous batching: ``pos`` is a (B,) vector — each batch row (decode
*slot*) carries its own position, idle slots pass -1, and all cache
writes are owner-masked per row.  ``insert_cache_row`` splices a newly
prefilled request into a free slot mid-flight; ``repro.serving`` builds
the request-level engine on top of these primitives.

Chunked prefill: ``make_chunk_prefill_step`` compiles ONE program that
advances any subset of rows by up to ``chunk_len`` prompt tokens at
per-row runtime offsets, writing K/V (and, in prism mode, the
Segment-Means running state kz/vz/gz/zsum — over REAL columns only)
straight into the decode-layout cache.  The engine interleaves these
chunk calls with decode steps so long prompts no longer stall
in-flight decodes, and short prompts stop paying a full ``prefill_len``
pad-to-length forward.  Chunk attention is exact (flash-decode stats
over the already-written prefix + a per-query intra-chunk pass, merged
and psum-combined across shards), so engine output is token-identical
to the monolithic prefill path.

Token-packed serving: ``make_packed_step`` compiles ONE program per
engine tick that consumes a flat ragged batch of ``token_budget``
mixed tokens — every live decode token plus prompt-chunk tokens from
every mid-prefill request, each carrying its own ``(slot, pos, off,
is_prefill)`` metadata — and runs embed→blocks→logits once over the
real tokens (dead entries pass slot = -1).  Per-tick cost scales with
the number of REAL tokens instead of ``n_slots × chunk_len``, which is
what closes the saturation gap the chunked engine's FLOP clock
recorded against gang flushes.  Attention generalizes the chunk path's
two-pass stats trick to ragged multi-request packing: prior cache
columns go through the flash-decode stats path with per-token ``pos``,
intra-tick self columns through a segment-id-masked causal pass
(tokens of different requests never attend to each other), merged with
``merge_stats`` and psum-combined — packed ≡ chunked ≡ sequential
token-for-token in both decode modes.

Kernel routing: every decode path funnels through ``decode_attention``
below, which computes the per-shard partial softmax stats with the
fused Pallas flash-decode kernel (``kernels/decode_attention.py``) or
its two-pass jnp twin, per ``ServeHParams.backend``
(``kernels/dispatch.py``; 'auto' = compiled Pallas on TPU, jnp
elsewhere).  Prefill attention and the voltage means capture route
through ``prism_attention_op`` / ``segment_means_op`` behind the same
switch.  The dense jnp forms stay below as the test oracles.
"""
from __future__ import annotations

import collections
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import paging as _paging
from .paging import KVCache, PagedLayout, PrefixCache, make_paged_layout
from ..core.attention import (_gqa_logits, _gqa_output, log_repeats,
                              prism_attention)
from ..core.masks import NEG_INF
from ..core.protocol import PrismConfig
from ..core.segment_means import (segment_fill_counts, segment_means,
                                  segment_sizes, segment_bounds)
from ..kernels.decode_attention import (chunk_softmax_stats,
                                        decode_stats_reference,
                                        flash_decode_stats, merge_stats)
from ..kernels.dispatch import pallas_interpret, use_pallas
from ..kernels.ops import prism_attention_op
from ..kernels.segment_means import segment_means_op
from ..models import transformer as T
from ..models.config import ModelConfig
from ..models.layers import (AttnSpec, attn_project_q, attn_project_kv,
                             attn_output, dense, norm)
from ..models.layers import mlp as _mlp
from ..models.moe import moe_apply
from ..models.ssm import (mlstm_decode, slstm_decode, mamba2_decode,
                          mlstm_apply, slstm_apply, mamba2_apply)
from ..sharding.context import ShardedPrismContext
from ..sharding.rules import gather_tree, param_specs, spec_tree
from ..launch.mesh import batch_axes, mesh_axes
from .train import embed_vp, output_table


#: Trace-time counters, bumped once per (re)trace of each step-factory
#: body.  A serving engine that caches its compiled programs correctly
#: keeps every count bounded no matter how its ticks alternate between
#: packed / decode / chunk programs — the regression test in
#: ``tests/test_packed_step.py`` asserts exactly that.
trace_counts: collections.Counter = collections.Counter()

#: The feed-forward under the ``mlp`` scope.  Each ``jax.named_scope``
#: below (``kv_gather``, ``kv_write``, ``attn_kernel``, ``mlp``,
#: ``lm_head``) rides in the metadata of the operations it covers, so a
#: device trace can attribute them; it changes no computation.
mlp = jax.named_scope("mlp")(_mlp)


@dataclass(frozen=True)
class ServeHParams:
    decode_mode: str = "exact"       # 'exact' | 'prism'
    decode_tp: bool = False          # Megatron-TP position-wise ops (§Perf)
    ssm_chunk: int = 128
    means_cr: float = 16.0           # CR for the prism decode means cache
    backend: str = "auto"            # kernel dispatch: 'auto'|'pallas'|'jnp'
                                     # (see repro.kernels.dispatch)


# --------------------------------------------------------------------------
# layout
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ServeLayout:
    """Cache placement.  The default ``'aligned'`` placement: positions
    [0, prefill_len) are *prefill-aligned* — shard ``s`` holds positions
    ``[s·n_loc0, (s+1)·n_loc0)`` in its slots ``[0, n_loc0)``.  Decoded
    positions ``p >= prefill_len`` go round-robin: shard
    ``(p - n0) % n_seq``, slot ``n_loc0 + (p - n0) // n_seq`` —
    balanced writes, static shapes, and ``p = n0 - 1`` degrades exactly to
    rewriting the final prefill slot (the dry-run's one-step case).

    The ``'rr'`` placement (paged exact mode) round-robins EVERY
    position: shard ``p % n_seq``, slot ``p // n_seq``.  A gang page of
    consecutive per-shard columns then covers a CONTIGUOUS block of
    token positions across all shards — the property prefix caching
    needs for a shared page set to equal a position prefix.  Prism mode
    keeps 'aligned' (Segment-Means shard ownership requires contiguous
    per-shard position blocks), so paged prism shares no prefixes."""
    ba: tuple                        # batch mesh axes (may be empty)
    seq_axes: tuple                  # mesh axes sharding the cache sequence
    n_seq: int                       # total sequence shards (PRISM's P)
    cap: int                         # global cache capacity (tokens)
    cap_l: int                       # per-shard capacity
    prefill_len: int                 # tokens laid down by prefill (n0)
    L: int                           # segment means per shard (prism cache)
    placement: str = "aligned"       # 'aligned' | 'rr' (paged exact)

    @property
    def bspec(self):
        return self.ba if self.ba else None

    @property
    def n_loc0(self) -> int:
        return self.prefill_len // self.n_seq


def _layout_axes(mesh, batch: int) -> tuple:
    """(batch axes, sequence axes) the layout will use: 'model' shards
    the sequence when the batch divides over the batch axes; otherwise
    (long_500k: B=1) batch is replicated and the sequence shards over
    every axis.  The single source of the rule — launchers round their
    prompt/cap lengths via ``seq_shards`` below."""
    axes = mesh_axes(mesh)
    ba = batch_axes(mesh)
    nb = int(np.prod([axes[a] for a in ba]))
    if batch % nb == 0:
        return ba, ("model",)
    return (), tuple(mesh.axis_names)


def seq_shards(mesh, batch: int) -> int:
    """Sequence-shard count ``make_layout`` will pick for this
    (mesh, batch) — prompt/cap lengths must be multiples of it."""
    axes = mesh_axes(mesh)
    _, seq = _layout_axes(mesh, batch)
    return int(np.prod([axes[a] for a in seq]))


def make_layout(cfg: ModelConfig, mesh, batch: int, cap: int,
                hp: ServeHParams, prefill_len: int | None = None,
                placement: str = "aligned") -> ServeLayout:
    axes = mesh_axes(mesh)
    ba, seq = _layout_axes(mesh, batch)
    n_seq = int(np.prod([axes[a] for a in seq]))
    n0 = cap if prefill_len is None else prefill_len
    assert cap % n_seq == 0 and n0 % n_seq == 0 and n0 <= cap, (cap, n0, n_seq)
    assert placement in ("aligned", "rr"), placement
    cap_l = cap // n_seq
    L = max(1, int(n0 // (hp.means_cr * n_seq)))
    L = min(L, n0 // n_seq)
    return ServeLayout(ba, seq, n_seq, cap, cap_l, n0, L, placement)


def _paged_placement(hp: ServeHParams, paging) -> str:
    """Paged exact mode stores round-robin so pages cover contiguous
    position blocks (prefix sharing); paged prism keeps the aligned
    placement the Segment-Means shard ownership is defined over."""
    return "rr" if (paging is not None
                    and hp.decode_mode == "exact") else "aligned"


def grow_cache(cache, lay_from: ServeLayout, lay_to: ServeLayout):
    """Deprecated shim — use ``KVCache.grow_from`` (the engine's single
    cache-lifecycle object, built by ``make_kv_cache``).  Kept for the
    legacy padded admission tests; delegates to
    ``runtime.paging.grow_rows``."""
    return _paging.grow_rows(cache, lay_from, lay_to)


def insert_cache_row(dst, src, src_row, dst_row):
    """Deprecated shim — use ``KVCache.insert_row``.  Kept for the
    legacy padded admission tests; delegates to
    ``runtime.paging.splice_row`` (same semantics: a batch-dim splice
    of one cache row; jit with ``donate_argnums=(0,)``)."""
    return _paging.splice_row(dst, src, src_row, dst_row)


def reset_cache_row(cache, row):
    """Deprecated shim — use ``KVCache.reset_row``.  Delegates to
    ``runtime.paging.zero_row``."""
    return _paging.zero_row(cache, row)


# --------------------------------------------------------------------------
# cache pytree (+ shardings / ShapeDtypeStructs for the dry-run)
# --------------------------------------------------------------------------

def layer_cache_shape(cfg: ModelConfig, kind: str, lay: ServeLayout,
                      batch: int, hp: ServeHParams, dtype,
                      paging: PagedLayout | None = None):
    hkv, hd = cfg.n_kv_heads, cfg.hd
    d_in = cfg.d_model * cfg.ssm_expand
    if kind in ("attn", "moe", "shared_attn"):
        if paging is not None:
            # paged pool: a page gangs ``page_cols`` columns on every
            # seq shard (global dim 1 = page_cols·n_seq, sharded over
            # the seq axes exactly like the dense rows); the batch dim
            # is GONE — requests own page lists, not rows.  Prism's
            # Segment-Means running state rides in its own state-page
            # pool, one row per active request via ``state_map``.
            c = {"k": ((paging.n_pages, paging.pool_cap, hkv, hd), dtype),
                 "v": ((paging.n_pages, paging.pool_cap, hkv, hd), dtype)}
            if hp.decode_mode == "prism":
                m = lay.n_seq * lay.L
                s = paging.n_state_pages
                c["kz"] = ((s, m, hkv, hd), dtype)
                c["vz"] = ((s, m, hkv, hd), dtype)
                c["gz"] = ((s, m), jnp.float32)
                c["zsum"] = ((s, m, cfg.d_model), jnp.float32)
            return c
        # GLOBAL shapes (jit-level inputs); sharded over seq -> (B, cap_l)
        c = {"k": ((batch, lay.cap, hkv, hd), dtype),
             "v": ((batch, lay.cap, hkv, hd), dtype)}
        if hp.decode_mode == "prism":
            m = lay.n_seq * lay.L
            c["kz"] = ((batch, m, hkv, hd), dtype)
            c["vz"] = ((batch, m, hkv, hd), dtype)
            # per-request means-column repeat counts (the g of Eq. 14:
            # how many REAL tokens each kz/vz column averages; 0 = dead)
            # and the running per-segment sums of the block-input
            # activations that chunked prefill accumulates kz/vz from.
            c["gz"] = ((batch, m), jnp.float32)
            c["zsum"] = ((batch, m, cfg.d_model), jnp.float32)
        return c
    if paging is not None:
        raise ValueError(
            f"paged caches support position-addressed attention kinds "
            f"only (got block kind {kind!r})")
    if kind == "attn_local":
        w = min(cfg.window or lay.cap, lay.cap)
        return {"k": ((batch, w, hkv, hd), dtype),
                "v": ((batch, w, hkv, hd), dtype)}
    if kind == "mlstm":
        hdm = d_in // cfg.n_ssm_heads
        return {"s": ((batch, cfg.n_ssm_heads, hdm, hdm + 1), jnp.float32)}
    if kind == "slstm":
        return {"s": ((batch, 3, cfg.n_ssm_heads,
                       cfg.d_model // cfg.n_ssm_heads), jnp.float32)}
    if kind == "mamba":
        hdm = d_in // cfg.n_ssm_heads
        return {"s": ((batch, cfg.n_ssm_heads, cfg.ssm_state, hdm),
                      jnp.float32),
                "tail": ((batch, cfg.ssm_conv - 1, d_in), dtype)}
    raise ValueError(kind)


def layer_cache_spec(kind: str, lay: ServeLayout, hp: ServeHParams,
                     paging: PagedLayout | None = None):
    b = lay.bspec
    if kind in ("attn", "moe", "shared_attn"):
        if paging is not None:
            # pool pages replicated over the batch axes (every batch
            # replica computes identical writes), sharded over seq
            s = {"k": P(None, lay.seq_axes), "v": P(None, lay.seq_axes)}
            if hp.decode_mode == "prism":
                s["kz"] = P(None)
                s["vz"] = P(None)
                s["gz"] = P(None)
                s["zsum"] = P(None)
            return s
        s = {"k": P(b, lay.seq_axes), "v": P(b, lay.seq_axes)}
        if hp.decode_mode == "prism":
            s["kz"] = P(b)
            s["vz"] = P(b)
            s["gz"] = P(b)
            s["zsum"] = P(b)
        return s
    if kind == "attn_local":
        return {"k": P(b), "v": P(b)}
    if kind in ("mlstm", "slstm"):
        return {"s": P(b)}
    if kind == "mamba":
        return {"s": P(b), "tail": P(b)}
    raise ValueError(kind)


def cache_shapes(cfg: ModelConfig, lay: ServeLayout, batch: int,
                 hp: ServeHParams, dtype=jnp.float32,
                 paging: PagedLayout | None = None):
    """ShapeDtypeStruct pytree (dry-run input stand-in; no allocation).
    Mirrors the stacked parameter layout: {'scan': [u stacked trees with
    leading n_units], 'tail': [...]}."""
    u, n_units, _ = cfg.scan_split
    kinds = cfg.block_kinds

    def one(kind, lead=None):
        shapes = layer_cache_shape(cfg, kind, lay, batch, hp, dtype,
                                   paging)
        return {k: jax.ShapeDtypeStruct(
            ((lead,) + sh) if lead else sh, dt)
            for k, (sh, dt) in shapes.items()}
    return {"scan": [one(kinds[j], n_units) for j in range(u)],
            "tail": [one(kinds[n_units * u + t])
                     for t in range(len(kinds) - n_units * u)]}


def cache_specs(cfg: ModelConfig, lay: ServeLayout, hp: ServeHParams,
                paging: PagedLayout | None = None):
    u, n_units, _ = cfg.scan_split
    kinds = cfg.block_kinds

    def one(kind, stacked):
        s = layer_cache_spec(kind, lay, hp, paging)
        if stacked:
            s = {k: P(*((None,) + tuple(v))) for k, v in s.items()}
        return s
    return {"scan": [one(kinds[j], True) for j in range(u)],
            "tail": [one(kinds[n_units * u + t], False)
                     for t in range(len(kinds) - n_units * u)]}


def init_cache(cfg: ModelConfig, lay: ServeLayout, batch: int,
               hp: ServeHParams, dtype=jnp.float32,
               paging: PagedLayout | None = None):
    """Zero-filled global-shape cache (host-mesh tests / examples)."""
    shapes = cache_shapes(cfg, lay, batch, hp, dtype, paging)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def make_kv_cache(cfg: ModelConfig, mesh, lay: ServeLayout, batch: int,
                  hp: ServeHParams, *, paging: PagedLayout | None = None,
                  prefix_cache: bool = False,
                  dtype=jnp.float32) -> KVCache:
    """Build the engine's single cache object: zero-filled device
    storage placed under the right shardings, wrapped in a ``KVCache``
    (paged: pool + ``PageTable`` [+ ``PrefixCache``] and the
    alloc/append/fork/free lifecycle; dense: the legacy rowset with
    ``grow_from``/``insert_row``/``reset_row`` replacing the old free
    functions)."""
    specs = cache_specs(cfg, lay, hp, paging)
    sh = jax.tree.map(functools.partial(NamedSharding, mesh), specs)
    storage = jax.device_put(init_cache(cfg, lay, batch, hp, dtype,
                                        paging), sh)
    kv = KVCache(storage=storage, layout=lay, paging=paging, sharding=sh)
    if paging is not None and prefix_cache:
        kv.prefix = PrefixCache(kv.table)
    return kv


def make_kv_store(capacity_bytes: int | None = None):
    """Host offload tier companion to ``make_kv_cache``: an LRU
    ``KVStore`` for spilled requests.  ``KVCache.spill`` gathers a
    slot's pages (+ prism kz/vz/gz/zsum state row) device→host in one
    jitted gather and hands the refcounts back to the page table;
    ``KVCache.plan_restore`` re-enters the normal ``plan`` → ``reserve``
    → ``bind`` admission path with the covered-token count taken from
    the store, and ``KVCache.restore`` injects the payload into the
    freshly bound pages — decode then resumes bit-identically in both
    decode modes (page/state maps hide the physical relocation)."""
    from .offload import KVStore
    return KVStore(capacity_bytes=capacity_bytes)


# --------------------------------------------------------------------------
# decode attention
# --------------------------------------------------------------------------

@jax.named_scope("kv_write")
def _write_slot(cache_kv, new_row, slot, owner):
    """Write (B,1,Hkv,hd) rows into per-request cache slots.

    ``slot`` and ``owner`` are (B,) — every batch row carries its own
    decode depth, so a continuous-batching engine can hold requests at
    different positions in the same cache.  Rows whose ``owner`` is
    False (wrong shard, or idle slot with pos < 0) get their current
    column written back unchanged — an O(B) scatter, not a full-cache
    select, so the write cost stays independent of the cache capacity.
    """
    rows = jnp.arange(cache_kv.shape[0])
    cols = jnp.clip(slot, 0, cache_kv.shape[1] - 1)
    cur = cache_kv[rows, cols]                            # (B, Hkv, hd)
    upd = jnp.where(owner[:, None, None],
                    new_row[:, 0].astype(cache_kv.dtype), cur)
    return cache_kv.at[rows, cols].set(upd)


@jax.named_scope("kv_write")
def _write_chunk(cache_kv, new_rows, slot, owner):
    """Scatter a prefill chunk's (B,C,Hkv,hd) rows into per-request
    cache slots at runtime offsets.  ``slot``/``owner`` are (B,C) —
    every chunk token lands at its own column of its own shard.
    Non-owner entries (wrong shard, dead token) are routed to an
    out-of-range column and dropped by the scatter, so duplicate
    in-range indices never occur (a request's chunk positions are
    distinct) and the write stays O(B·C), independent of capacity."""
    b, cap_l = cache_kv.shape[:2]
    rows = jnp.arange(b)[:, None]
    cols = jnp.where(owner, slot, cap_l)                  # OOB -> dropped
    return cache_kv.at[rows, cols].set(
        new_rows.astype(cache_kv.dtype), mode="drop")


@jax.named_scope("kv_write")
def _write_packed(cache_kv, new_rows, row, col, ok):
    """Scatter a packed tick's (T,Hkv,hd) K/V rows into the
    (B,cap_l,Hkv,hd) cache at per-token (batch row, column) addresses.
    Tokens whose ``ok`` is False (dead entry, wrong sequence shard,
    wrong batch shard) are routed to an out-of-range column and dropped
    by the scatter.  In-range duplicates never occur — the engine packs
    each (request, position) at most once per tick — so the write stays
    O(T), independent of both the slot count and the capacity."""
    b, cap_l = cache_kv.shape[:2]
    r = jnp.clip(row, 0, b - 1)
    c = jnp.where(ok, col, cap_l)                         # OOB -> dropped
    return cache_kv.at[r, c].set(new_rows.astype(cache_kv.dtype),
                                 mode="drop")


@jax.named_scope("kv_gather")
def _gather_pages(pool, pages):
    """Reassemble virtual cache rows from the page pool: ``pool``
    (n_pages, page_cols, ...) LOCAL shard, ``pages`` (R, ppr) physical
    page ids per logical page slot -> (R, ppr·page_cols, ...) —
    logical column ``j`` of row ``r`` is page ``pages[r, j // pc]``,
    offset ``j % pc``.  Unmapped slots (id < 0) gather page 0; callers
    mask them out of ``valid`` (they are never owned positions).  This
    is the one extra level of indirection every paged step pays —
    the paged generalization of the packed step's per-token row
    gather."""
    pc = pool.shape[1]
    g = jnp.take(pool, jnp.clip(pages, 0, pool.shape[0] - 1), axis=0)
    return g.reshape(pages.shape[0], pages.shape[1] * pc,
                     *pool.shape[2:])


@jax.named_scope("kv_write")
def _write_pool(pool, rows, page, poff, ok):
    """Scatter per-item (Hkv, hd) rows into the page pool at
    (physical page, in-page offset) addresses.  Items with ``ok``
    False or an unmapped page route to an out-of-range offset and are
    dropped; in-range duplicates never occur (each page has exactly
    one writer — shared prefix pages are never in any write window).
    O(items), independent of pool size."""
    n_pages, pc = pool.shape[:2]
    pg = jnp.clip(page, 0, n_pages - 1)
    po = jnp.where(ok & (page >= 0), poff, pc)            # OOB -> dropped
    return pool.at[pg, po].set(rows.astype(pool.dtype), mode="drop")


@jax.named_scope("attn_kernel")
def decode_attention(q, k, v, valid, axes, scale, *, gz=None, kz=None,
                     vz=None, owner=None, mode="exact", backend="auto"):
    """Single entry point for per-token decode attention — every decode
    path (exact flash-decode, prism means decode, the TP variant) routes
    here.  Partial softmax stats (m, l, acc) over the LOCAL cache shard
    — plus, in prism mode, the means columns folded in via the ``+log g``
    bias, with no cache-sized concatenate on either backend — come from
    the Pallas kernel (``backend='pallas'``) or the two-pass jnp
    implementation (``'jnp'``; ``'auto'`` picks by platform).  The
    cross-shard combine is unchanged from ``flash_decode_combine`` /
    ``prism_decode_attention``, which remain below as the dense jnp
    test oracles.

    q (B,1,Hq,hd); k,v (B,M,Hkv,hd) local shard; valid (B,M) bool.
    Prism extras: gz (B,m) per-row means repeat counts (0 = dead
    column), kz/vz (B,m,Hkv,hd), owner (B,) bool, mode='prism'.
    """
    log_gz = log_repeats(gz) if kz is not None else None
    if use_pallas(backend):
        m_p, l_p, acc_p = flash_decode_stats(
            q, k, v, valid, log_gz, kz, vz, scale=scale,
            interpret=pallas_interpret())
    else:
        m_p, l_p, acc_p = decode_stats_reference(
            q, k, v, valid, log_gz, kz, vz, scale=scale)

    if mode == "prism":
        # scaling-aware softmax already folded into the stats; normalize
        # locally and select the owner's view (paper rule) via psum
        denom = jnp.maximum(l_p[:, :, 0, 0], 1e-30)       # (B,Hq)
        out = (acc_p / denom[:, None, :, None]).astype(v.dtype)
        if axes:
            sel = owner[:, None, None, None]
            out = lax.psum(jnp.where(sel, out, jnp.zeros_like(out)), axes)
        return out

    # exact: the flash-decoding pmax/psum stat combine
    return _combine_exact(m_p, l_p, acc_p, axes).astype(v.dtype)


@jax.named_scope("attn_kernel")
def chunk_attention(q, k, v, valid, bias_self, k_new, v_new, axes, scale,
                    backend="auto"):
    """Exact attention for one prefill chunk — the multi-query sibling
    of ``decode_attention``.  Two disjoint column sets, two passes:

      * **prior columns** — everything this request laid down before
        the chunk (``valid (B,M)`` is col_pos < chunk offset, uniform
        over the chunk's queries), so the single-token flash-decode
        kernel applies verbatim with the C·Hq query heads folded into
        the GQA head axis (KV-head-major, preserving the grouping);
      * **the chunk itself** — the C just-projected K/V rows under a
        per-query causal bias (``bias_self (B,C,C)``), a tiny dense
        jnp pass (C ≪ cache capacity).

    The two stat triples merge associatively and the cross-shard
    pmax/psum combine keeps the result exact — chunked prefill is
    token-identical to the monolithic prefill and to sequential decode.

    q (B,C,Hq,hd); k,v (B,M,Hkv,hd) the local prefill-region shard;
    k_new,v_new (B,C,Hkv,hd).  Returns (B,C,Hq,hd)."""
    b, c, hq, hd = q.shape
    hkv = k.shape[2]
    grp = hq // hkv
    # fold queries KV-head-major: index (kv, c, g) -> kernel's GQA map
    # (head i attends kv head i // (c·grp)) stays correct
    qf = (q.reshape(b, c, hkv, grp, hd).swapaxes(1, 2)
          .reshape(b, 1, c * hq, hd))
    if use_pallas(backend):
        m1, l1, a1 = flash_decode_stats(qf, k, v, valid, scale=scale,
                                        interpret=pallas_interpret())
    else:
        m1, l1, a1 = decode_stats_reference(qf, k, v, valid, scale=scale)

    def unfold_stat(s):                       # (B, C·Hq, 1, 1)
        s = s.reshape(b, hkv, c, grp)
        return s.transpose(0, 1, 3, 2).reshape(b, hq, c)[..., None]

    def unfold_acc(a):                        # (B, 1, C·Hq, hd)
        a = a[:, 0].reshape(b, hkv, c, grp, hd)
        return a.transpose(0, 2, 1, 3, 4).reshape(b, c, hq, hd)

    stats_prior = (unfold_stat(m1), unfold_stat(l1), unfold_acc(a1))
    stats_self = chunk_softmax_stats(q, k_new, v_new, bias_self, scale)
    m_p, l_p, acc_p = merge_stats(stats_prior, stats_self)
    return _combine_exact(m_p, l_p, acc_p, axes).astype(v.dtype)


def _combine_exact(m_p, l_p, acc_p, axes):
    """Cross-shard flash-softmax stat combine: rescale each shard's
    (l, acc) to the global max, psum, normalize.  O(B·Hq·hd) traffic,
    independent of N.  Shards with no valid column (m = NEG) cancel via
    corr = 0; an all-shards-empty row lands on the 1e-30 clamp and
    yields a finite zero.  Shape-generic over the query count Nq —
    m, l (B,Hq,Nq,1), acc (B,Nq,Hq,hd) — so the chunked-prefill pass
    combines a whole chunk of queries with the same primitive."""
    m_g = lax.pmax(m_p, axes) if axes else m_p
    corr = jnp.exp(m_p - m_g)                             # (B,Hq,Nq,1)
    l_c = l_p * corr
    acc_c = acc_p * jnp.swapaxes(corr[..., 0], 1, 2)[..., None].astype(
        acc_p.dtype)
    if axes:
        l_c = lax.psum(l_c, axes)
        acc_c = lax.psum(acc_c, axes)
    denom = jnp.maximum(l_c[..., 0], 1e-30)               # (B,Hq,Nq)
    return acc_c / jnp.swapaxes(denom, 1, 2)[..., None].astype(acc_c.dtype)


def flash_decode_combine(q, k, v, valid, axes, scale):
    """Exact distributed flash-decoding, dense jnp form — materializes
    the (B,Hq,1,M) score tensor, so it now serves as the TEST ORACLE for
    ``decode_attention`` (the runtime routes through the kernel/two-pass
    path above).  q (B,1,Hq,hd); k,v are LOCAL cache shards
    (B,M,Hkv,hd); ``valid`` (B,M) bool (per-request column visibility).
    Combines partial softmax stats over ``axes`` — O(B·Hq·hd) traffic,
    independent of N."""
    s = _gqa_logits(q, k, scale)                          # (B,Hq,1,M)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m_p = jnp.max(s, axis=-1, keepdims=True)              # (B,Hq,1,1)
    e = jnp.exp(s - m_p)
    l_p = jnp.sum(e, axis=-1, keepdims=True)              # (B,Hq,1,1)
    acc_p = _gqa_output(e.astype(v.dtype), v)             # (B,1,Hq,hd)
    return _combine_exact(m_p, l_p, acc_p, axes)


def prism_decode_attention(q, k_loc, v_loc, kz, vz, valid, gz, owner,
                           axes, scale):
    """Paper-faithful decode, dense jnp form — TEST ORACLE for
    ``decode_attention(mode='prism')``; the runtime no longer pays this
    per-step cache-sized concatenate.  Exact local columns (g=1 where
    valid) plus remote Segment-Means columns (g = segment sizes; 0 for
    own shard), scaling-aware softmax, owner's view selected via masked
    psum.  ``valid`` (B,M_loc), ``gz`` (B,m) and ``owner`` (B,) are
    per-request — slots decode at independent depths."""
    k_all = jnp.concatenate([k_loc, kz.astype(k_loc.dtype)], axis=1)
    v_all = jnp.concatenate([v_loc, vz.astype(v_loc.dtype)], axis=1)
    g = jnp.concatenate([valid.astype(jnp.float32), gz], axis=1)
    s = _gqa_logits(q, k_all, scale)                      # (B,Hq,1,M)
    s = s + log_repeats(g)[:, None, None, :]
    s = s - jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s)
    w = e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)
    out = _gqa_output(w.astype(v_all.dtype), v_all)       # (B,1,Hq,hd)
    if axes:
        sel = owner[:, None, None, None]
        out = lax.psum(jnp.where(sel, out, jnp.zeros_like(out)), axes)
    return out


# --------------------------------------------------------------------------
# per-layer decode dispatch
# --------------------------------------------------------------------------

def _seq_index(seq_axes):
    idx = lax.axis_index(seq_axes[0])
    for a in seq_axes[1:]:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


def _batch_index(ba):
    """Linearized shard index over the batch mesh axes (0 when the
    batch is replicated) — the packed step needs it to map a global
    slot id to this shard's local cache row."""
    if not ba:
        return jnp.int32(0)
    return _seq_index(ba)


def _means_meta(lay: ServeLayout):
    """Static (lo, hi, mid, sizes, shard_of) for the means-cache columns,
    shard-major over the PREFILL region — matching both the
    ShardedPrismContext._augment_prism ordering and the prefill capture."""
    n0 = lay.n_loc0
    lo0, hi0 = segment_bounds(n0, lay.L)
    sizes = segment_sizes(n0, lay.L).astype(np.float32)
    offs = np.repeat(np.arange(lay.n_seq) * n0, lay.L)
    lo = np.tile(lo0, lay.n_seq) + offs
    hi = np.tile(hi0, lay.n_seq) + offs
    shard_of = np.repeat(np.arange(lay.n_seq), lay.L)
    return lo, hi, (lo + hi) / 2.0, np.tile(sizes, lay.n_seq), shard_of


def _decode_cols(lay: ServeLayout, idx, pos):
    """(write_slot (B,), owner (B,), col_pos (cap_l,)) under the
    layout's placement (see ServeLayout).  ``pos`` is the (B,)
    per-request position vector; idle slots pass pos = -1, which lands
    owner = False on every shard (no write).  ``col_pos`` maps shard
    slots to global positions and is position-independent."""
    if lay.placement == "rr":
        # pure round-robin: position p -> shard p % n_seq, column
        # p // n_seq.  pos = -1 floors to slot -1 (owner False).
        slot = pos // lay.n_seq
        wr_shard = pos % lay.n_seq
        owner = ((wr_shard == idx) & (slot >= 0) & (slot < lay.cap_l)
                 & (pos >= 0))
        col_pos = jnp.arange(lay.cap_l) * lay.n_seq + idx
        return slot, owner, col_pos
    n0, n_loc0 = lay.prefill_len, lay.n_loc0
    extra = pos - n0
    slot = jnp.where(extra >= 0,
                     n_loc0 + extra // lay.n_seq,
                     pos - idx * n_loc0)
    wr_shard = jnp.where(extra >= 0, extra % lay.n_seq,
                         jnp.clip(pos // jnp.maximum(n_loc0, 1),
                                  0, lay.n_seq - 1))
    owner = (wr_shard == idx) & (slot >= 0) & (slot < lay.cap_l)
    j = jnp.arange(lay.cap_l)
    col_pos = jnp.where(
        j < n_loc0,
        idx * n_loc0 + j,
        n0 + (j - n_loc0) * lay.n_seq + idx)
    return slot, owner, col_pos


def attn_decode(p, spec: AttnSpec, cfg: ModelConfig, x, c, pos,
                lay: ServeLayout, hp: ServeHParams, *, local: bool,
                page_map=None, state_map=None, degraded=None):
    """x (B,1,D) replicated over seq axes, pos (B,) per-request positions
    (-1 = idle slot) -> (out (B,1,D), new layer cache).

    Paged mode (``page_map`` (B, ppr) set): the layer cache is the
    page pool; each row's virtual cache row is gathered through its
    page list, the new K/V row scatters to its (page, offset) address,
    and in prism mode the per-request means state is read through
    ``state_map`` (B,) from the state-page pool.  Everything is
    replicated over the batch axes (identical writes on every
    replica), so the attention combine still runs over the sequence
    axes only.

    ``degraded = (lost, rep)`` arms the shard-loss path (engine
    degraded mode, ``runtime/replica.py``): ``lost`` is the (n_seq,)
    float mask of unreadable sequence shards.  On a lost shard every
    exact column is masked out of the stat combine and cache writes
    are dropped; the shard's positions are served instead by
    Segment-Means columns through the existing ``+log g`` bias path —
    in exact mode from the standby replica ``rep`` ({"kz" (B,m,Hkv,hd),
    "vz", "gz" (B,m)}, served ONLY by the lost shard so the psum
    counts each mean once — in the simulation its device lanes stand
    in for the neighbor that would host the replica), in prism mode
    from the means already replicated in the cache (the lost shard's
    own-shard gate simply opens, ``rep`` rides as None).  Tokens stay
    finite with PRISM-bounded quality loss instead of failing."""
    xn = norm(p["ln1"], x, cfg.norm_kind)
    rp = pos[:, None]                          # (B,1) row positions
    q = attn_project_q(p["attn"], spec, xn, rp)
    k_new, v_new = attn_project_kv(p["attn"], spec, xn, rp)
    scale = spec.head_dim ** -0.5

    if local:                                  # ring window cache, replicated
        w = c["k"].shape[1]
        alive = pos >= 0
        k_c = _write_slot(c["k"], k_new, pos % w, alive)
        v_c = _write_slot(c["v"], v_new, pos % w, alive)
        j = jnp.arange(w)
        # ring slot -> global position, per request
        col_pos = pos[:, None] - ((pos[:, None] - j[None, :]) % w)
        valid = col_pos >= 0
        if spec.window:
            valid &= col_pos > pos[:, None] - spec.window
        out = decode_attention(q, k_c, v_c, valid, (), scale,
                               backend=hp.backend)
        new_c = dict(c, k=k_c, v=v_c)
    else:
        idx = _seq_index(lay.seq_axes)
        slot, owner, col_pos = _decode_cols(lay, idx, pos)
        wr_ok, lostf, rep = owner, None, None
        if degraded is not None:
            lost_vec, rep = degraded
            lostf = jnp.take(lost_vec, idx) > 0    # this shard is dead
            wr_ok = owner & ~lostf                 # writes dropped there
        if page_map is not None:
            pc = c["k"].shape[1]
            colc = jnp.clip(slot, 0, lay.cap_l - 1)
            pg = jnp.take_along_axis(
                page_map, (colc // pc)[:, None], axis=1)[:, 0]
            k_pool = _write_pool(c["k"], k_new[:, 0], pg, colc % pc,
                                 wr_ok)
            v_pool = _write_pool(c["v"], v_new[:, 0], pg, colc % pc,
                                 wr_ok)
            k_c = _gather_pages(k_pool, page_map)
            v_c = _gather_pages(v_pool, page_map)
            mapped = jnp.repeat(page_map >= 0, pc, axis=1)
            valid = mapped & (col_pos[None, :] <= pos[:, None])
        else:
            k_c = _write_slot(c["k"], k_new, slot, wr_ok)
            v_c = _write_slot(c["v"], v_new, slot, wr_ok)
            valid = col_pos[None, :] <= pos[:, None]
        if lostf is not None:
            # the lost shard's exact columns leave the stat combine
            valid = valid & ~lostf
        if hp.decode_mode == "prism" and "kz" in c:
            # per-request repeat counts ride in the cache (written by
            # the prefill that captured kz/vz, so they count REAL
            # columns only — a short prompt's partially-filled segments
            # carry their true token count, never pad columns).  The
            # own shard is masked out (its columns are served exact),
            # and a mean is visible only once every position it covers
            # ([lo, lo+gz), prefix-contiguous by construction) is in
            # the query's past — for chunked captures that always
            # holds, for the legacy padded flush (gz = full sizes) it
            # reduces to the old ``hi <= pos`` causal gating.
            lo, _, _, _, shard_of = _means_meta(lay)
            if state_map is not None:
                sr = jnp.clip(state_map, 0, c["gz"].shape[0] - 1)
                cnt = jnp.take(c["gz"], sr, axis=0)
                kz_r = jnp.take(c["kz"], sr, axis=0)
                vz_r = jnp.take(c["vz"], sr, axis=0)
            else:
                cnt, kz_r, vz_r = c["gz"], c["kz"], c["vz"]
            served = jnp.asarray(shard_of)[None, :] != idx
            if lostf is not None:
                # the state pool is replicated across the seq shards,
                # so the means ARE the standby: the lost shard's own
                # columns open up everywhere (including on itself)
                lost_col = jnp.take(lost_vec, jnp.asarray(shard_of)) > 0
                served = served | lost_col[None, :]
            gz = jnp.where(
                served
                & (jnp.asarray(lo)[None, :] + cnt <= pos[:, None] + 1),
                cnt, 0.0)
            out = decode_attention(
                q, k_c, v_c, valid, lay.seq_axes, scale,
                gz=gz, kz=kz_r, vz=vz_r, owner=owner,
                mode="prism", backend=hp.backend)
        elif rep is not None:
            # exact degraded: substitute the lost shard's columns with
            # its standby Segment-Means replica, served only on the
            # lost shard itself so the exact psum counts each mean once
            shard_of_r = np.repeat(np.arange(lay.n_seq), lay.L)
            lost_col = jnp.take(lost_vec, jnp.asarray(shard_of_r)) > 0
            serve = lost_col[None, :] & \
                (jnp.asarray(shard_of_r)[None, :] == idx)
            gz_d = jnp.where(serve, rep["gz"], 0.0)
            out = decode_attention(
                q, k_c, v_c, valid, lay.seq_axes, scale,
                gz=gz_d, kz=rep["kz"].astype(k_c.dtype),
                vz=rep["vz"].astype(v_c.dtype),
                mode="exact", backend=hp.backend)
        else:
            out = decode_attention(q, k_c, v_c, valid, lay.seq_axes,
                                   scale, backend=hp.backend)
        if page_map is not None:
            new_c = dict(c, k=k_pool, v=v_pool)
        else:
            new_c = dict(c, k=k_c, v=v_c)

    o = attn_output(p["attn"], out)
    if cfg.parallel_block:
        o = o + mlp(p["mlp"], xn, cfg.mlp_kind)
    return o, new_c


def mlp_tp(p, x, kind: str, *, tp_ffn: bool):
    """Feed-forward with column-parallel up/gate and row-parallel down
    (weights stay sharded over 'model'; one psum of (B,1,D))."""
    y = mlp(p, x, kind)
    return lax.psum(y, "model") if tp_ffn else y


def attn_decode_tp(p, spec: AttnSpec, cfg: ModelConfig, x, c, pos,
                   lay: ServeLayout, hp: ServeHParams, *,
                   attn_tp: bool, ffn_tp: bool):
    """Tensor-parallel single-token attention (§Perf H1).

    wq column-parallel (this shard computes Hq/tp heads, then a tiny
    head all-gather so every shard can attend over its LOCAL cache shard
    with ALL heads — flash-decoding needs the full head dim against the
    sequence shard), wo row-parallel (each shard consumes its own head
    slice; psum of (B,1,D)).  wk/wv are replicated (GQA keeps them
    small).  Per-token parameter traffic: ZERO — the baseline's
    per-layer FSDP gather (the whole weight matrix per token) becomes
    one activation psum.
    """
    tp = lax.axis_size("model")
    xn = norm(p["ln1"], x, cfg.norm_kind)
    rp = pos[:, None]                          # (B,1) row positions
    b = x.shape[0]

    if attn_tp:
        hq_loc = spec.n_heads // tp
        q_loc = dense(p["attn"]["wq"], xn).reshape(b, 1, hq_loc,
                                                   spec.head_dim)
        if spec.qk_norm:
            q_loc = norm(p["attn"]["qnorm"], q_loc)
        if spec.rope_theta is not None:
            from ..models.layers import rope
            q_loc = rope(q_loc, rp, theta=spec.rope_theta)
        q = lax.all_gather(q_loc, "model", axis=2, tiled=True)
    else:
        q = attn_project_q(p["attn"], spec, xn, rp)
    k_new, v_new = attn_project_kv(p["attn"], spec, xn, rp)
    scale = spec.head_dim ** -0.5

    idx = _seq_index(lay.seq_axes)
    slot, owner, col_pos = _decode_cols(lay, idx, pos)
    k_c = _write_slot(c["k"], k_new, slot, owner)
    v_c = _write_slot(c["v"], v_new, slot, owner)
    valid = col_pos[None, :] <= pos[:, None]
    out = decode_attention(q, k_c, v_c, valid, lay.seq_axes, scale,
                           backend=hp.backend)
    new_c = dict(c, k=k_c, v=v_c)

    if attn_tp:
        midx = lax.axis_index("model")
        hq_loc = spec.n_heads // tp
        out_loc = lax.dynamic_slice_in_dim(out, midx * hq_loc, hq_loc,
                                           axis=2)
        o = dense(p["attn"]["wo"], out_loc.reshape(b, 1, -1))
        o = lax.psum(o, "model")
    else:
        o = attn_output(p["attn"], out)
    if cfg.parallel_block:
        o = o + mlp_tp(p["mlp"], xn, cfg.mlp_kind, tp_ffn=ffn_tp)
    return o, new_c


class DecodeMoeCtx:
    """Expert exchange for single-token decode: all_to_all over 'model'
    (expert parallelism); with ``tp`` the per-expert d_ff dim is sharded
    over 'data' and the down-projection partials are psum'd (expert-TP —
    no per-token expert-weight gather, ever)."""

    def __init__(self, tp: bool = False):
        self.tp = tp

    def expert_exchange(self, buf):
        out = lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                             tiled=True)
        if self.tp:
            # expert-TP: the d_ff slices live across 'data', but so do the
            # tokens — share tokens first (activation-sized), compute the
            # dff partials everywhere, psum in expert_reduce, slice back.
            out = lax.all_gather(out, "data", axis=1, tiled=True)

        def undo(y):
            if self.tp:
                d = lax.axis_index("data")
                s = y.shape[1] // lax.axis_size("data")
                y = lax.dynamic_slice_in_dim(y, d * s, s, axis=1)
            return lax.all_to_all(y, "model", split_axis=1, concat_axis=0,
                                  tiled=True)
        return out, undo

    def expert_reduce(self, y):
        return lax.psum(y, "data") if self.tp else y

    def ffn_reduce(self, y):
        return lax.psum(y, "model") if self.tp else y


def block_decode(cfg: ModelConfig, kind: str, p, shared, x, c, pos,
                 lay: ServeLayout, hp: ServeHParams,
                 tp_flags=(False, False), page_map=None, state_map=None,
                 degraded=None):
    """One residual block, single-token decode.  Returns (x, new_cache).
    ``degraded`` (see ``attn_decode``) arms the shard-loss substitution
    on the sequence-sharded attention kinds; ring-window and SSM state
    is replicated over the sequence axes and unaffected."""
    attn_tp, ffn_tp = tp_flags
    use_tp = hp.decode_tp and kind in ("attn", "moe", "shared_attn")

    def ffn(pp, xx):
        if hp.decode_tp and ffn_tp:
            return mlp_tp(pp, xx, cfg.mlp_kind, tp_ffn=True)
        return mlp(pp, xx, cfg.mlp_kind)

    if kind in ("attn", "attn_local", "moe"):
        spec = T.attn_spec(cfg, kind)
        if use_tp:
            o, c = attn_decode_tp(p, spec, cfg, x, c, pos, lay, hp,
                                  attn_tp=attn_tp, ffn_tp=ffn_tp)
        else:
            o, c = attn_decode(p, spec, cfg, x, c, pos, lay, hp,
                               local=(kind == "attn_local"),
                               page_map=page_map, state_map=state_map,
                               degraded=(None if kind == "attn_local"
                                         else degraded))
        x = x + o
        if cfg.parallel_block:
            return x, c
        if kind == "moe":
            y, _ = moe_apply(p["moe"], norm(p["ln2"], x, cfg.norm_kind),
                             cfg, DecodeMoeCtx(tp=hp.decode_tp))
            x = x + y
        elif cfg.d_ff:
            x = x + ffn(p["mlp"], norm(p["ln2"], x, cfg.norm_kind))
        return x, c
    if kind == "shared_attn":
        spec = T.attn_spec(cfg, "attn")
        if use_tp:
            o, c = attn_decode_tp(shared, spec, cfg, x, c, pos, lay, hp,
                                  attn_tp=attn_tp, ffn_tp=ffn_tp)
        else:
            o, c = attn_decode(shared, spec, cfg, x, c, pos, lay, hp,
                               local=False, page_map=page_map,
                               state_map=state_map, degraded=degraded)
        x = x + o
        x = x + ffn(shared["mlp"], norm(shared["ln2"], x, cfg.norm_kind))
        return x, c
    if kind == "mlstm":
        y, s = mlstm_decode(p["cell"], norm(p["ln"], x, cfg.norm_kind),
                            c["s"], heads=cfg.n_ssm_heads)
        return x + y, dict(c, s=s)
    if kind == "slstm":
        y, s = slstm_decode(p["cell"], norm(p["ln"], x, cfg.norm_kind),
                            c["s"], heads=cfg.n_ssm_heads)
        return x + y, dict(c, s=s)
    if kind == "mamba":
        y, cc = mamba2_decode(p["cell"], norm(p["ln"], x, cfg.norm_kind),
                              c, heads=cfg.n_ssm_heads,
                              d_state=cfg.ssm_state, expand=cfg.ssm_expand,
                              conv=cfg.ssm_conv)
        return x + y, cc
    raise ValueError(kind)


# --------------------------------------------------------------------------
# decode embedding / head
# --------------------------------------------------------------------------

def embed_tokens(cfg: ModelConfig, params, rules, token, pos, *,
                 sharded_vocab):
    """token (B,T), pos (B,T) -> x (B,T,D), replicated over the
    sequence axes.  Positions are per request *and* per token (chunked
    prefill feeds T = chunk_len tokens at per-row offsets); dead
    entries (pos = -1) still embed but never reach the cache (owner
    masking in the attention layers)."""
    table = params["embed"]["table"]
    if sharded_vocab:
        v_loc = table.shape[0]
        vstart = lax.axis_index("model") * v_loc
        t = token - vstart
        ok = (t >= 0) & (t < v_loc)
        e = jnp.take(table, jnp.clip(t, 0, v_loc - 1), axis=0)
        x = lax.psum(jnp.where(ok[..., None], e, jnp.zeros_like(e)),
                     "model")
    else:
        table = gather_tree(params["embed"], rules["embed"])["table"]
        x = jnp.take(table, token, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.pos == "learned":
        tbl = gather_tree(params["pos_embed"], rules["pos_embed"])["table"]
        safe = jnp.clip(pos, 0, tbl.shape[0] - 1)
        x = x + jnp.take(tbl, safe, axis=0).astype(x.dtype)
    elif cfg.pos == "sincos":
        half = cfg.d_model // 2
        freq = jnp.exp(-np.log(10000.0)
                       * jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos.astype(jnp.float32)[..., None] * freq    # (B,T,half)
        x = x + jnp.concatenate(
            [jnp.sin(ang), jnp.cos(ang)], -1).astype(x.dtype)
    return x


def embed_token(cfg: ModelConfig, params, rules, token, pos, *,
                sharded_vocab):
    """Single-token decode form: token (B,), pos (B,) -> x (B,1,D)."""
    return embed_tokens(cfg, params, rules, token[:, None], pos[:, None],
                        sharded_vocab=sharded_vocab)


# --------------------------------------------------------------------------
# serve (decode) step factory
# --------------------------------------------------------------------------

def make_serve_step(cfg: ModelConfig, mesh, params, *,
                    batch: int, cap: int, prefill_len: int | None = None,
                    hp: ServeHParams = ServeHParams(),
                    paging: PagedLayout | None = None,
                    degraded: bool = False):
    """jitted (params, cache, token (B,), pos (B,)) -> (logits, cache).

    ``pos`` carries one position per batch row, so independent requests
    can decode at different depths in the same step (continuous
    batching).  Idle slots pass pos = -1: they compute garbage-but-
    finite logits and never write the cache (owner masking).  ``logits``
    is (B, V) — vocab-sharded over 'model' when the embedding table is
    (the returned lspec says which).

    With ``paging`` the cache is the page pool and the program takes
    two extra inputs ``(page_map (B, ppr), state_map (B,))`` — the
    per-slot physical page lists the host rebuilds each tick.  Token /
    pos vectors ride replicated (the pool is replicated over the batch
    axes; every replica computes identical writes), and logits come
    back replicated too.

    ``degraded=True`` builds the SHARD-LOSS variant the engine runs
    while a sequence shard is unreadable: the program takes one extra
    ``lost (n_seq,)`` float mask (replicated) and — in exact decode
    mode — a standby-replica tree ({"scan": [{kz,vz,gz} ...], "tail":
    [...]}, ``MeansReplica.assemble``'s output, replicated).  The lost
    shard's exact columns are masked out of the stat combine and its
    positions served from Segment-Means columns instead (see
    ``attn_decode``); cache writes to the lost shard are dropped.
    Requires the paged cache (the engine's degraded orchestration
    rides page-table bookkeeping).
    """
    lay = make_layout(cfg, mesh, batch, cap, hp, prefill_len,
                      _paged_placement(hp, paging))
    if degraded:
        assert paging is not None, \
            "degraded decode requires the paged cache"
    if paging is not None:
        assert not hp.decode_tp, "paged serving does not support decode_tp"
    if hp.decode_tp:
        from ..sharding.rules import decode_param_specs
        rules = decode_param_specs(params, mesh, cfg.vocab_size, cfg)
        ax = mesh_axes(mesh).get("model", 1)
        tp_flags = (cfg.n_heads % ax == 0 and not cfg.attn_bias
                    and (cfg.n_heads * cfg.hd) % ax == 0,
                    bool(cfg.d_ff) and cfg.d_ff % ax == 0
                    and not cfg.attn_bias)
    else:
        rules = param_specs(params, mesh, cfg.vocab_size)
        tp_flags = (False, False)
    pspecs = spec_tree(rules)
    cspecs = cache_specs(cfg, lay, hp, paging)
    vocab_sharded = (rules["embed"]["table"].kind == "vocab")
    shared_rules = rules.get("shared")

    u, n_units, _ = cfg.scan_split
    unit_kinds = cfg.block_kinds[:u]
    # degraded exact mode takes the standby-replica tree as one more
    # input; degraded prism reads its means straight from the
    # (replicated) cache state pool and needs only the lost mask
    with_rep = degraded and hp.decode_mode == "exact"

    def _rep_spec(kind):
        if kind not in ("attn", "moe", "shared_attn"):
            return {}
        return {k: P(None) for k in ("kz", "vz", "gz")}
    rep_specs = ({"scan": [_rep_spec(unit_kinds[j]) for j in range(u)],
                  "tail": [_rep_spec(cfg.block_kinds[n_units * u + t])
                           for t in range(len(cfg.block_kinds)
                                          - n_units * u)]}
                 if with_rep else None)

    def decode_step(params_local, cache_local, token, pos, page_map,
                    state_map, lost=None, rep=None):
        trace_counts["serve_step_degraded" if degraded
                     else "serve_step"] += 1
        x = embed_token(cfg, params_local, rules, token, pos,
                        sharded_vocab=vocab_sharded)

        def unit_body(x, xs):
            if rep is not None:
                p_sl, c_sl, r_sl = xs
            else:
                (p_sl, c_sl), r_sl = xs, None
            shared = (gather_tree(params_local["shared"], shared_rules)
                      if shared_rules else None)
            new = []
            for j, kind in enumerate(unit_kinds):
                p = gather_tree(p_sl[j], rules["scan"][j])
                deg = (None if lost is None else
                       (lost, r_sl[j] if (r_sl is not None and r_sl[j])
                        else None))
                x, nc = block_decode(cfg, kind, p, shared, x, c_sl[j],
                                     pos, lay, hp, tp_flags,
                                     page_map, state_map, degraded=deg)
                new.append(nc)
            return x, tuple(new)

        xs = (tuple(params_local["scan"]), tuple(cache_local["scan"]))
        if rep is not None:
            xs = xs + (tuple(rep["scan"]),)
        x, new_stacks = lax.scan(unit_body, x, xs)

        new_tail = []
        for t, tree in enumerate(params_local["tail"]):
            kind = cfg.block_kinds[n_units * u + t]
            p = gather_tree(tree, rules["tail"][t])
            shared = (gather_tree(params_local["shared"], shared_rules)
                      if shared_rules else None)
            deg = None
            if lost is not None:
                rt = rep["tail"][t] if rep is not None else {}
                deg = (lost, rt if rt else None)
            x, nc = block_decode(cfg, kind, p, shared, x,
                                 cache_local["tail"][t], pos, lay, hp,
                                 tp_flags, page_map, state_map,
                                 degraded=deg)
            new_tail.append(nc)

        with jax.named_scope("lm_head"):
            x = norm(params_local["final_norm"], x, cfg.norm_kind)
            table = output_table(params_local, cfg)
            logits = (x[:, 0] @ table.T.astype(x.dtype)).astype(
                jnp.float32)
            if cfg.logit_softcap:
                logits = cfg.logit_softcap * jnp.tanh(
                    logits / cfg.logit_softcap)
        return logits, {"scan": list(new_stacks), "tail": new_tail}

    vspec = P(None) if paging is not None else P(lay.bspec)
    lspec = P(None if paging is not None else lay.bspec,
              "model" if vocab_sharded else None)
    extra = ()
    if degraded:
        extra = ((P(None), rep_specs) if with_rep else (P(None),))
    # the program is named after the function shard_map wraps:
    # ``jit_decode_step`` in HLO and in a device trace
    if paging is not None:
        body = decode_step
        in_specs = (pspecs, cspecs, vspec, vspec, P(None), P(None)) \
            + extra
    else:                              # degraded needs paging: no extra
        body = functools.partial(decode_step, page_map=None,
                                 state_map=None)
        in_specs = (pspecs, cspecs, vspec, vspec)
    body_sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=(lspec, cspecs),
        check_vma=False)

    sh = functools.partial(NamedSharding, mesh)
    jitted = jax.jit(
        body_sm,
        in_shardings=tuple(jax.tree.map(sh, s) for s in in_specs),
        out_shardings=(sh(lspec), jax.tree.map(sh, cspecs)),
        donate_argnums=(1,),
    )
    return jitted, lay, rules, lspec


# --------------------------------------------------------------------------
# prefill
# --------------------------------------------------------------------------

@jax.named_scope("attn_kernel")
def _prefill_attention(q, k, v, akv, spec: AttnSpec, cfg: ModelConfig,
                       hp: ServeHParams):
    """Route the prefill attention through the Pallas flash kernel when
    the backend switch says so AND the augment carries positional
    (col_lo, col_hi) ranges — the kernel re-derives the mask in-VMEM.
    Views with extra mask structure (ring halo) stay on the jnp path."""
    if use_pallas(hp.backend) and akv.col_lo is not None:
        g = (akv.g if akv.g is not None
             else jnp.ones((k.shape[1],), jnp.float32))
        return prism_attention_op(
            q, k, v, g, akv.col_lo, akv.col_hi, akv.row_pos,
            causal=spec.causal, prefix_len=cfg.prefix_len,
            window=spec.window, interpret=pallas_interpret())
    return prism_attention(q, k, v, g=akv.g, mask=akv.mask,
                           block=cfg.attn_block)


def prefill_attn(p, spec: AttnSpec, cfg: ModelConfig, x, ctx, lay,
                 hp: ServeHParams, prism_augment: bool):
    """Attention sublayer that also captures this layer's decode cache."""
    xq, akv = ctx.augment(x, spec)
    xq_n = norm(p["ln1"], xq, cfg.norm_kind)
    xh_n = norm(p["ln1"], akv.x_hat, cfg.norm_kind)
    q = attn_project_q(p["attn"], spec, xq_n, akv.row_pos)
    k, v = attn_project_kv(p["attn"], spec, xh_n, akv.col_pos)
    o = _prefill_attention(q, k, v, akv, spec, cfg, hp)
    o = attn_output(p["attn"], o)
    if cfg.parallel_block:
        o = o + mlp(p["mlp"], xq_n, cfg.mlp_kind)

    n_loc = x.shape[1]
    if spec.window is not None:
        # window augment puts the local block LAST; ring cache = global
        # tail = last shard's last W rows, scattered to ring order.
        w = min(spec.window, lay.cap)
        assert n_loc >= w, "window larger than per-shard tokens"
        kw = ctx.last_shard(k[:, -w:])
        vw = ctx.last_shard(v[:, -w:])
        slots = np.arange(lay.cap - w, lay.cap) % w
        order = np.zeros(w, np.int64)
        order[slots] = np.arange(w)
        cache = {"k": jnp.take(kw, jnp.asarray(order), axis=1),
                 "v": jnp.take(vw, jnp.asarray(order), axis=1)}
        return ctx.finalize(o), cache

    if prism_augment:
        k_loc, v_loc = k[:, :n_loc], v[:, :n_loc]   # local block first
    else:                                           # voltage: full sequence
        start = ctx._index() * n_loc
        k_loc = lax.dynamic_slice_in_dim(k, start, n_loc, axis=1)
        v_loc = lax.dynamic_slice_in_dim(v, start, n_loc, axis=1)
    pad = lay.cap_l - n_loc
    if pad:
        k_loc = jnp.pad(k_loc, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_loc = jnp.pad(v_loc, ((0, 0), (0, pad), (0, 0), (0, 0)))
    cache = {"k": k_loc, "v": v_loc}
    if hp.decode_mode == "prism":
        m = lay.n_seq * lay.L
        if prism_augment:
            # means columns sit right after the local block in x_hat
            cache["kz"] = k[:, n_loc:n_loc + m]
            cache["vz"] = v[:, n_loc:n_loc + m]
            z_all = akv.x_hat[:, n_loc:n_loc + m]
        else:                           # voltage prefill: compute means-KV
            if use_pallas(hp.backend):
                z = segment_means_op(x, L=lay.L,
                                     interpret=pallas_interpret())
            else:
                z = segment_means(x, lay.L)
            zg = ctx._gather(z)
            b = x.shape[0]
            z_all = jnp.moveaxis(zg, 0, 1).reshape(b, m, x.shape[-1])
            _, _, mid, _, _ = _means_meta(lay)
            kz, vz = attn_project_kv(
                p["attn"], spec, norm(p["ln1"], z_all, cfg.norm_kind),
                jnp.asarray(mid, jnp.float32))
            cache["kz"], cache["vz"] = kz, vz
        # monolithic prefill covers every position of [0, n0), so the
        # per-request repeat counts are the full static segment sizes
        # and the running sums are means × sizes (chunked prefill's
        # invariant: zsum / gz == the mean each kz/vz row was cut from)
        _, _, _, sizes, _ = _means_meta(lay)
        b = x.shape[0]
        cache["gz"] = jnp.broadcast_to(
            jnp.asarray(sizes, jnp.float32)[None], (b, m))
        cache["zsum"] = (z_all.astype(jnp.float32)
                         * jnp.asarray(sizes, jnp.float32)[None, :, None])
    return ctx.finalize(o), cache


def make_prefill_step(cfg: ModelConfig, mesh, params, prism: PrismConfig,
                      *, batch: int, n: int,
                      hp: ServeHParams = ServeHParams(),
                      cap: int | None = None):
    """jitted (params, batch_dict) -> (last-token logits, decode cache).

    ``batch_dict`` = {"tokens": (B, N)} (+ "embeds" for vlm/audio stubs).
    ``cap`` sizes the captured cache rows beyond the prompt (the
    padded-admission engine prefills straight into decode-capacity
    rows, so no grow step remains); default: rows sized to ``n``.
    """
    lay = make_layout(cfg, mesh, batch, n if cap is None else cap, hp,
                      prefill_len=n)
    rules = param_specs(params, mesh, cfg.vocab_size)
    pspecs = spec_tree(rules)
    cspecs = cache_specs(cfg, lay, hp)
    vocab_sharded = (rules["embed"]["table"].kind == "vocab")
    shared_rules = rules.get("shared")
    n_loc = n // lay.n_seq
    prism_cfg = prism.with_(P=lay.n_seq,
                            L=lay.L if hp.decode_mode == "prism"
                            else prism.L)
    prism_augment = prism_cfg.mode == "prism"

    def prefill_step(params_local, batch_local):
        trace_counts["prefill_step"] += 1
        ctx = ShardedPrismContext(
            prism_cfg, axis=lay.seq_axes[-1], n_shards=lay.n_seq,
            seq_shards=lay.seq_axes[:-1], prefix_len=cfg.prefix_len)
        tokens = batch_local.get("tokens")
        embeds = batch_local.get("embeds")
        start = ctx._index() * n_loc
        if tokens is not None:
            x = embed_vp(params_local["embed"]["table"], tokens,
                         sharded_vocab=vocab_sharded)
        else:
            fp = gather_tree(params_local["frontend_proj"],
                             rules["frontend_proj"])
            x = dense(fp, embeds)
        if cfg.arch_type == "vlm" and embeds is not None and tokens is not None:
            fp = gather_tree(params_local["frontend_proj"],
                             rules["frontend_proj"])
            fe = dense(fp, embeds)                 # (B, prefix, D) replicated
            pos = start + jnp.arange(n_loc)
            idx = jnp.clip(pos, 0, cfg.prefix_len - 1)
            fe_rows = jnp.take(fe, idx, axis=1)
            x = jnp.where((pos < cfg.prefix_len)[None, :, None], fe_rows, x)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        if cfg.pos == "learned":
            tbl = gather_tree(params_local["pos_embed"],
                              rules["pos_embed"])["table"]
            x = x + lax.dynamic_slice_in_dim(tbl, start, n_loc
                                             ).astype(x.dtype)
        elif cfg.pos == "sincos":
            x = x + T.sincos_embed(n_loc, cfg.d_model, start).astype(x.dtype)

        u, n_units, _ = cfg.scan_split
        unit_kinds = cfg.block_kinds[:u]

        def one_block(kind, p, shared, x):
            if kind in ("attn", "attn_local", "moe", "shared_attn"):
                pp = shared if kind == "shared_attn" else p
                spec = T.attn_spec(cfg, "attn" if kind == "shared_attn"
                                   else kind)
                o, c = prefill_attn(pp, spec, cfg, x, ctx, lay, hp,
                                    prism_augment)
                x = x + o
                if kind == "moe" and not cfg.parallel_block:
                    y, _ = moe_apply(p["moe"],
                                     norm(p["ln2"], x, cfg.norm_kind),
                                     cfg, ctx)
                    x = x + y
                elif kind == "shared_attn":
                    x = x + mlp(shared["mlp"],
                                norm(shared["ln2"], x, cfg.norm_kind),
                                cfg.mlp_kind)
                elif cfg.d_ff and not cfg.parallel_block:
                    x = x + mlp(p["mlp"], norm(p["ln2"], x, cfg.norm_kind),
                                cfg.mlp_kind)
                return x, c
            if kind == "mlstm":
                y, s = mlstm_apply(p["cell"],
                                   norm(p["ln"], x, cfg.norm_kind),
                                   heads=cfg.n_ssm_heads, ctx=ctx,
                                   chunk=hp.ssm_chunk, return_state=True)
                return x + y, {"s": s}
            if kind == "slstm":
                y, s = slstm_apply(p["cell"],
                                   norm(p["ln"], x, cfg.norm_kind),
                                   heads=cfg.n_ssm_heads, ctx=ctx,
                                   return_state=True)
                return x + y, {"s": s}
            if kind == "mamba":
                y, c = mamba2_apply(p["cell"],
                                    norm(p["ln"], x, cfg.norm_kind),
                                    heads=cfg.n_ssm_heads,
                                    d_state=cfg.ssm_state,
                                    expand=cfg.ssm_expand,
                                    conv=cfg.ssm_conv, ctx=ctx,
                                    chunk=hp.ssm_chunk, return_state=True)
                return x + y, c
            raise ValueError(kind)

        def unit_body(x, sliced):
            shared = (gather_tree(params_local["shared"], shared_rules)
                      if shared_rules else None)
            cs = []
            for j, kind in enumerate(unit_kinds):
                p = gather_tree(sliced[j], rules["scan"][j])
                x, c = one_block(kind, p, shared, x)
                cs.append(c)
            return x, tuple(cs)

        x, cache_stacks = lax.scan(unit_body, x,
                                   tuple(params_local["scan"]))
        tail_caches = []
        for t, tree in enumerate(params_local["tail"]):
            kind = cfg.block_kinds[n_units * u + t]
            p = gather_tree(tree, rules["tail"][t])
            shared = (gather_tree(params_local["shared"], shared_rules)
                      if shared_rules else None)
            x, c = one_block(kind, p, shared, x)
            tail_caches.append(c)

        with jax.named_scope("lm_head"):
            x = norm(params_local["final_norm"], x, cfg.norm_kind)
            last = ctx.last_shard(x[:, -1])                # (B, D)
            table = output_table(params_local, cfg)
            logits = (last @ table.T.astype(last.dtype)).astype(
                jnp.float32)
            if cfg.logit_softcap:
                logits = cfg.logit_softcap * jnp.tanh(
                    logits / cfg.logit_softcap)
        return logits, {"scan": list(cache_stacks), "tail": tail_caches}

    bspec = {}
    if cfg.frontend == "encodec_stub":
        bspec["embeds"] = P(lay.bspec, lay.seq_axes, None)
    else:
        bspec["tokens"] = P(lay.bspec, lay.seq_axes)
        if cfg.arch_type == "vlm":
            bspec["embeds"] = P(lay.bspec, None, None)
    lspec = P(lay.bspec, "model" if vocab_sharded else None)
    body_sm = jax.shard_map(
        prefill_step, mesh=mesh,
        in_specs=(pspecs, bspec),
        out_specs=(lspec, cspecs),
        check_vma=False)

    sh = functools.partial(NamedSharding, mesh)
    jitted = jax.jit(
        body_sm,
        in_shardings=(jax.tree.map(sh, pspecs),
                      jax.tree.map(sh, bspec)),
        out_shardings=(sh(lspec), jax.tree.map(sh, cspecs)),
    )
    return jitted, lay, rules, lspec


# --------------------------------------------------------------------------
# chunked prefill
# --------------------------------------------------------------------------

def attn_chunk_prefill(p, spec: AttnSpec, cfg: ModelConfig, x, c, row_pos,
                       off, lay: ServeLayout, hp: ServeHParams,
                       page_map=None, state_map=None):
    """Attention sublayer over one prefill chunk.

    ``x`` (B,C,D) replicated over the sequence axes; ``row_pos`` (B,C)
    global positions of the chunk tokens (-1 = dead: idle row or past
    the row's remaining prompt); ``off`` (B,) the per-row chunk offset
    (-1 = row not prefilling this call).  Writes the chunk's K/V rows
    at their runtime offsets, attends *exactly* (prior columns via the
    flash-decode stats path, the chunk itself via a per-query causal
    pass, cross-shard stat combine), and in prism mode advances the
    Segment-Means capture over REAL columns only — the running
    per-segment sums ``zsum`` and counts ``gz`` ride in the cache, so
    a short prompt's kz/vz never average pad columns.

    Paged mode (``page_map`` (B, ppr) set): K/V writes scatter to each
    token's (page, offset) address and the prior columns gather through
    the row's leading pages; the Segment-Means running state lives in
    the state-page pool, read and written through ``state_map`` (B,)."""
    xn = norm(p["ln1"], x, cfg.norm_kind)
    q = attn_project_q(p["attn"], spec, xn, row_pos)
    k_new, v_new = attn_project_kv(p["attn"], spec, xn, row_pos)
    scale = spec.head_dim ** -0.5

    idx = _seq_index(lay.seq_axes)
    slot, owner, col_pos = _decode_cols(lay, idx, row_pos)
    # prior columns: everything before the chunk offset lives in the
    # leading [0, n_loc0) columns of the shard under BOTH placements
    # (aligned: by construction; rr: p < n0 => p//n_seq < n_loc0), so
    # the static slice / leading-page gather suffices and validity is
    # uniform over the chunk's queries
    n_loc0 = lay.n_loc0
    if page_map is not None:
        pc = c["k"].shape[1]
        colc = jnp.clip(slot, 0, lay.cap_l - 1)
        pg = jnp.take_along_axis(page_map, colc // pc, axis=1)
        flat = lambda a: a.reshape((-1,) + a.shape[2:])
        k_pool = _write_pool(c["k"], flat(k_new), flat(pg),
                             flat(colc % pc), flat(owner))
        v_pool = _write_pool(c["v"], flat(v_new), flat(pg),
                             flat(colc % pc), flat(owner))
        pages_pre = page_map[:, :n_loc0 // pc]
        k_pre = _gather_pages(k_pool, pages_pre)
        v_pre = _gather_pages(v_pool, pages_pre)
        mapped = jnp.repeat(pages_pre >= 0, pc, axis=1)
        valid = mapped & (col_pos[:n_loc0][None, :]
                          < jnp.maximum(off, 0)[:, None])
        new_c = dict(c, k=k_pool, v=v_pool)
    else:
        k_c = _write_chunk(c["k"], k_new, slot, owner)
        v_c = _write_chunk(c["v"], v_new, slot, owner)
        k_pre, v_pre = k_c[:, :n_loc0], v_c[:, :n_loc0]
        valid = col_pos[:n_loc0][None, :] < jnp.maximum(off, 0)[:, None]
        new_c = dict(c, k=k_c, v=v_c)

    # the chunk itself: causal over its own just-projected rows.  Each
    # chunk column contributes on the ONE shard that owns its cache
    # slot (a chunk may span a shard boundary) — the cross-shard psum
    # then sums disjoint column sets, keeping the combine exact.
    jj = jnp.arange(row_pos.shape[1])
    alive = row_pos >= 0
    bias_self = jnp.where(
        (jj[None, None, :] <= jj[None, :, None])
        & alive[:, :, None] & owner[:, None, :], 0.0, NEG_INF)
    out = chunk_attention(q, k_pre, v_pre, valid,
                          bias_self, k_new, v_new, lay.seq_axes, scale,
                          backend=hp.backend)

    if hp.decode_mode == "prism" and "kz" in c:
        lo, hi, mid, _, _ = _means_meta(lay)
        act = off >= 0                             # rows advanced this call
        seg = ((jnp.asarray(lo)[None, None, :] <= row_pos[:, :, None])
               & (row_pos[:, :, None] <= jnp.asarray(hi)[None, None, :]))
        if state_map is not None:
            sr = jnp.clip(state_map, 0, c["zsum"].shape[0] - 1)
            zs_prev = jnp.take(c["zsum"], sr, axis=0)
        else:
            zs_prev = c["zsum"]
        zsum = jnp.where((off == 0)[:, None, None], 0.0, zs_prev)
        zsum = zsum + jnp.einsum("bcm,bcd->bmd", seg.astype(jnp.float32),
                                 x.astype(jnp.float32))
        filled = jnp.maximum(off, 0) + alive.sum(axis=1)
        cnt = segment_fill_counts(lo, hi, filled)  # (B, m) real columns
        z = (zsum / jnp.maximum(cnt, 1.0)[..., None]).astype(x.dtype)
        kz, vz = attn_project_kv(p["attn"], spec,
                                 norm(p["ln1"], z, cfg.norm_kind),
                                 jnp.asarray(mid, jnp.float32))
        if state_map is not None:
            # state rows are unique per active slot, so the scatter has
            # no in-range duplicates; inactive rows route OOB (their
            # pool rows stay put — same as the dense where(act) select)
            S = c["zsum"].shape[0]
            dst = jnp.where(act & (state_map >= 0), state_map, S)
            new_c["kz"] = c["kz"].at[dst].set(
                kz.astype(c["kz"].dtype), mode="drop")
            new_c["vz"] = c["vz"].at[dst].set(
                vz.astype(c["vz"].dtype), mode="drop")
            new_c["gz"] = c["gz"].at[dst].set(cnt, mode="drop")
            new_c["zsum"] = c["zsum"].at[dst].set(zsum, mode="drop")
        else:
            sel = act[:, None, None, None]
            new_c["kz"] = jnp.where(sel, kz.astype(c["kz"].dtype), c["kz"])
            new_c["vz"] = jnp.where(sel, vz.astype(c["vz"].dtype), c["vz"])
            new_c["gz"] = jnp.where(act[:, None], cnt, c["gz"])
            new_c["zsum"] = zsum

    o = attn_output(p["attn"], out)
    if cfg.parallel_block:
        o = o + mlp(p["mlp"], xn, cfg.mlp_kind)
    return o, new_c


def block_chunk_prefill(cfg: ModelConfig, kind: str, p, shared, x, c,
                        row_pos, off, lay: ServeLayout, hp: ServeHParams,
                        page_map=None, state_map=None):
    """One residual block over a prefill chunk.  Returns (x, new_cache).
    Only position-addressed global-attention kinds are chunkable — the
    same set the serving engine admits."""
    if kind in ("attn", "moe"):
        spec = T.attn_spec(cfg, kind)
        o, c = attn_chunk_prefill(p, spec, cfg, x, c, row_pos, off,
                                  lay, hp, page_map, state_map)
        x = x + o
        if cfg.parallel_block:
            return x, c
        if kind == "moe":
            y, _ = moe_apply(p["moe"], norm(p["ln2"], x, cfg.norm_kind),
                             cfg, DecodeMoeCtx(tp=hp.decode_tp))
            x = x + y
        elif cfg.d_ff:
            x = x + mlp(p["mlp"], norm(p["ln2"], x, cfg.norm_kind),
                        cfg.mlp_kind)
        return x, c
    if kind == "shared_attn":
        spec = T.attn_spec(cfg, "attn")
        o, c = attn_chunk_prefill(shared, spec, cfg, x, c, row_pos, off,
                                  lay, hp, page_map, state_map)
        x = x + o
        x = x + mlp(shared["mlp"], norm(shared["ln2"], x, cfg.norm_kind),
                    cfg.mlp_kind)
        return x, c
    raise ValueError(
        f"chunked prefill supports position-addressed attention caches "
        f"only (got block kind {kind!r})")


def make_chunk_prefill_step(cfg: ModelConfig, mesh, params, *,
                            batch: int, cap: int, prefill_len: int,
                            chunk_len: int,
                            hp: ServeHParams = ServeHParams(),
                            paging: PagedLayout | None = None):
    """jitted (params, cache, tokens (B,C), off (B,), nreal (B,)) -> cache
    (paged: two trailing (page_map (B,ppr), state_map (B,)) inputs).

    One compiled program advances every mid-prefill request by up to
    ``chunk_len`` prompt tokens: row ``i``'s tokens land at global
    positions ``[off[i], off[i] + nreal[i])`` of its cache row (idle
    rows pass ``off = -1``), interleaved by the engine's scheduler with
    single-token decode steps so long prompts never stall in-flight
    decodes.  The cache has the DECODE layout (``cap``/``prefill_len``
    as in ``make_serve_step``) — requests are admitted straight into
    their decode slot, with no grow/insert round trip; stale columns
    from a previous occupant are never visible because visibility
    (``col_pos < off`` / ``col_pos <= pos``) only ever reaches columns
    this request has already written.

    Exactness: chunk queries attend with the full cross-shard stat
    combine, so the written cache and any later decode are
    token-identical to the monolithic prefill (the equivalence tests
    pin this).  In prism decode mode the program additionally
    accumulates the Segment-Means state (kz/vz/gz/zsum) over real
    columns only.  Returns (jitted, layout, rules)."""
    lay = make_layout(cfg, mesh, batch, cap, hp, prefill_len,
                      _paged_placement(hp, paging))
    assert 1 <= chunk_len <= prefill_len, (chunk_len, prefill_len)
    rules = param_specs(params, mesh, cfg.vocab_size)
    pspecs = spec_tree(rules)
    cspecs = cache_specs(cfg, lay, hp, paging)
    vocab_sharded = (rules["embed"]["table"].kind == "vocab")
    shared_rules = rules.get("shared")
    u, n_units, _ = cfg.scan_split
    unit_kinds = cfg.block_kinds[:u]
    for kind in cfg.block_kinds:
        if kind not in ("attn", "moe", "shared_attn"):
            raise ValueError(
                f"chunked prefill needs position-addressed attention "
                f"caches; arch {cfg.name!r} has block kind {kind!r}")

    def chunk_prefill_step(params_local, cache_local, tokens, off, nreal,
                           page_map, state_map):
        trace_counts["chunk_prefill_step"] += 1
        j = jnp.arange(chunk_len)
        alive = (off[:, None] >= 0) & (j[None, :] < nreal[:, None])
        row_pos = jnp.where(alive, off[:, None] + j[None, :], -1)
        x = embed_tokens(cfg, params_local, rules, tokens, row_pos,
                         sharded_vocab=vocab_sharded)

        def unit_body(x, xs):
            p_sl, c_sl = xs
            shared = (gather_tree(params_local["shared"], shared_rules)
                      if shared_rules else None)
            new = []
            for k, kind in enumerate(unit_kinds):
                p = gather_tree(p_sl[k], rules["scan"][k])
                x, nc = block_chunk_prefill(cfg, kind, p, shared, x,
                                            c_sl[k], row_pos, off, lay, hp,
                                            page_map, state_map)
                new.append(nc)
            return x, tuple(new)

        x, new_stacks = lax.scan(
            unit_body, x,
            (tuple(params_local["scan"]), tuple(cache_local["scan"])))

        new_tail = []
        for t, tree in enumerate(params_local["tail"]):
            kind = cfg.block_kinds[n_units * u + t]
            p = gather_tree(tree, rules["tail"][t])
            shared = (gather_tree(params_local["shared"], shared_rules)
                      if shared_rules else None)
            x, nc = block_chunk_prefill(cfg, kind, p, shared, x,
                                        cache_local["tail"][t], row_pos,
                                        off, lay, hp, page_map, state_map)
            new_tail.append(nc)
        # no logits: the engine's rewind re-feeds the last prompt token
        # as the first decode step (idempotent K/V rewrite), which is
        # what produces the teacher-forced next-token logits
        return {"scan": list(new_stacks), "tail": new_tail}

    if paging is not None:
        body = chunk_prefill_step
        in_specs = (pspecs, cspecs, P(None), P(None), P(None),
                    P(None), P(None))
    else:
        body = functools.partial(chunk_prefill_step, page_map=None,
                                 state_map=None)
        in_specs = (pspecs, cspecs, P(lay.bspec, None), P(lay.bspec),
                    P(lay.bspec))
    body_sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=cspecs,
        check_vma=False)

    sh = functools.partial(NamedSharding, mesh)
    jitted = jax.jit(
        body_sm,
        in_shardings=tuple(jax.tree.map(sh, s) for s in in_specs),
        out_shardings=jax.tree.map(sh, cspecs),
        donate_argnums=(1,),
    )
    return jitted, lay, rules


# --------------------------------------------------------------------------
# token-packed unified serving step (mixed prefill + decode per tick)
# --------------------------------------------------------------------------

@jax.named_scope("attn_kernel")
def packed_attention(q, k, v, valid, bias_self, k_new, v_new, axes, scale,
                     backend="auto"):
    """Exact attention for one token-packed tick — the ragged
    multi-request generalization of ``chunk_attention``.  Two disjoint
    column sets, two passes:

      * **prior columns** — each packed token attends its own request's
        already-written cache row (gathered per token), with validity
        stopping strictly before the request's tick-start offset, so
        the single-token flash-decode stats path applies verbatim with
        T tokens as the batch axis (no query folding needed: Nq = 1
        per token);
      * **intra-tick columns** — the T just-projected K/V rows under a
        segment-id-masked causal bias (``bias_self (1,T,T)``): tokens
        of different requests NEVER attend to each other, and each
        column contributes on the one shard pair owning its cache
        address.

    The stat triples merge associatively and the cross-shard combine
    runs over the sequence AND batch axes (``axes``) — shards that do
    not hold a token's cache row contribute empty stats and cancel —
    so packed output is exact and replicated on every device.

    q (T,1,Hq,hd); k,v (T,M,Hkv,hd) per-token gathered cache rows;
    valid (T,M); k_new,v_new (T,1,Hkv,hd).  Returns (T,1,Hq,hd)."""
    if use_pallas(backend):
        m1, l1, a1 = flash_decode_stats(q, k, v, valid, scale=scale,
                                        interpret=pallas_interpret())
    else:
        m1, l1, a1 = decode_stats_reference(q, k, v, valid, scale=scale)
    # (T,Hq,1,1)/(T,1,Hq,hd) -> the Nq = T shapes merge_stats expects
    stats_prior = (m1[:, :, 0, 0].T[None, :, :, None],
                   l1[:, :, 0, 0].T[None, :, :, None],
                   a1[:, 0][None])
    stats_self = chunk_softmax_stats(q[:, 0][None], k_new[:, 0][None],
                                     v_new[:, 0][None], bias_self, scale)
    m_p, l_p, acc_p = merge_stats(stats_prior, stats_self)
    out = _combine_exact(m_p, l_p, acc_p, axes)           # (1,T,Hq,hd)
    return out[0][:, None].astype(v.dtype)


def attn_packed(p, spec: AttnSpec, cfg: ModelConfig, x, c, meta,
                lay: ServeLayout, hp: ServeHParams,
                page_map=None, state_map=None):
    """Attention sublayer over one token-packed tick.

    ``x`` (T,1,D) replicated; ``meta = (slot, pos, off, is_prefill,
    row_loc, owned)`` — the per-token packing metadata (slot = -1 dead
    entry) plus this batch shard's local cache row per token and
    whether it owns it.  Writes every real token's K/V at its runtime
    (slot, position) address, attends exactly (prior columns via
    per-token flash-decode stats over the token's gathered cache row,
    intra-tick columns via the segment-masked causal pass, stat combine
    over sequence and batch axes), serves decode tokens the prism
    owner-view over the means cache when configured, and in prism mode
    advances the per-request Segment-Means running state over the REAL
    prefill tokens only — the flat-token twin of the chunk path's
    accumulation, so a prompt that arrives packed produces bit-equal
    gz/zsum (and kz/vz) to one that arrives chunked.

    Paged mode (``page_map`` (n_slots, ppr) set): every replica holds
    the full pool and packs every token (``row_loc = slot``), per-token
    K/V writes scatter to (page, offset) addresses, the token's virtual
    cache row gathers through its slot's page list, and the stat
    combine runs over the sequence axes ONLY (batch replicas are
    identical — a psum over them would over-count the prism
    owner-select).  Prism state reads/writes go through ``state_map``
    (n_slots,) into the state-page pool."""
    slot, pos, off, is_prefill, row_loc, owned = meta
    xn = norm(p["ln1"], x, cfg.norm_kind)
    rp = pos[:, None]                          # (T,1) token positions
    q = attn_project_q(p["attn"], spec, xn, rp)
    k_new, v_new = attn_project_kv(p["attn"], spec, xn, rp)
    scale = spec.head_dim ** -0.5
    axes_all = (tuple(lay.seq_axes) if page_map is not None
                else tuple(lay.seq_axes) + tuple(lay.ba))

    idx = _seq_index(lay.seq_axes)
    col, seq_owner, col_pos = _decode_cols(lay, idx, pos)
    alive = pos >= 0
    wr = seq_owner & owned & alive
    if page_map is not None:
        pc = c["k"].shape[1]
        b_loc = page_map.shape[0]
        row = jnp.clip(row_loc, 0, b_loc - 1)
        colc = jnp.clip(col, 0, lay.cap_l - 1)
        pages_t = jnp.take(page_map, row, axis=0)          # (T, ppr)
        pg = jnp.take_along_axis(pages_t, (colc // pc)[:, None],
                                 axis=1)[:, 0]
        k_pool = _write_pool(c["k"], k_new[:, 0], pg, colc % pc, wr)
        v_pool = _write_pool(c["v"], v_new[:, 0], pg, colc % pc, wr)
        new_c = dict(c, k=k_pool, v=v_pool)
        # one gather per SLOT, then a per-token row take — same shape
        # the dense path produces from its row cache
        with jax.named_scope("kv_gather"):
            k_t = jnp.take(_gather_pages(k_pool, page_map), row, axis=0)
            v_t = jnp.take(_gather_pages(v_pool, page_map), row, axis=0)
            mapped = jnp.take(jnp.repeat(page_map >= 0, pc, axis=1),
                              row, axis=0)                 # (T, cap_l)
    else:
        k_c = _write_packed(c["k"], k_new[:, 0], row_loc, col, wr)
        v_c = _write_packed(c["v"], v_new[:, 0], row_loc, col, wr)
        new_c = dict(c, k=k_c, v=v_c)
        b_loc = k_c.shape[0]
        row = jnp.clip(row_loc, 0, b_loc - 1)
        with jax.named_scope("kv_gather"):
            k_t = jnp.take(k_c, row, axis=0)   # (T, cap_l, Hkv, hd)
            v_t = jnp.take(v_c, row, axis=0)
        mapped = True

    # prior columns: strictly before the request's tick-start offset,
    # on the batch shard holding the slot (others: empty stats)
    valid = (mapped & (owned & alive)[:, None]
             & (col_pos[None, :] < jnp.maximum(off, 0)[:, None]))
    # intra-tick columns: same request only — tokens of different
    # requests must never attend to each other — causal, each column
    # on the one (batch, sequence) shard pair owning its address
    ok_q = (slot >= 0) & alive
    ok_k = ok_q & seq_owner & owned
    bias_self = jnp.where(
        (slot[None, :, None] == slot[None, None, :])
        & (pos[None, None, :] <= pos[None, :, None])
        & ok_q[None, :, None] & ok_k[None, None, :], 0.0, NEG_INF)
    out = packed_attention(q, k_t, v_t, valid, bias_self, k_new, v_new,
                           axes_all, scale, backend=hp.backend)

    if hp.decode_mode == "prism" and "kz" in c:
        # decode tokens take the paper's owner view over the means
        # cache (identical semantics to attn_decode) with every
        # per-request input gathered per token; prefill tokens keep
        # the exact combine above, as on the chunked path
        lo, hi, mid, _, shard_of = _means_meta(lay)
        if state_map is not None:
            st = jnp.clip(jnp.take(state_map, row),
                          0, c["gz"].shape[0] - 1)         # (T,)
            cnt_t = jnp.take(c["gz"], st, axis=0)          # (T, m)
            kz_t = jnp.take(c["kz"], st, axis=0)
            vz_t = jnp.take(c["vz"], st, axis=0)
        else:
            cnt_t = jnp.take(c["gz"], row, axis=0)         # (T, m)
            kz_t = jnp.take(c["kz"], row, axis=0)
            vz_t = jnp.take(c["vz"], row, axis=0)
        gz = jnp.where(
            (jnp.asarray(shard_of)[None, :] != idx)
            & (jnp.asarray(lo)[None, :] + cnt_t <= pos[:, None] + 1)
            & (owned & alive)[:, None],
            cnt_t, 0.0)
        valid_le = (mapped & (owned & alive)[:, None]
                    & (col_pos[None, :] <= pos[:, None]))
        sel = seq_owner & owned & alive & (is_prefill == 0)
        out_pz = decode_attention(q, k_t, v_t, valid_le, axes_all,
                                  scale, gz=gz, kz=kz_t, vz=vz_t,
                                  owner=sel, mode="prism",
                                  backend=hp.backend)
        out = jnp.where((is_prefill != 0)[:, None, None, None],
                        out, out_pz)

        # Segment-Means capture over the tick's REAL prefill tokens
        upd = (is_prefill != 0) & owned & alive
        r_upd = jnp.where(upd, row_loc, b_loc)             # OOB -> drop
        big = jnp.int32(1 << 30)
        off_b = jnp.full((b_loc,), big, jnp.int32).at[r_upd].min(
            jnp.where(upd, off, big), mode="drop")
        filled = jnp.zeros((b_loc,), jnp.int32).at[r_upd].max(
            jnp.where(upd, pos + 1, 0), mode="drop")
        act = jnp.zeros((b_loc,), jnp.int32).at[r_upd].max(
            upd.astype(jnp.int32), mode="drop") > 0
        onehot = r_upd[:, None] == jnp.arange(b_loc)[None, :]
        seg = ((jnp.asarray(lo)[None, :] <= pos[:, None])
               & (pos[:, None] <= jnp.asarray(hi)[None, :]))
        if state_map is not None:
            S = c["zsum"].shape[0]
            sr = jnp.clip(state_map, 0, S - 1)
            zs_prev = jnp.take(c["zsum"], sr, axis=0)      # (n_slots, ...)
        else:
            zs_prev = c["zsum"]
        zsum = jnp.where((act & (off_b == 0))[:, None, None], 0.0,
                         zs_prev)
        zsum = zsum + jnp.einsum("tb,tm,td->bmd",
                                 onehot.astype(jnp.float32),
                                 seg.astype(jnp.float32),
                                 x[:, 0].astype(jnp.float32))
        cnt = segment_fill_counts(lo, hi, filled)          # (b_loc, m)
        z = (zsum / jnp.maximum(cnt, 1.0)[..., None]).astype(x.dtype)
        kz, vz = attn_project_kv(p["attn"], spec,
                                 norm(p["ln1"], z, cfg.norm_kind),
                                 jnp.asarray(mid, jnp.float32))
        if state_map is not None:
            # unique state row per active slot; inactive slots route
            # OOB and keep their pool rows (the dense where(act) select)
            dst = jnp.where(act & (state_map >= 0), state_map, S)
            new_c["kz"] = c["kz"].at[dst].set(
                kz.astype(c["kz"].dtype), mode="drop")
            new_c["vz"] = c["vz"].at[dst].set(
                vz.astype(c["vz"].dtype), mode="drop")
            new_c["gz"] = c["gz"].at[dst].set(cnt, mode="drop")
            new_c["zsum"] = c["zsum"].at[dst].set(zsum, mode="drop")
        else:
            sel_b = act[:, None, None, None]
            new_c["kz"] = jnp.where(sel_b, kz.astype(c["kz"].dtype),
                                    c["kz"])
            new_c["vz"] = jnp.where(sel_b, vz.astype(c["vz"].dtype),
                                    c["vz"])
            new_c["gz"] = jnp.where(act[:, None], cnt, c["gz"])
            new_c["zsum"] = zsum

    o = attn_output(p["attn"], out)
    if cfg.parallel_block:
        o = o + mlp(p["mlp"], xn, cfg.mlp_kind)
    return o, new_c


def block_packed(cfg: ModelConfig, kind: str, p, shared, x, c, meta,
                 lay: ServeLayout, hp: ServeHParams,
                 page_map=None, state_map=None):
    """One residual block over a token-packed tick.  Returns
    (x, new_cache).  Same chunkable-kind restriction as the engine."""
    if kind in ("attn", "moe"):
        spec = T.attn_spec(cfg, kind)
        o, c = attn_packed(p, spec, cfg, x, c, meta, lay, hp,
                           page_map, state_map)
        x = x + o
        if cfg.parallel_block:
            return x, c
        if kind == "moe":
            y, _ = moe_apply(p["moe"], norm(p["ln2"], x, cfg.norm_kind),
                             cfg, DecodeMoeCtx(tp=hp.decode_tp))
            x = x + y
        elif cfg.d_ff:
            x = x + mlp(p["mlp"], norm(p["ln2"], x, cfg.norm_kind),
                        cfg.mlp_kind)
        return x, c
    if kind == "shared_attn":
        spec = T.attn_spec(cfg, "attn")
        o, c = attn_packed(shared, spec, cfg, x, c, meta, lay, hp,
                           page_map, state_map)
        x = x + o
        x = x + mlp(shared["mlp"], norm(shared["ln2"], x, cfg.norm_kind),
                    cfg.mlp_kind)
        return x, c
    raise ValueError(
        f"packed serving supports position-addressed attention caches "
        f"only (got block kind {kind!r})")


def make_packed_step(cfg: ModelConfig, mesh, params, *,
                     batch: int, cap: int, prefill_len: int,
                     token_budget: int,
                     hp: ServeHParams = ServeHParams(),
                     paging: PagedLayout | None = None):
    """jitted (params, cache, tokens (T,), slot (T,), pos (T,),
    off (T,), is_prefill (T,)) -> (logits (min(batch,T), V), cache)
    (paged: two trailing (page_map (B,ppr), state_map (B,)) inputs) —
    ONE compiled program per engine tick over a flat token-packed
    batch of ``T = token_budget`` mixed prefill + decode tokens.

    Each entry of the (T,) metadata vectors describes one packed
    token: ``slot`` the decode slot (cache batch row) it belongs to
    (-1 = dead entry; ragged budgets leave the tail dead), ``pos`` its
    global position, ``off`` the first position its request packs this
    tick (a decode token has off == pos — its prior columns are
    everything strictly before it, its own column rides the intra-tick
    pass), ``is_prefill`` 1 for prompt tokens (never sampled; the
    engine keeps the rewind) and 0 for decode tokens.  The LM head
    runs over the static decode prefix only — ``plan_tick`` packs
    decode tokens first, so ``logits`` is ``(min(batch, T), V)``.
    Per-tick cost scales with the REAL packed tokens, not
    ``n_slots × chunk_len`` — the fleet-level token packing the
    chunked engine's FLOP clock called for.

    The cache has the DECODE layout and is written in place with
    owner-masked scatters (no grow/insert round trip); in prism decode
    mode the program additionally advances the per-request
    Segment-Means state (kz/vz/gz/zsum) over the tick's real prompt
    tokens only.  Output is token-for-token identical to chunked and
    sequential serving in both decode modes (the packed equivalence
    tests pin this on the 2x4 mesh).  Returns
    (jitted, layout, rules, logits_spec)."""
    lay = make_layout(cfg, mesh, batch, cap, hp, prefill_len,
                      _paged_placement(hp, paging))
    assert token_budget >= 1, token_budget
    assert not hp.decode_tp, "packed serving does not support decode_tp"
    rules = param_specs(params, mesh, cfg.vocab_size)
    pspecs = spec_tree(rules)
    cspecs = cache_specs(cfg, lay, hp, paging)
    vocab_sharded = (rules["embed"]["table"].kind == "vocab")
    shared_rules = rules.get("shared")
    u, n_units, _ = cfg.scan_split
    unit_kinds = cfg.block_kinds[:u]
    for kind in cfg.block_kinds:
        if kind not in ("attn", "moe", "shared_attn"):
            raise ValueError(
                f"packed serving needs position-addressed attention "
                f"caches; arch {cfg.name!r} has block kind {kind!r}")
    axes = mesh_axes(mesh)
    n_b = int(np.prod([axes[a] for a in lay.ba])) if lay.ba else 1
    b_loc = batch // n_b
    head_rows = min(batch, token_budget)   # decode tokens pack first

    def packed_step(params_local, cache_local, tokens, slot, pos, off,
                    pre, page_map, state_map):
        trace_counts["packed_step"] += 1
        if paging is not None:
            # pool replicated over the batch axes: every replica packs
            # every token against the full page pool
            row_loc = slot
            owned = slot >= 0
        else:
            didx = _batch_index(lay.ba)
            row_loc = jnp.where(slot >= 0, slot - didx * b_loc, -1)
            owned = (row_loc >= 0) & (row_loc < b_loc)
        meta = (slot, pos, off, pre, row_loc, owned)
        x = embed_token(cfg, params_local, rules, tokens, pos,
                        sharded_vocab=vocab_sharded)

        def unit_body(x, xs):
            p_sl, c_sl = xs
            shared = (gather_tree(params_local["shared"], shared_rules)
                      if shared_rules else None)
            new = []
            for j, kind in enumerate(unit_kinds):
                p = gather_tree(p_sl[j], rules["scan"][j])
                x, nc = block_packed(cfg, kind, p, shared, x, c_sl[j],
                                     meta, lay, hp, page_map, state_map)
                new.append(nc)
            return x, tuple(new)

        x, new_stacks = lax.scan(
            unit_body, x,
            (tuple(params_local["scan"]), tuple(cache_local["scan"])))

        new_tail = []
        for t, tree in enumerate(params_local["tail"]):
            kind = cfg.block_kinds[n_units * u + t]
            p = gather_tree(tree, rules["tail"][t])
            shared = (gather_tree(params_local["shared"], shared_rules)
                      if shared_rules else None)
            x, nc = block_packed(cfg, kind, p, shared, x,
                                 cache_local["tail"][t], meta, lay, hp,
                                 page_map, state_map)
            new_tail.append(nc)

        # only decode tokens are ever sampled, and plan_tick packs them
        # first — at most n_slots of them — so the LM head runs over
        # the static decode prefix, not the whole budget (a prefill-
        # heavy tick would otherwise pay budget/n_slots times the
        # needed head FLOPs on logits nobody reads)
        with jax.named_scope("lm_head"):
            x = norm(params_local["final_norm"], x, cfg.norm_kind)
            table = output_table(params_local, cfg)
            xh = x[:head_rows, 0]
            logits = (xh @ table.T.astype(xh.dtype)).astype(jnp.float32)
            if cfg.logit_softcap:
                logits = cfg.logit_softcap * jnp.tanh(
                    logits / cfg.logit_softcap)
        return logits, {"scan": list(new_stacks), "tail": new_tail}

    vspec = P(None)                    # packed vectors ride replicated
    lspec = P(None, "model" if vocab_sharded else None)
    if paging is not None:
        body = packed_step
        in_specs = (pspecs, cspecs, vspec, vspec, vspec, vspec, vspec,
                    P(None), P(None))
    else:
        body = functools.partial(packed_step, page_map=None,
                                 state_map=None)
        in_specs = (pspecs, cspecs, vspec, vspec, vspec, vspec, vspec)
    body_sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=(lspec, cspecs),
        check_vma=False)

    sh = functools.partial(NamedSharding, mesh)
    jitted = jax.jit(
        body_sm,
        in_shardings=tuple(jax.tree.map(sh, s) for s in in_specs),
        out_shardings=(sh(lspec), jax.tree.map(sh, cspecs)),
        donate_argnums=(1,),
    )
    return jitted, lay, rules, lspec


def make_result_pack(n_slots: int):
    """Device-side result packing for the streaming engine
    (JetStream's ``ResultTokens`` idiom): build the ONE small device
    array a tick sends home, plus the merge that feeds the previous
    in-flight tick's on-device samples into the next tick's token
    batch.  Returns ``(pack, merge)``:

    * ``pack(logits (L, V), row_slot (L,) i32, is_decode (L,) i32,
      lengths (n_slots,) i32) -> data (n_slots, 4) i32`` — per-slot
      rows ``[token, valid, length, finite]``.  ``token`` is the
      greedy argmax of the slot's decode logits row this tick
      (first-max tie break — bit-identical to the host ``np.argmax``
      the synchronous engine samples with), ``valid`` is 1 iff the
      slot decoded this tick, ``length`` is the host-supplied cache
      length after the row, and ``finite`` is 0 iff the row held a
      non-finite logit (the NaN-quarantine trigger, reduced on device
      so the host copy stays one small array instead of (B, V)
      logits).  Slots without a decode row come back ``[0, 0, length,
      1]``.
    * ``merge(tok_host (T,) i32, src (T,) i32, prev (n_slots, 4) i32)
      -> (T,) i32`` — the double-buffer splice: entry ``i`` takes
      ``prev[src[i], 0]`` (the previous tick's sampled token for that
      slot, still device-resident) when ``src[i] >= 0``, else the
      host-planned ``tok_host[i]`` (prefill tokens, rewind re-feeds,
      and the first decode row after a reconciled tick).

    Both are ``jax.jit`` closures over plain ``jnp`` — logits arrive
    with whatever sharding the step program produced and GSPMD places
    the argmax/reduction accordingly.  Non-decode rows scatter to the
    out-of-bounds index ``n_slots`` and are dropped (``mode='drop'``,
    the same contract the paged pool's scatter writes rely on), so a
    ragged tick never corrupts a neighbouring slot's entry.
    """
    S = n_slots

    @jax.jit
    def pack(logits, row_slot, is_decode, lengths):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        fin = jnp.isfinite(logits).all(axis=-1)
        idx = jnp.where(is_decode > 0, row_slot, S)
        zeros = jnp.zeros((S,), jnp.int32)
        tok_s = zeros.at[idx].set(tok, mode="drop")
        val_s = zeros.at[idx].set(1, mode="drop")
        fin_s = jnp.ones((S,), jnp.int32).at[idx].set(
            fin.astype(jnp.int32), mode="drop")
        return jnp.stack(
            [tok_s, val_s, lengths.astype(jnp.int32), fin_s], axis=1)

    @jax.jit
    def merge(tok_host, src, prev):
        pick = prev[:, 0][jnp.clip(src, 0, S - 1)]
        return jnp.where(src >= 0, pick, tok_host)

    return pack, merge
