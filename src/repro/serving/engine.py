"""Continuous-batching serving engine over the sequence-sharded runtime.

Request lifecycle (docs/serving.md has the full tour)::

    submit ──> [FIFO queue] ──> admit into a free slot (host-side)
    ──> PACKED PREFILL: each engine tick, every live decode token plus
    prompt-chunk tokens from every mid-prefill request pack into ONE
    flat token batch, consumed by one compiled program (cost ∝ real
    tokens) ──> rewind to pos = len(prompt) - 1 ──> decode (packed
    ticks while anything is prefilling, the plain per-slot decode
    program otherwise) ──> host-side sampling ──> evict on EOS /
    max-tokens ──> slot freed, mid-flight.

Engine tick programs (compiled lazily, cached by ``(kind,
token_budget)`` so alternating tick kinds never retrace):

  * ``packed`` — the default hot path: one flat ``(token_budget,)``
    batch of mixed work per tick, planned Sarathi-style by
    ``FifoScheduler.plan_tick`` (decodes first, remaining budget
    filled with prompt tokens across ALL mid-prefill requests).
    Per-tick cost scales with the REAL packed tokens instead of
    ``n_slots × chunk_len`` — under saturation this out-amortizes even
    the gang flush, which the chunked engine could not.
  * ``decode`` — batch = n_slots single-token decode with a (B,) pos
    vector; used for ticks with nothing prefilling (every request at
    its own depth).
  * ``chunk``  — the ``prefill_mode='chunked'`` oracle: batch =
    n_slots, up to chunk_len prompt tokens per row at per-row runtime
    offsets, interleaved with decodes under ``decode_per_prefill``.
  * the legacy ``padded`` flush (prefill at decode capacity + one
    row splice per admitted request, via ``KVCache.insert_row``).

The admission rewind: prefill programs return no sampled tokens; when
a request's last prompt token lands, the slot starts decoding at
``pos = len(prompt) - 1``, re-feeding the last prompt token.  That
first decode rewrites the token's K/V row in place (an idempotent
rewrite — the computation is identical) and yields exactly the
teacher-forced next-token logits, in the configured decode mode.  TTFT
is measured to the first token sampled from those logits.  Packed and
chunk attention are exact (cross-shard stat combine), so engine output
is token-identical to sequential serving in every mode.

In ``prism`` decode mode the prefill programs also accumulate the
Segment-Means state (kz/vz + per-request counts gz + running sums
zsum) over REAL prompt columns only — short prompts no longer fold pad
columns into the remote-means approximation, which the padded flush
admission used to do (the old wart, kept reproducible via
``prefill_mode='padded'``).

``prefill_mode='chunked'`` (the PR-4 hot path) and
``prefill_mode='padded'`` (the PR-2 three-program admission) survive
as selectable oracles and benchmark baselines; docs/serving.md
quantifies the differences.
"""
from __future__ import annotations

import copy
import heapq
import math
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..core.protocol import PrismConfig
from ..models.config import ModelConfig
from ..runtime.faults import FaultInjector, FaultPlan
from ..runtime.offload import KVStore
from ..runtime.paging import AdmitPlan, make_paged_layout
from ..runtime.replica import MeansReplica
from ..runtime.serve import (ServeHParams, _paged_placement, make_layout,
                             make_chunk_prefill_step, make_kv_cache,
                             make_packed_step, make_prefill_step,
                             make_serve_step, seq_shards)
from .sampling import SamplingParams, sample_token
from .scheduler import EngineStats, FifoScheduler, Request, RequestState
from .spans import span


@dataclass(frozen=True)
class EngineConfig:
    """Validated engine configuration — the single construction path
    for ``ServingEngine`` (launch, examples, and benches all build one
    of these; the legacy kwarg constructor is a thin shim over it).

    ``__post_init__`` normalizes the derived fields so an EngineConfig
    is always self-consistent by the time the engine sees it:
    ``chunk_len`` clamps to ``[1, prefill_len]``, ``token_budget``
    defaults to ``n_slots + chunk_len`` (the smallest budget that keeps
    a full decode fleet moving while packing prefill work),
    ``prefill_mode='padded'`` forces the dense rowset (the legacy
    flush+insert admission predates paging), and ``prefix_cache``
    defaults on exactly where it is sound: the paged exact engine
    (paged prism keeps the aligned Segment-Means placement, where a
    partial page set never covers a position prefix)."""
    n_slots: int
    prefill_len: int
    max_cache: int
    hp: ServeHParams = ServeHParams()
    prism: PrismConfig | None = None
    decode_per_prefill: int = 4
    gang: bool = False
    chunk_len: int = 64
    prefill_mode: str = "packed"
    token_budget: int | None = None
    pad_id: int = 0
    paged: bool = True                 # page-table cache (the default)
    page_tokens: int | None = None     # page size in token positions
    n_pages: int | None = None         # pool size (default: slot parity)
    prefix_cache: bool | None = None   # shared-prefix COW reuse
    offload: bool = False              # host KVStore tier + preemption
    offload_bytes: int | None = None   # store capacity (None = unbounded)
    faults: FaultPlan | None = None    # seeded chaos plan (None = off)
    max_restarts: int = 3              # reset_for_refill bound per request
    degraded_grace: int = 2            # means-substituted ticks per loss
    replica_refresh: int = 16          # standby staleness refresh period
    restore_retries: int = 2           # KVStore.get retries before refill
    restore_backoff_s: float = 0.0     # exponential backoff base (sleep)

    def __post_init__(self):
        if self.max_restarts < 1:
            raise ValueError(f"max_restarts {self.max_restarts} < 1")
        if self.degraded_grace < 0:
            raise ValueError(f"degraded_grace {self.degraded_grace} < 0")
        if self.replica_refresh < 1:
            raise ValueError(
                f"replica_refresh {self.replica_refresh} < 1")
        if self.restore_retries < 0:
            raise ValueError(
                f"restore_retries {self.restore_retries} < 0")
        if self.restore_backoff_s < 0.0:
            raise ValueError(
                f"restore_backoff_s {self.restore_backoff_s} < 0")
        if self.faults is not None and not isinstance(self.faults,
                                                      FaultPlan):
            raise ValueError(
                f"faults must be a FaultPlan, got "
                f"{type(self.faults).__name__}")
        if self.prefill_mode not in ("packed", "chunked", "padded"):
            raise ValueError(f"prefill_mode {self.prefill_mode!r} not in "
                             "('packed', 'chunked', 'padded')")
        if self.n_slots < 1:
            raise ValueError(f"n_slots {self.n_slots} < 1")
        if not 1 <= self.prefill_len <= self.max_cache:
            raise ValueError(
                f"prefill_len {self.prefill_len} not in "
                f"[1, max_cache={self.max_cache}]")
        set_ = lambda k, v: object.__setattr__(self, k, v)
        set_("chunk_len",
             max(1, min(self.chunk_len, self.prefill_len)))
        if self.token_budget is None:
            set_("token_budget", self.n_slots + self.chunk_len)
        if self.token_budget < self.n_slots:
            raise ValueError(
                f"token_budget {self.token_budget} < n_slots "
                f"{self.n_slots}: every decoding slot needs its token "
                "in each tick")
        if self.prefill_mode == "padded" and self.paged:
            set_("paged", False)       # legacy flush+insert admission
        ok_prefix = self.paged and self.hp.decode_mode == "exact"
        if self.prefix_cache is None:
            set_("prefix_cache", ok_prefix)
        elif self.prefix_cache and not ok_prefix:
            raise ValueError(
                "prefix_cache requires the paged cache in exact decode "
                f"mode (paged={self.paged}, "
                f"decode_mode={self.hp.decode_mode!r})")
        if self.offload:
            if not self.paged:
                raise ValueError(
                    "offload requires the paged cache "
                    f"(paged={self.paged}, prefill_mode="
                    f"{self.prefill_mode!r}): spill/restore moves pages, "
                    "not dense rows")
            if self.gang:
                raise ValueError(
                    "offload/preemption is incompatible with gang "
                    "(static batching) admission")
        if self.prism is None:
            set_("prism", PrismConfig(
                P=1, cr=self.hp.means_cr,
                mode="prism" if self.hp.decode_mode == "prism"
                else "voltage"))


@dataclass
class EngineSnapshot:
    """Crash-consistent journal of one engine's complete serving state,
    taken between steps (``ServingEngine.snapshot``).  Host-side only:
    every live slot's cache footprint rides as the same bit-exact
    device→host gather the offload tier spills through, so a restored
    engine (``ServingEngine.restore`` on a fresh engine built from the
    SAME config/params/mesh) resumes token-identically to one that was
    never killed — in exact AND prism decode modes (the prism means
    rows kz/vz/gz/zsum are part of the gathered payload).

    ``active`` holds ``(slot, RequestState, payload, n_pages)`` per
    live slot; RNG state travels inside the deepcopied RequestStates
    (the per-request numpy Generators pickle their exact position)."""
    now: float                         # engine-clock time of the cut
    next_rid: int
    active: list                       # [(slot, state, payload, n_pages)]
    queues: dict                       # priority -> [Request] (fresh)
    resume: dict                       # priority -> [RequestState]
    pending: list                      # future arrivals (heap entries)
    suspended: dict                    # rid -> RequestState
    store_entries: list                # journalled SpilledEntry objects
    results: dict                      # rid -> finished RequestState
    failed: dict                       # rid -> failure reason
    stats: EngineStats
    injector: object                   # FaultInjector mid-stream (or None)
    decodes_since_prefill: int
    drain: bool
    has_deadlines: bool


class ServingEngine:
    """Multiplexes independent requests through a fixed pool of decode
    slots backed by one ``KVCache`` (paged pool + page table by
    default; the dense rowset survives as the ``paged=False`` oracle
    and the padded-admission path)."""

    def __init__(self, cfg: ModelConfig, mesh, params,
                 config: EngineConfig | None = None, *,
                 clock=time.monotonic, **kwargs):
        if config is None:
            config = EngineConfig(**kwargs)   # legacy kwarg construction
        elif kwargs:
            raise TypeError(
                f"pass either an EngineConfig or legacy kwargs, not "
                f"both (got extra {sorted(kwargs)})")
        unsupported = {k for k in cfg.block_kinds
                       if k in ("mlstm", "slstm", "mamba", "attn_local")}
        if unsupported:
            # The admission scheme relies on the cache being addressed
            # purely by global position: right-padded prefill leaves the
            # real rows exact, and the rewind rewrite is idempotent.
            # Recurrent SSM state consumes pad tokens (and the rewind
            # would double-feed the last prompt token), and the ring
            # window cache holds the padded tail, so those blocks need a
            # state-snapshot admission path — future work.  The static
            # serve path (repro.launch.serve without --engine) still
            # covers these architectures.
            raise ValueError(
                f"ServingEngine does not support block kinds "
                f"{sorted(unsupported)} (arch {cfg.name!r}); only "
                "global-attention caches (attn/moe/shared_attn) admit "
                "correctly")
        if cfg.arch_type == "vlm" or cfg.frontend:
            # those prefill signatures require an 'embeds' input the
            # engine's token-only admission path never builds
            raise ValueError(
                f"ServingEngine serves token prompts only; arch "
                f"{cfg.name!r} (arch_type={cfg.arch_type!r}, "
                f"frontend={cfg.frontend!r}) needs embedding inputs")
        self.cfg, self.mesh, self.params = cfg, mesh, params
        self.config = config
        hp, prism = config.hp, config.prism
        n_slots = config.n_slots
        self.n_slots, self.prefill_len = n_slots, config.prefill_len
        self.prefill_mode = config.prefill_mode
        self.chunk_len = config.chunk_len
        self.token_budget = int(config.token_budget)
        self.pad_id, self._clock = config.pad_id, clock
        self._hp, self._prism = hp, prism
        self._max_cache = config.max_cache

        # cache layout + paging geometry (the paged placement depends
        # only on decode mode, not on the pool shape, so the aligned
        # base layout can seed the page-size derivation)
        base = make_layout(cfg, mesh, n_slots, config.max_cache, hp,
                           config.prefill_len)
        self._paging = (self._derive_paging(base, config)
                        if config.paged else None)
        self.layout = make_layout(cfg, mesh, n_slots, config.max_cache,
                                  hp, config.prefill_len,
                                  _paged_placement(hp, self._paging))
        self._paged = self._paging is not None
        self._prefix_on = bool(config.prefix_cache) and self._paged
        # prism pages the means state per request but keeps the aligned
        # placement — rows are whole-row allocations, prefixes unshared
        self._full_row = self._paged and hp.decode_mode == "prism"

        # the one cache object: device storage + (paged) page table /
        # prefix cache + the alloc/bind/free lifecycle
        self._kv = make_kv_cache(cfg, mesh, self.layout, n_slots, hp,
                                 paging=self._paging,
                                 prefix_cache=self._prefix_on)
        # compiled-program cache: one entry per (kind, token_budget),
        # so ticks that alternate program kinds (packed <-> decode)
        # reuse the SAME jitted callable and never retrace —
        # runtime.serve.trace_counts pins this in the tests
        self._programs: dict = {}
        self._step = self._program("decode")
        if self.prefill_mode == "packed":
            self._packed = self._program("packed", self.token_budget)
        elif self.prefill_mode == "chunked":
            self._chunk = self._program("chunk")
        else:
            self._prefill = self._program("padded_prefill")

        self._sched = FifoScheduler(
            n_slots, decode_per_prefill=config.decode_per_prefill,
            gang=config.gang)
        self.stats = EngineStats(n_slots=n_slots)
        self._pending: list = []       # heap of (arrival, rid, Request)
        self._results: dict = {}       # rid -> RequestState
        self._plans: dict = {}         # rid -> reserved AdmitPlan
        self._next_rid = 0
        self._t0 = None                # clock origin (first submit/run)
        # seeded chaos: one injector per engine, shared with the store
        # so every fault kind draws from the same replayable plan
        self._injector = (FaultInjector(config.faults)
                          if config.faults is not None else None)
        # host offload tier: spilled KV pages + prism state, keyed by
        # rid.  Tests may swap in a capacity-limited / faulty store.
        self._store = (KVStore(capacity_bytes=config.offload_bytes,
                               injector=self._injector)
                       if config.offload else None)
        self._suspended: dict = {}     # rid -> parked RequestState
        self._from_store: set = set()  # rids whose reservation restores
        self._failed: dict = {}        # rid -> reason (deadline/restarts)
        self._has_deadlines = False    # any live request with a deadline
        # the NaN/inf guard rides the hot decode paths; the padded
        # flush admission cannot re-prefill an active slot in place,
        # so quarantine is only armed for the packed/chunked engines
        self._nan_guard = self.prefill_mode != "padded"
        # degraded-mesh serving (shard_loss): the standby replica is
        # armed only when the fault is schedulable AND the cache is
        # paged (captures ride the extract_slot gather; recovery rides
        # the page-table scrub + re-prefill path).  Non-paged engines
        # never draw the shard_loss stream.
        self._replica = None
        self._lost: set = set()        # sequence shards currently dead
        self._degraded_left = 0        # grace ticks before recovery
        if (self._injector is not None and self._paged
                and config.faults.spec("shard_loss").enabled):
            self._replica = MeansReplica(
                cfg, self.layout, hp, self._paging, n_slots,
                refresh_every=config.replica_refresh)

    @staticmethod
    def _derive_paging(base, config: EngineConfig):
        """Pool geometry from the layout.  The default page size aims
        for ~16-token spans while keeping ``page_cols`` a divisor of
        both the per-shard prefill region and row capacity (whole-page
        static slices everywhere)."""
        if config.page_tokens is None:
            pc = math.gcd(math.gcd(base.n_loc0, base.cap_l),
                          max(1, 16 // base.n_seq))
            page_tokens = pc * base.n_seq
        else:
            page_tokens = config.page_tokens
        return make_paged_layout(base, page_tokens=page_tokens,
                                 n_pages=config.n_pages,
                                 n_slots=config.n_slots)

    @property
    def kv_cache(self):
        """The engine's ``KVCache`` (page table, prefix cache, device
        storage) — exposed for tests, stats, and offload tiers."""
        return self._kv

    @property
    def kv_store(self):
        """The host offload tier (None unless ``offload=True``)."""
        return self._store

    # ------------------------------------------------------------------
    # compiled-program cache
    # ------------------------------------------------------------------
    def _program(self, kind: str, token_budget: int | None = None):
        """Build-or-fetch one of the engine's compiled step programs.

        Keyed by ``(kind, token_budget)``: repeated requests return the
        SAME jitted callable, so however the engine's ticks alternate
        (packed while anything prefills, plain decode otherwise) each
        program traces at most once per engine — the regression test in
        ``tests/test_packed_step.py`` asserts the bound via the
        trace-time counters in ``repro.runtime.serve``."""
        key = (kind, token_budget)
        if key in self._programs:
            return self._programs[key]
        cfg, mesh, params, hp = self.cfg, self.mesh, self.params, self._hp
        kw = dict(batch=self.n_slots, cap=self._max_cache,
                  prefill_len=self.prefill_len, hp=hp,
                  paging=self._paging)
        if kind == "decode":
            prog, lay, _, _ = make_serve_step(cfg, mesh, params, **kw)
            assert lay == self.layout, (lay, self.layout)
        elif kind == "decode_degraded":
            # the shard-loss variant: built lazily on the first
            # degraded tick, then cached like every other program
            prog, lay, _, _ = make_serve_step(cfg, mesh, params,
                                              degraded=True, **kw)
            assert lay == self.layout, (lay, self.layout)
        elif kind == "packed":
            prog, lay, _, _ = make_packed_step(
                cfg, mesh, params, token_budget=token_budget, **kw)
            assert lay == self.layout, (lay, self.layout)
        elif kind == "chunk":
            prog, lay, _ = make_chunk_prefill_step(
                cfg, mesh, params, chunk_len=self.chunk_len, **kw)
            assert lay == self.layout, (lay, self.layout)
        elif kind == "padded_prefill":
            # legacy padded admission, dense rowset only.  The captured
            # cache rows are sized straight to decode capacity (``cap``)
            # so admission is one splice per request — the old 'grow'
            # program is gone.  (make_prefill_step re-derives
            # PrismConfig.P from the layout's n_seq; only mode/cr of
            # ``prism`` matter here.)
            prog, lay_p, _, _ = make_prefill_step(
                cfg, mesh, params, self._prism, batch=self.n_slots,
                n=self.prefill_len, hp=hp, cap=self._max_cache)
            assert lay_p == self.layout, (lay_p, self.layout)
        else:
            raise ValueError(kind)
        self._programs[key] = prog
        return prog

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------
    def now(self) -> float:
        if self._t0 is None:
            self._t0 = self._clock()
        return self._clock() - self._t0

    def submit(self, prompt, *, max_new_tokens: int, eos_id=None,
               sampling: SamplingParams = SamplingParams(),
               arrival: float | None = None, priority: int = 0,
               deadline: float | None = None) -> int:
        """Queue one request.  ``arrival`` (engine-relative seconds) may
        lie in the future — the run loop holds the request back until
        the clock passes it, which is how Poisson traces are replayed.
        ``priority`` (higher = more urgent) picks the admission class;
        with ``offload=True`` a blocked higher-priority arrival preempts
        lower-priority work into the host KV store.  ``deadline`` is an
        absolute engine-clock time (same clock as ``arrival`` — wall
        seconds, or logical steps under an injected clock): once the
        clock passes it the request is cancelled wherever it is
        (queued, prefilling, decoding, spilled, or suspended), its
        pages/store bytes are reclaimed, and the miss is counted per
        priority class."""
        prompt = tuple(int(t) for t in prompt)
        if not 1 <= len(prompt) <= self.prefill_len:
            raise ValueError(
                f"prompt length {len(prompt)} not in [1, {self.prefill_len}]")
        if len(prompt) + max_new_tokens > self.layout.cap:
            raise ValueError(
                f"prompt {len(prompt)} + max_new {max_new_tokens} exceeds "
                f"cache capacity {self.layout.cap}")
        arrival = self.now() if arrival is None else arrival
        if deadline is not None:
            if deadline <= arrival:
                raise ValueError(
                    f"deadline {deadline} <= arrival {arrival}")
            self._has_deadlines = True
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt, max_new_tokens=max_new_tokens,
                      eos_id=eos_id, sampling=sampling,
                      arrival=arrival, priority=priority,
                      deadline=deadline)
        # always route through the arrival-ordered pending heap so a
        # late submit with an already-past arrival cannot jump ahead of
        # earlier arrivals still waiting to be released (FIFO by
        # arrival time; rid breaks ties in submit order)
        heapq.heappush(self._pending, (req.arrival, rid, req))
        self._release_arrivals()
        return rid

    def _release_arrivals(self):
        now = self.now()
        while self._pending and self._pending[0][0] <= now:
            self._sched.submit(heapq.heappop(self._pending)[2])
        self._sched.drain = not self._pending

    def next_arrival(self) -> float | None:
        """Arrival time of the earliest not-yet-released request —
        what an external drive loop (logical-clock benchmarks) jumps
        the clock to when the engine reports 'idle'."""
        return self._pending[0][0] if self._pending else None

    # ------------------------------------------------------------------
    # one engine iteration
    # ------------------------------------------------------------------
    def step(self) -> str:
        """Run one scheduler decision: a packed tick (chunked mode: a
        prefill chunk; padded mode: an admission flush), a decode step,
        a stall (chaos ``tick_delay``), or nothing ('idle').  Returns
        which.  In packed mode a tick with nothing prefilling falls
        through to the plain decode program — both programs live in the
        compiled-program cache, so alternating kinds never retrace."""
        kind = self._step_inner()
        if kind == "idle":
            self.stats.ticks_idle += 1
        if self._injector is not None:
            self.stats.faults_injected = self._injector.total_injected
            self.stats.faults_by_kind = dict(self._injector.injected)
        if self._store is not None:
            self.stats.store_get_retries = self._store.get_retries
        # standby-replica piggyback: after a healthy tick, capture any
        # newly decoding slot (plus one bounded staleness refresh).
        # NEVER while degraded — a capture would read the lost shard.
        if (self._replica is not None and not self._lost
                and kind in ("decode", "packed", "prefill")):
            self._replica.tick(self._kv, self._sched.decoding(),
                               self.stats.decode_steps
                               + self.stats.packed_ticks)
        return kind

    def _step_inner(self) -> str:
        sch = self._sched
        with span(self.stats, "serve.admit"):
            self._release_arrivals()
        if self.stats.t_start is None:
            self.stats.t_start = self.now()
        if self._has_deadlines:
            self._expire()
        if (self._injector is not None and sch.has_work
                and self._injector.fire("tick_delay")):
            return "stalled"           # the whole tick does nothing
        if (self._replica is not None and sch.has_work
                and self._injector.fire("shard_loss")):
            spec = self._injector.plan.spec("shard_loss")
            shard = (spec.shard if spec.shard is not None
                     else self._injector.pick("shard_loss",
                                              self.layout.n_seq))
            self._lose_shard(shard % self.layout.n_seq)
        if self._lost:
            return self._degraded_tick()

        if self.prefill_mode == "padded":
            if sch.want_prefill():
                return self._padded_flush()
        else:                              # packed or chunked
            with span(self.stats, "serve.admit"):
                self._admit_or_preempt()   # host-side: slots + pages
            if self.prefill_mode == "chunked":
                if sch.want_chunk():
                    return self._chunk_step()
            elif any(st.prefilling for st in sch.active.values()):
                return self._packed_tick()  # packed: one program a tick

        decoding = sch.decoding()
        if decoding:
            with span(self.stats, "serve.plan"):
                self._maybe_poison()
                tok = np.zeros(self.n_slots, np.int32)
                pos = np.full(self.n_slots, -1, np.int32)
                for st in decoding:
                    tok[st.slot] = st.next_token
                    pos[st.slot] = st.pos
            t0 = self.now()
            with span(self.stats, "serve.launch"):
                logits, self._kv.storage = self._step(
                    self.params, self._kv.storage, jnp.asarray(tok),
                    jnp.asarray(pos), *self._maps())
            with span(self.stats, "serve.wait"):
                rows = np.asarray(jax.device_get(logits))
            with span(self.stats, "serve.reconcile"):
                now = self.now()
                self.stats.step_latency.append(now - t0)
                self.stats.occupancy.append(len(sch.active) / self.n_slots)
                self.stats.decode_steps += 1
                # ONE fused non-finite reduction over the tick's logits
                # — the quarantine trigger costs a single host-side pass
                bad = (~np.isfinite(rows).all(axis=-1)
                       if self._nan_guard else None)
                for st in decoding:
                    if bad is not None and bad[st.slot]:
                        self._quarantine(st)
                    else:
                        self._advance_decode(st, rows[st.slot], now)
                sch.note_decode()
                self.stats.t_end = self.now()
            return "decode"
        return "idle"

    # ------------------------------------------------------------------
    # admission (page-aware) + per-tick device maps
    # ------------------------------------------------------------------
    def _maps(self) -> tuple:
        """The paged step programs take the per-slot (page_map,
        state_map) device arrays each tick; dense programs take
        nothing."""
        if not self._paged:
            return ()
        return (jnp.asarray(self._kv.page_map(self.n_slots)),
                jnp.asarray(self._kv.state_map(self.n_slots)))

    def _admit_gate(self, req) -> bool:
        """Page-aware admission check, consulted by the scheduler on
        the FIFO head: plan the request's page needs (prefix lookup
        included), reclaim LRU prefix entries if the free list is
        short, and RESERVE the pages before the scheduler pops the
        request — so several admissions in one engine loop can never
        double-count the free list."""
        kv = self._kv
        plan = kv.plan(req.prompt, req.max_new_tokens,
                       use_prefix=self._prefix_on,
                       full_row=self._full_row)
        if not kv.can_admit(plan, reclaim=False):
            if kv.prefix is not None:
                kv.prefix.reclaim(plan.fresh_pages)
                # reclaim may have dropped the very entry the plan
                # shares — re-plan against the surviving entries
                plan = kv.plan(req.prompt, req.max_new_tokens,
                               use_prefix=self._prefix_on,
                               full_row=self._full_row)
            if not kv.can_admit(plan, reclaim=False):
                self.stats.out_of_pages += 1
                return False
        if not kv.reserve(req.rid, plan):
            self.stats.out_of_pages += 1
            return False
        self._plans[req.rid] = plan
        return True

    def _restore_gate(self, st: RequestState) -> bool:
        """Admission check for a preempted request coming back from the
        host store: the plan's page count (and covered-token count)
        comes from the spilled entry instead of the prefix cache.  When
        the store LOST the entry (host-memory pressure / fault
        injection) the recovery is per-request and clean: reset the
        state for a full re-prefill and fall through to the ordinary
        fresh-admission gate — greedy/seeded sampling makes the rerun
        deterministic, and no other slot is touched."""
        kv, rid = self._kv, st.req.rid
        plan = kv.plan_restore(rid, self._store,
                               retries=self.config.restore_retries,
                               backoff_s=self.config.restore_backoff_s)
        if plan is None:
            self.stats.restore_misses += 1
            if st.restarts >= self.config.max_restarts:
                # the restart budget is spent: fail the head candidate
                # here (it holds no pages, no slot, no store entry) so
                # it cannot block the admission queue forever
                self._sched.cancel(rid)
                self._store.drop(rid)
                self._failed[rid] = "max_restarts"
                self.stats.failed_requests += 1
                return False
            self._note_restart(st)
            return self._admit_gate(st.req)
        if not kv.can_admit(plan, reclaim=False):
            if kv.prefix is not None:
                kv.prefix.reclaim(plan.fresh_pages)
            if not kv.can_admit(plan, reclaim=False):
                self.stats.out_of_pages += 1
                return False
        if not kv.reserve(rid, plan):
            self.stats.out_of_pages += 1
            return False
        self._plans[rid] = plan
        self._from_store.add(rid)
        return True

    def _gate(self, cand) -> bool:
        """Dispatch the page-aware admission gate on the candidate
        kind: fresh Request vs RequestState resuming from the store."""
        if isinstance(cand, RequestState):
            return self._restore_gate(cand)
        return self._admit_gate(cand)

    def _admit(self) -> list:
        """Assign free slots to queued requests; in paged mode each
        admission binds its reserved pages to the slot, then either a
        prefix hit fast-forwards the prompt past the tokens its shared
        pages already hold, or — for a resume — the spilled content is
        injected back into the freshly bound pages."""
        states = self._sched.admit(
            self.now(), gate=self._gate if self._paged else None)
        for st in states:
            if not self._paged:
                continue
            rid = st.req.rid
            self._kv.bind(rid, st.slot)
            plan = self._plans.pop(rid)
            if rid in self._from_store:
                self._from_store.discard(rid)
                if self._kv.restore(
                        rid, st.slot, self._store,
                        retries=self.config.restore_retries,
                        backoff_s=self.config.restore_backoff_s):
                    self.stats.restore_hits += 1
                else:
                    # entry evicted between plan and bind: the bound
                    # pages are large enough for a full re-prefill
                    self.stats.restore_misses += 1
                    if st.restarts >= self.config.max_restarts:
                        self._fail_active(st, "max_restarts")
                        continue
                    self._note_restart(st)
            elif plan.covered:
                st.nprefilled = plan.covered
                self.stats.prefix_hits += 1
                self.stats.prefix_tokens_saved += plan.covered
        return states

    def _admit_or_preempt(self) -> None:
        """The tick loop's admission move: admit what fits, then — with
        the offload tier on — spill strictly-lower-priority active work
        whenever the head admission candidate is still blocked (no free
        slot, or ``out_of_pages``).  Each spill is one device→host
        gather; the victim's RequestState parks on the scheduler's
        resume queue and restores through the normal admission path
        once pressure clears.  Equal-priority arrivals never preempt —
        the pool drains by itself and swapping would only thrash."""
        sch = self._sched
        if (self._injector is not None and sch.queued
                and self._injector.fire("admission_stall")):
            return                     # control plane stuck this tick
        if sch.want_admit():
            self._admit()
        if self._store is None:
            return
        while True:
            cand = sch.peek_admit()
            if cand is None:
                return
            prio = (cand.req.priority if isinstance(cand, RequestState)
                    else cand.priority)
            victim = sch.pick_victim(prio, now=self.now())
            if victim is None:
                return
            self._spill(victim)
            self._admit()

    def _spill(self, st: RequestState, *, requeue: bool = True) -> None:
        """Preempt an active request: gather its pages (+ prism state
        row) into the host store, free the device footprint, and either
        park it for automatic resume or hand it to the caller
        (suspend)."""
        with span(self.stats, "serve.spill"):
            n = self._kv.spill(st.req.rid, st.slot, self._store,
                               tokens=st.nprefilled)
        self.stats.preemptions += 1
        self.stats.spilled_pages += n
        self._drop_replica(st.slot)
        if requeue:
            self._sched.preempt(st)
        else:
            self._sched.remove(st)

    def _find_active(self, rid: int) -> RequestState | None:
        for st in self._sched.active.values():
            if st.req.rid == rid:
                return st
        return None

    # -- fault injection + quarantine ----------------------------------
    def _maybe_poison(self) -> None:
        """Chaos ``page_poison``: NaN-fill the first page of one
        decoding slot whose page is PRIVATE (refcount 1 — shared prefix
        pages are other requests' reads; corrupting one would break the
        neighbour-isolation guarantee the quarantine test pins).  Page 0
        always holds attended positions, so in exact decode mode the
        poison reaches the slot's next logits row and the isfinite
        guard fires the same tick.  Prism decode reads remote content
        through the precomputed means state, where raw-page poison can
        go undetected and leak through the free list — injection is
        exact-mode only.

        Injection happens only before PURE-DECODE ticks, where one
        poisoned page NaNs exactly its own slot's logits row (the
        quarantine test pins this isolation).  The token-packed program
        is excluded: its intra-tick pass masks cross-request columns
        with an additive ``NEG_INF`` bias and folds ``0 * NaN`` in the
        stat combine, so one poisoned slot's second-layer K/V
        projection would NaN every decode row in the tick — detection
        still fires and recovery stays token-identical, but the blast
        radius (spurious neighbour quarantines) would be wrong.  The
        packed path keeps its isfinite guard armed purely defensively;
        same-tick detection on the decode path means a scrub always
        lands before any packed tick can gather the poisoned page."""
        if (self._injector is None or not self._paged
                or self._hp.decode_mode != "exact"):
            return
        kv = self._kv
        cands = [st for st in self._sched.decoding()
                 if kv.slot_pages.get(st.slot)
                 and kv.table.refs[kv.slot_pages[st.slot][0]] == 1]
        if not cands or not self._injector.fire("page_poison"):
            return
        st = cands[self._injector.pick("page_poison", len(cands))]
        kv.poison_page(kv.slot_pages[st.slot][0])

    def _note_restart(self, st: RequestState) -> None:
        """The one re-prefill entry point: every recovery path (lost
        restore, quarantine) goes through here so the aggregate restart
        counter can never drift from the per-request ones."""
        st.reset_for_refill()
        self.stats.restarts += 1

    def _fail_active(self, st: RequestState, reason: str) -> None:
        """Fail-hard an ACTIVE request: scrub its private pages (NaN
        content must never rejoin the free list — masked attention
        still folds ``0 * NaN``), release pages + slot, and record the
        failure.  The request never reaches ``results()``."""
        if self._paged:
            self._kv.scrub_slot(st.slot)
            self._kv.free(st.slot, None)   # never register the prompt
        else:
            self._kv.reset_row(st.slot)
        self._drop_replica(st.slot)
        self._sched.remove(st)
        self._failed[st.req.rid] = reason
        self.stats.failed_requests += 1

    def _quarantine(self, st: RequestState) -> None:
        """Non-finite logits on a decode row: quarantine exactly this
        slot.  Recovery is the existing ``reset_for_refill`` re-prefill
        path — scrub the slot's pages and state row in place, then
        replay the prompt into the SAME bound pages; per-request seeded
        sampling makes the regenerated tokens identical.  Bounded by
        ``max_restarts``: a slot that keeps producing NaNs fails hard
        instead of burning ticks forever."""
        self.stats.quarantined += 1
        if st.restarts >= self.config.max_restarts:
            self._fail_active(st, "max_restarts")
            return
        if self._paged:
            try:
                # fork any COW-shared prefix pages private first — the
                # re-prefill rewrites position 0 onward, and shared
                # pages must never see a write
                self._kv.ensure_writable(st.slot, 0,
                                         len(st.req.prompt) - 1)
            except RuntimeError:
                self._fail_active(st, "quarantine_out_of_pages")
                return
            self._kv.scrub_slot(st.slot)
        else:
            self._kv.reset_row(st.slot)
        self._note_restart(st)
        self._drop_replica(st.slot)

    # -- degraded-mesh serving (shard loss) ----------------------------
    def _drop_replica(self, slot: int) -> None:
        if self._replica is not None:
            self._replica.drop(slot)

    def _lose_shard(self, shard: int) -> None:
        """A ``shard_loss`` fault fired: one sequence shard's KV is now
        unreadable.  Mark the degraded window open (the next
        ``_degraded_left`` ticks serve through the standby replicas)
        and empty the prefix cache — its shared pages hold content on
        the dead shard, so every future hit would splice garbage."""
        if shard in self._lost:
            return
        self._lost.add(shard)
        self.stats.shard_lost += 1
        self._degraded_left = self.config.degraded_grace
        if self._kv.prefix is not None:
            self._kv.prefix.clear()

    def _degraded_tick(self) -> str:
        """One serving tick with >= 1 sequence shard dead.  In-flight
        decode requests keep emitting finite tokens through the
        degraded program: the lost shard's exact columns are masked out
        of the stat combine and its standby Segment-Means columns are
        substituted through the log-g bias path (PRISM-bounded quality
        loss instead of failure).  No admissions, no prefill, no
        replica captures (a capture would gather the dead shard), and
        crucially NO evictions — a request that looks finished is HELD
        in its slot so its degraded tail tokens never reach
        ``results()``; recovery re-prefills it and regenerates every
        token exactly.  When the grace window closes (or nothing is
        decoding) the tick recovers instead."""
        sch = self._sched
        decoding = [st for st in sch.decoding() if not st.finished()]
        if self._degraded_left <= 0 or not decoding:
            return self._recover_from_loss()
        self.stats.degraded_ticks += 1
        self._degraded_left -= 1
        tok = np.zeros(self.n_slots, np.int32)
        pos = np.full(self.n_slots, -1, np.int32)
        for st in decoding:
            tok[st.slot] = st.next_token
            pos[st.slot] = st.pos
        lost = jnp.asarray(self._replica.lost_mask(self._lost))
        args = (jnp.asarray(tok), jnp.asarray(pos), *self._maps(), lost)
        if self._hp.decode_mode == "exact":
            args = args + (self._replica.assemble(),)
        step = self._program("decode_degraded")
        t0 = self.now()
        logits, self._kv.storage = step(self.params, self._kv.storage,
                                        *args)
        rows = np.asarray(jax.device_get(logits))
        now = self.now()
        self.stats.step_latency.append(now - t0)
        self.stats.occupancy.append(len(sch.active) / self.n_slots)
        bad = ~np.isfinite(rows).all(axis=-1)
        for st in decoding:
            if bad[st.slot]:
                continue    # don't quarantine: recovery resets it anyway
            self._advance_degraded(st, rows[st.slot], now)
        sch.note_decode()
        self.stats.t_end = self.now()
        return "degraded"

    def _advance_degraded(self, st: RequestState, logits_row,
                          now) -> None:
        """Advance one decode slot on a degraded tick: sample and
        stream the approximate token, but never finish/evict — the slot
        is held until ``_recover_from_loss`` resets it, which is what
        keeps the final ``results()`` oracle-identical."""
        t = sample_token(logits_row, st.req.sampling, st.rng)
        st.generated.append(t)
        self.stats.generated_tokens += 1
        if st.ttft is None:
            st.ttft = now - st.req.arrival
            self.stats.ttft.append(st.ttft)
        st.pos += 1
        st.next_token = t

    def _recover_from_loss(self) -> str:
        """Close the degraded window: rebuild EXACT KV for every active
        request and return to exact serving.  Device-side content is
        gone on the lost shard, so each slot goes through the
        deterministic ``reset_for_refill`` re-prefill (scrub + replay
        the prompt into the same bound pages; seeded sampling makes the
        rerun token-identical to the uninterrupted oracle).  Spilled /
        suspended entries live HOST-side in the offload store and
        survive shard loss untouched — they restore through the normal
        admission path after recovery.  Requests admitted after this
        tick never see the degraded program."""
        sch = self._sched
        for _slot, st in sorted(sch.active.items()):
            if st.restarts >= self.config.max_restarts:
                self._fail_active(st, "max_restarts")
                continue
            try:
                # fork COW-shared prefix pages private before the
                # re-prefill rewrites position 0 onward
                self._kv.ensure_writable(st.slot, 0,
                                         len(st.req.prompt) - 1)
            except RuntimeError:
                self._fail_active(st, "degraded_out_of_pages")
                continue
            self._kv.scrub_slot(st.slot)
            self._note_restart(st)
        if self._replica is not None:
            self._replica.drop_all()
        self._lost.clear()
        self._degraded_left = 0
        return "recovered"

    # -- deadline expiry -----------------------------------------------
    def _miss(self, req, *, st: RequestState | None = None,
              now: float | None = None) -> None:
        self.stats.deadline_miss += 1
        cls = self.stats.deadline_miss_by_class
        cls[req.priority] = cls.get(req.priority, 0) + 1
        self._failed[req.rid] = "deadline"
        if st is not None and st.t_finish is None and now is not None:
            st.t_finish = now

    def _expire(self) -> None:
        """Cancel every request whose deadline has passed, wherever it
        sits in the lifecycle: future arrival, fresh queue, resume
        queue (spilled), suspended (spilled), or active (prefilling or
        decoding).  Each path reclaims exactly the resources that state
        holds — heap entry, queue position, store bytes, or bound
        pages + state row + slot — so a deadline storm leaves the
        engine leak-free (the chaos audits pin this)."""
        now = self.now()
        dead = lambda req: req.deadline is not None and now >= req.deadline
        # future arrivals (heap)
        expired = [e for e in self._pending if dead(e[2])]
        if expired:
            self._pending = [e for e in self._pending if not dead(e[2])]
            heapq.heapify(self._pending)
            for _, _, req in expired:
                self._miss(req)
        sch = self._sched
        # fresh queues + resume queues (spilled entries also free store
        # bytes)
        for q in sch.queues.values():
            for req in [r for r in q if dead(r)]:
                q.remove(req)
                self._miss(req)
        for q in sch.resume.values():
            for st in [s for s in q if dead(s.req)]:
                q.remove(st)
                if self._store is not None:
                    self._store.drop(st.req.rid)
                self._miss(st.req, st=st, now=now)
        # suspended sessions (store entry, no slot)
        for rid in [r for r, s in self._suspended.items()
                    if dead(s.req)]:
            st = self._suspended.pop(rid)
            self._store.drop(rid)
            self._miss(st.req, st=st, now=now)
        # active slots: free pages + state row + slot.  The prompt is
        # never registered in the prefix cache — a cancelled request
        # may hold a partially-prefilled page set.
        for st in [s for s in list(sch.active.values()) if dead(s.req)]:
            if self._paged:
                self._kv.free(st.slot, None)
            else:
                self._kv.reset_row(st.slot)
            self._drop_replica(st.slot)
            sch.remove(st)
            self._miss(st.req, st=st, now=now)
        self._has_deadlines = any(
            r.deadline is not None
            for r in self._live_requests())

    def _live_requests(self):
        """Every not-yet-finished Request the engine still tracks."""
        for _, _, req in self._pending:
            yield req
        for q in self._sched.queues.values():
            yield from q
        for q in self._sched.resume.values():
            for st in q:
                yield st.req
        for st in self._sched.active.values():
            yield st.req
        for st in self._suspended.values():
            yield st.req

    # -- public offload controls ---------------------------------------
    def preempt(self, rid: int) -> bool:
        """Force-preempt an active request into the host store; it
        requeues for automatic restore (fair resume ordering).  The
        tick loop preempts on priority pressure by itself — this hook
        exists for tests, draining, and external policies."""
        st = self._find_active(rid)
        if st is None or self._store is None:
            return False
        self._spill(st, requeue=True)
        return True

    def suspend(self, rid: int) -> bool:
        """Evict an idle multi-turn session to the host tier.  The
        request keeps its KV in the store but does NOT requeue — it
        consumes no slot, no pages, and no scheduler attention until
        ``resume(rid)``.  ``run()`` does not wait for suspended
        requests."""
        st = self._find_active(rid)
        if st is None or self._store is None:
            return False
        self._spill(st, requeue=False)
        self._suspended[rid] = st
        return True

    def resume(self, rid: int) -> bool:
        """Requeue a suspended session; its cache restores through the
        normal admission path on the next tick with free capacity."""
        st = self._suspended.pop(rid, None)
        if st is None:
            return False
        self._sched.push_resume(st)
        return True

    def cancel(self, rid: int) -> bool:
        """Drop a request wherever it sits in the lifecycle: pending
        (future arrival), queued, parked for resume, suspended, or
        ACTIVE (prefilling or decoding mid-flight).  Each path reclaims
        exactly what that state holds — heap entry, queue position,
        store bytes, or bound pages + state row + slot (the same
        zero-leak release the deadline sweep uses; the prompt is never
        prefix-registered, since a cancelled request may hold a
        partially-prefilled page set).  A cancelled request lands in
        ``failed()`` under reason ``'cancelled'`` and never reaches
        ``results()``.  Callers driving the engine through the
        streaming front-end must cancel via ``StreamingEngine.cancel``,
        which drains the in-flight tick pipeline first."""
        for i, (_, r, req) in enumerate(self._pending):
            if r == rid:
                self._pending.pop(i)
                heapq.heapify(self._pending)
                self.stats.cancelled += 1
                return True
        if self._sched.cancel(rid) is not None:
            if self._store is not None:
                self._store.drop(rid)
            self.stats.cancelled += 1
            return True
        if self._suspended.pop(rid, None) is not None:
            self._store.drop(rid)
            self.stats.cancelled += 1
            return True
        st = self._find_active(rid)
        if st is not None:
            if self._paged:
                self._kv.free(st.slot, None)
            else:
                self._kv.reset_row(st.slot)
            self._drop_replica(st.slot)
            self._sched.remove(st)
            st.t_finish = self.now()
            self._failed[rid] = "cancelled"
            self.stats.cancelled += 1
            return True
        return False

    # -- crash-consistent snapshot / restore ---------------------------
    def snapshot(self) -> EngineSnapshot:
        """Journal the engine's complete serving state into one
        host-side object: every live slot's pages + prism state row
        (the PR-7 bit-exact gather, non-destructive), the scheduler
        queues, pending arrivals, suspended sessions, the offload
        store's entries, per-request RNG states (inside the deepcopied
        RequestStates), stats, and the fault injector's stream
        position.  Must be called between steps (no reservation in
        flight — always true outside ``step()``)."""
        if not self._paged:
            raise ValueError(
                "snapshot requires the paged cache (paged=True): the "
                "journal rides the page gather path")
        assert not self._plans and not self._from_store, (
            "snapshot mid-admission: call between engine steps")
        if self._lost:
            raise ValueError(
                "snapshot during a degraded window (shard lost): the "
                "page gather would read the dead shard — recover first")
        active = []
        for slot, st in sorted(self._sched.active.items()):
            active.append((slot, copy.deepcopy(st),
                           self._kv.extract_slot(slot),
                           len(self._kv.slot_pages[slot])))
        return EngineSnapshot(
            now=self.now(),
            next_rid=self._next_rid,
            active=active,
            queues={p: list(q) for p, q in self._sched.queues.items()
                    if q},
            resume={p: copy.deepcopy(q)
                    for p, q in self._sched.resume.items() if q},
            pending=copy.deepcopy(self._pending),
            suspended=copy.deepcopy(self._suspended),
            store_entries=(copy.deepcopy(self._store.entries())
                           if self._store is not None else []),
            results=copy.deepcopy(self._results),
            failed=dict(self._failed),
            stats=copy.deepcopy(self.stats),
            injector=copy.deepcopy(self._injector),
            decodes_since_prefill=self._sched._decodes_since_prefill,
            drain=self._sched.drain,
            has_deadlines=self._has_deadlines)

    def restore(self, snap: EngineSnapshot) -> None:
        """Rebuild the journalled serving state into THIS engine —
        which must be fresh (no requests yet) and built from the same
        config over the same params/mesh.  Each journalled slot
        re-reserves its page count through the normal two-phase
        admission and injects its payload; page ids may differ from the
        killed engine's, which the per-tick maps make invisible.  The
        prefix cache intentionally starts cold (it is a cache — losing
        it costs recompute, never tokens).  The snapshot object is not
        consumed: the same journal can restore any number of fresh
        engines."""
        if not self._paged:
            raise ValueError("restore requires the paged cache")
        if self._next_rid or self._sched.active or self._sched.queued:
            raise ValueError("restore target must be a fresh engine")
        snap = copy.deepcopy(snap)     # keep the journal re-restorable
        sch = self._sched
        sch.queues = {p: deque(q) for p, q in snap.queues.items()}
        sch.resume = snap.resume
        sch.drain = snap.drain
        sch._decodes_since_prefill = snap.decodes_since_prefill
        self._pending = snap.pending
        heapq.heapify(self._pending)
        self._suspended = snap.suspended
        if self._store is not None:
            self._store.adopt(snap.store_entries)
        for slot, st, payload, n_pages in snap.active:
            key = ("__restore__", slot)
            if not self._kv.reserve(key, AdmitPlan(total_pages=n_pages,
                                                   fresh_pages=n_pages)):
                raise RuntimeError(
                    f"restore out of pages binding slot {slot}")
            self._kv.bind(key, slot)
            st.slot = slot
            self._kv.inject_slot(slot, payload)
            sch.active[slot] = st
            sch.free_slots.remove(slot)
        self._results = snap.results
        self._failed = snap.failed
        self.stats = snap.stats
        self._next_rid = snap.next_rid
        self._has_deadlines = snap.has_deadlines
        if snap.injector is not None:
            self._injector = snap.injector
            if self._store is not None:
                self._store._injector = snap.injector
        # clock continuity: the restored engine's now() resumes at the
        # snapshot cut, so arrivals/deadlines keep their meaning
        self._t0 = self._clock() - snap.now

    def failed(self) -> dict:
        """{rid: reason} for requests the engine gave up on (deadline
        miss, max_restarts exceeded) — disjoint from ``results()``."""
        return dict(self._failed)

    def _advance_decode(self, st, logits_row, now):
        """Sample one token for a decode-phase request and advance /
        evict it — shared by the decode step and the packed tick."""
        t = sample_token(logits_row, st.req.sampling, st.rng)
        self._advance_token(st, t, now)

    def _advance_token(self, st, t: int, now):
        """Advance a decode-phase request by one ALREADY-SAMPLED token
        (append, TTFT, position, finish/evict).  The sync tick loop
        reaches it through ``_advance_decode`` (host sampling); the
        streaming engine calls it directly with the device-argmaxed
        token carried home in the tick's ``ResultTokens`` — identical
        state transitions either way, which is what keeps streamed
        output token-identical to the synchronous engine."""
        st.generated.append(t)
        self.stats.generated_tokens += 1
        if st.ttft is None:
            st.ttft = now - st.req.arrival
            self.stats.ttft.append(st.ttft)
        st.pos += 1
        st.next_token = t
        if st.finished():
            if self._paged:
                # release the request's pages (prefix-registered full
                # prompt pages survive under their cache entries)
                self._kv.free(st.slot, st.req.prompt
                              if self._prefix_on else None)
            self._drop_replica(st.slot)
            self._sched.evict(st, now)
            self._results[st.req.rid] = st
            self.stats.completed += 1

    def _packed_tick(self) -> str:
        """ONE compiled program for the whole engine tick: every live
        decode token plus prompt-chunk tokens from every mid-prefill
        request, flattened into a (token_budget,) ragged batch (dead
        tail entries pass slot = -1).  Decode rows are sampled from the
        returned logits; prefill rows only advance their request's
        offset (the rewind then re-feeds the last prompt token, exactly
        as in chunked mode, so output stays token-identical)."""
        sch = self._sched
        with span(self.stats, "serve.plan"):
            decode, prefill = sch.plan_tick(self.token_budget)
            tb = self.token_budget
            tok = np.zeros(tb, np.int32)
            slot = np.full(tb, -1, np.int32)
            pos = np.full(tb, -1, np.int32)
            off = np.full(tb, -1, np.int32)
            pre = np.zeros(tb, np.int32)
            i = 0
            dec_rows = []
            for st in decode:
                tok[i], slot[i] = st.next_token, st.slot
                pos[i] = off[i] = st.pos
                dec_rows.append((i, st))
                i += 1
            n_prefill = 0
            for st, take in prefill:
                o = st.nprefilled
                tok[i:i + take] = st.req.prompt[o:o + take]
                slot[i:i + take] = st.slot
                pos[i:i + take] = np.arange(o, o + take)
                off[i:i + take] = o
                pre[i:i + take] = 1
                i += take
                n_prefill += take

        t0 = self.now()
        for st, _ in prefill:
            st.note_dispatch(t0)
        with span(self.stats, "serve.launch"):
            logits, self._kv.storage = self._packed(
                self.params, self._kv.storage, jnp.asarray(tok),
                jnp.asarray(slot), jnp.asarray(pos), jnp.asarray(off),
                jnp.asarray(pre), *self._maps())
        with span(self.stats, "serve.wait"):
            rows = np.asarray(jax.device_get(logits))
        with span(self.stats, "serve.reconcile"):
            now = self.now()
            self.stats.step_latency.append(now - t0)
            self.stats.occupancy.append(len(sch.active) / self.n_slots)
            self.stats.packed_ticks += 1
            self.stats.packed_decode_tokens += len(dec_rows)
            self.stats.packed_prefill_tokens += n_prefill
            self.stats.prefill_tokens += n_prefill
            # ONE fused non-finite reduction over the tick's logits; only
            # decode rows sample, so only they can quarantine
            bad = (~np.isfinite(rows).all(axis=-1)
                   if self._nan_guard else None)
            for j, st in dec_rows:
                if bad is not None and bad[j]:
                    self._quarantine(st)
                else:
                    self._advance_decode(st, rows[j], now)
            for st, take in prefill:
                st.nprefilled += take
                if not st.prefilling:
                    st.begin_decode()      # rewind: re-feed last token
            self.stats.t_end = self.now()
        return "packed"

    def _chunk_step(self) -> str:
        """Advance EVERY mid-prefill request by one chunk (each at its
        own offset) in a single compiled call.  The empty-states guard
        keeps the no-mid-prefill-no-launch invariant local (the
        scheduler's ``want_chunk`` enforces it on the step() path; a
        direct caller gets the same no-op), and the real-vs-padded
        chunk-token split is tracked so ``EngineStats.summary`` can
        report how much of each launched ``(n_slots, chunk_len)``
        program was live work — the waste the FLOP model exposed and
        packed mode eliminates."""
        sch = self._sched
        states = sch.prefilling()
        if not states:                     # nothing mid-prefill: no-op
            return "idle"
        c = self.chunk_len
        tokens = np.full((self.n_slots, c), self.pad_id, np.int32)
        off = np.full(self.n_slots, -1, np.int32)
        nreal = np.zeros(self.n_slots, np.int32)
        for st in states:
            take = min(c, len(st.req.prompt) - st.nprefilled)
            tokens[st.slot, :take] = st.req.prompt[
                st.nprefilled:st.nprefilled + take]
            off[st.slot] = st.nprefilled
            nreal[st.slot] = take
        now = self.now()
        for st in states:
            st.note_dispatch(now)
        self._kv.storage = self._chunk(self.params, self._kv.storage,
                                       jnp.asarray(tokens), jnp.asarray(off),
                                       jnp.asarray(nreal), *self._maps())
        for st in states:
            st.nprefilled += int(nreal[st.slot])
            if not st.prefilling:
                st.begin_decode()          # rewind: re-feed last token
        sch.note_chunk()
        real = int(nreal.sum())
        self.stats.prefills += 1
        self.stats.prefill_chunks += 1
        self.stats.prefill_tokens += real
        self.stats.chunk_tokens_real += real
        self.stats.chunk_tokens_padded += self.n_slots * c - real
        self.stats.t_end = self.now()
        return "prefill"

    def _padded_flush(self) -> str:
        """Legacy admission: right-pad every admitted prompt to
        ``prefill_len``, one monolithic prefill (its cache rows come out
        sized to decode capacity — no separate grow step), splice each
        row into its slot, start decoding at the rewind position."""
        sch = self._sched
        batch = np.full((self.n_slots, self.prefill_len), self.pad_id,
                        np.int32)
        states = sch.admit(self.now())
        now = self.now()
        for i, st in enumerate(states):
            batch[i, :len(st.req.prompt)] = st.req.prompt
            st.note_dispatch(now)
        _, fresh = self._prefill(self.params, {"tokens":
                                               jnp.asarray(batch)})
        for i, st in enumerate(states):
            self._kv.insert_row(fresh, i, st.slot)
            st.begin_decode()
            self.stats.prefill_tokens += len(st.req.prompt)
        self.stats.prefills += 1
        self.stats.t_end = self.now()
        return "prefill"

    # ------------------------------------------------------------------
    # drive to completion
    # ------------------------------------------------------------------
    def run(self) -> dict:
        """Step until every submitted request (including future
        arrivals) has finished.  Returns {rid: [generated token ids]}."""
        while True:
            kind = self.step()
            if kind != "idle":
                continue
            if self._pending:
                # nothing runnable until the next arrival — wait it
                # out.  An injected clock that doesn't tick with wall
                # time (e.g. a logical StepClock) is fast-forwarded to
                # the arrival instead, so run() terminates under both.
                before = self.now()
                dt = self.next_arrival() - before
                if dt > 0:
                    time.sleep(min(dt, 0.05))
                    if self.now() <= before:
                        self._t0 -= dt
                continue
            if not self._sched.has_work:
                break
        return self.results()

    def results(self) -> dict:
        return {rid: list(st.generated)
                for rid, st in sorted(self._results.items())}

    def request_stats(self) -> dict:
        """{rid: times and counts} of every admitted request, active or
        finished, on the engine clock: its ``arrival``, ``t_admit``
        (slot assigned), ``t_first_dispatch`` (first prompt token
        dispatched), ``ttft_s`` and ``latency_s`` from arrival (None
        until reached), and ``tokens`` generated so far."""
        states = {st.req.rid: st for st in self._sched.active.values()}
        states.update(self._results)
        return {rid: {"arrival": st.req.arrival,
                      "t_admit": st.t_admit,
                      "t_first_dispatch": st.t_first_dispatch,
                      "ttft_s": st.ttft,
                      "latency_s": (st.t_finish - st.req.arrival
                                    if st.t_finish is not None else None),
                      "tokens": len(st.generated)}
                for rid, st in sorted(states.items())}
