"""Request-level scheduling for the continuous-batching engine.

FIFO admission over a fixed pool of decode slots.  Admission assigns a
slot immediately (it is host-side bookkeeping); the prompt is then
prefilled either *packed* or *in chunks*.

In the default **packed** mode the scheduler is a Sarathi-style
token-budget planner: ``plan_tick(token_budget)`` gives every decoding
slot its one decode token first, then fills the remaining budget with
prompt-chunk tokens across *all* mid-prefill requests (ascending slot
order) — one flat ragged batch per engine tick, consumed by ONE
compiled program.  Decode streams are structurally protected (their
token is always in the tick), so the ``decode_per_prefill`` interleave
bound is retired in packed mode.

In the legacy **chunked** mode the scheduler interleaves chunk steps
with decode steps: once streams are decoding, at most one chunk per
``decode_per_prefill`` decode steps, so a long prompt (or a burst of
arrivals) can never starve running streams of decode bandwidth for
more than a bounded number of steps.  An engine with nothing decoding
always chunks immediately — there is no decode work to protect, and
TTFT is all that matters.  All mid-prefill rows advance *together* in
one batched chunk call (each at its own offset), so concurrent
admissions don't serialize.

The legacy ``padded`` engine mode still uses the all-or-nothing policy
(``want_prefill``): one whole pad-to-``prefill_len`` flush per
``decode_per_prefill`` decode steps.

``gang=True`` degrades admission to classic *static batching* — admit
only into an empty pool, then drain it completely — which is the
baseline the engine-throughput benchmark compares against.

**Priority classes and preemption** (PR 7): every request carries an
integer ``priority`` (higher = more urgent; default 0 keeps the
scheduler exactly FIFO).  Admission serves the highest non-empty class
first; within a class, *resume* candidates (requests preempted earlier,
ordered by original arrival) go before fresh ones, and fresh ones stay
FIFO.  ``pick_victim`` implements the preempt policy: when a
higher-priority candidate would otherwise block (no slot, or
``out_of_pages``), the engine spills the lowest-priority
longest-remaining active request to the host KV store
(``runtime/offload.py``) and parks its RequestState on the resume
queue.  A preempted request keeps its RequestState — generated tokens,
decode position, and sampler RNG survive, so a restore continues
bit-identically.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field

import numpy as np

from .sampling import SamplingParams


@dataclass(frozen=True)
class Request:
    rid: int
    prompt: tuple
    max_new_tokens: int
    eos_id: int | None = None
    sampling: SamplingParams = SamplingParams()
    arrival: float = 0.0             # absolute clock time of arrival
    priority: int = 0                # higher = more urgent; 0 = default
    deadline: float | None = None    # absolute clock time; None = never


class RequestState:
    """Mutable per-request serving state while a request owns a slot.

    A request starts in the *prefill* phase: ``nprefilled`` counts the
    prompt tokens already laid down (the engine advances it one chunk
    at a time; the legacy padded mode jumps it to the full length in
    one flush).  ``begin_decode`` performs the *rewind* to the decode
    phase: the slot starts at ``pos = len(prompt) - 1`` and re-feeds
    the final prompt token — the first decode step rewrites that K/V
    row in place (an idempotent rewrite: chunked prefill already wrote
    it, and the computation is identical) and returns the exact
    teacher-forced next-token logits.  Everything past ``pos`` is
    invisible (``col_pos <= pos``) until real decoded tokens land there.
    """
    __slots__ = ("req", "slot", "pos", "next_token", "nprefilled",
                 "generated", "rng", "t_admit", "t_first_dispatch",
                 "ttft", "t_finish", "restarts", "epoch", "inflight")

    def __init__(self, req: Request, slot: int, t_admit: float):
        self.req = req
        self.slot = slot
        self.pos = -1                  # decode position; -1 while prefilling
        self.next_token = None
        self.nprefilled = 0            # prompt tokens laid down so far
        self.generated: list = []
        self.rng = req.sampling.make_rng()
        self.t_admit = t_admit
        self.t_first_dispatch = None   # first prompt token dispatched
        self.ttft = None
        self.t_finish = None
        self.restarts = 0              # re-prefills after a lost restore
        self.epoch = 0                 # bumped on every rewind-to-zero;
        # the streaming engine stamps in-flight rows with it so results
        # that raced a quarantine/restart reconcile as stale
        self.inflight = 0              # dispatched, not-yet-reconciled rows

    @property
    def prefilling(self) -> bool:
        return self.nprefilled < len(self.req.prompt)

    @property
    def remaining(self) -> int:
        """Tokens of work left (prompt still to prefill + tokens still
        to generate) — the preempt policy's tie-breaker."""
        return ((len(self.req.prompt) - self.nprefilled)
                + (self.req.max_new_tokens - len(self.generated)))

    def reset_for_refill(self):
        """Restart from scratch after the offload store lost this
        request's spilled KV (host-memory pressure): clean per-request
        recovery — re-prefill the prompt, regenerate from a fresh
        sampler RNG (greedy/seeded sampling makes the rerun
        deterministic).  ``ttft`` is NOT cleared: time-to-FIRST-token
        was already observed and must not be double-counted."""
        self.pos = -1
        self.next_token = None
        self.nprefilled = 0
        self.generated = []
        self.rng = self.req.sampling.make_rng()
        self.restarts += 1
        self.epoch += 1                # invalidate in-flight rows

    def note_dispatch(self, t: float):
        """The engine dispatched prompt tokens of this request at ``t``;
        the first such time stays (a restart does not reset it)."""
        if self.t_first_dispatch is None:
            self.t_first_dispatch = t

    def begin_decode(self):
        """Prefill done — rewind to the last prompt token and decode."""
        self.nprefilled = len(self.req.prompt)
        self.pos = len(self.req.prompt) - 1
        self.next_token = int(self.req.prompt[-1])

    def finished(self) -> bool:
        if len(self.generated) >= self.req.max_new_tokens:
            return True
        eos = self.req.eos_id
        return eos is not None and self.generated and self.generated[-1] == eos


#: ring-buffer cap on the per-step/per-request sample lists — a
#: long-running engine must not grow host memory without bound;
#: percentiles over the most recent window are what an operator wants
#: anyway
STATS_WINDOW = 65536


@dataclass
class EngineStats:
    """Throughput/latency counters the engine accumulates as it runs.
    Sample lists are bounded deques (see ``STATS_WINDOW``)."""
    n_slots: int = 0
    ttft: deque = field(                               # arrival -> 1st token
        default_factory=lambda: deque(maxlen=STATS_WINDOW))
    step_latency: deque = field(                       # per decode step (s)
        default_factory=lambda: deque(maxlen=STATS_WINDOW))
    occupancy: deque = field(                          # active/slots per step
        default_factory=lambda: deque(maxlen=STATS_WINDOW))
    itl: deque = field(                                # streamed token gaps
        default_factory=lambda: deque(maxlen=STATS_WINDOW))
    prefills: int = 0                  # prefill program calls (flush/chunk)
    prefill_chunks: int = 0            # chunked-mode calls among them
    prefill_tokens: int = 0            # REAL prompt tokens laid down
    chunk_tokens_real: int = 0         # real rows×tokens in chunk calls
    chunk_tokens_padded: int = 0       # padded waste in chunk calls
    decode_steps: int = 0
    packed_ticks: int = 0              # packed-program calls
    packed_decode_tokens: int = 0      # real decode tokens packed
    packed_prefill_tokens: int = 0     # real prompt tokens packed
    completed: int = 0
    generated_tokens: int = 0
    out_of_pages: int = 0              # admissions blocked on the free list
    prefix_hits: int = 0               # admissions that mapped a prefix
    prefix_tokens_saved: int = 0       # prompt tokens never prefilled
    preemptions: int = 0               # spills to the host KV store
    spilled_pages: int = 0             # pages gathered device -> host
    restore_hits: int = 0              # resumes injected from the store
    restore_misses: int = 0            # resumes re-prefilled (entry lost)
    restarts: int = 0                  # reset_for_refill invocations
    deadline_miss: int = 0             # requests cancelled past deadline
    deadline_miss_by_class: dict = field(default_factory=dict)
    quarantined: int = 0               # non-finite decode rows caught
    failed_requests: int = 0           # max_restarts / unrecoverable
    faults_injected: int = 0           # chaos faults actually fired
    faults_by_kind: dict = field(default_factory=dict)  # kind -> fired
    store_get_retries: int = 0         # KVStore reads re-tried (restore)
    shard_lost: int = 0                # shard_loss faults entered degraded
    degraded_ticks: int = 0            # ticks served in degraded mode
    cancelled: int = 0                 # requests cancelled by the caller
    ticks_idle: int = 0                # step() calls that found no work
    tokens_streamed: int = 0           # tokens delivered to TokenStreams
    host_busy_s: float = 0.0           # host-side bookkeeping (streaming)
    loop_wall_s: float = 0.0           # total non-idle streaming wall time
    phase_s: dict = field(default_factory=dict)  # serving.spans name -> s
    t_start: float | None = None
    t_end: float | None = None

    def summary(self) -> dict:
        span = ((self.t_end - self.t_start)
                if self.t_start is not None and self.t_end is not None
                else 0.0)
        pct = (lambda xs, q: float(np.percentile(list(xs), q))
               if xs else 0.0)
        return {
            "requests": self.completed,
            "elapsed_s": span,
            "requests_per_s": self.completed / span if span else 0.0,
            "decode_tokens_per_s": (self.generated_tokens / span
                                    if span else 0.0),
            "ttft_p50_s": pct(self.ttft, 50),
            "ttft_p90_s": pct(self.ttft, 90),
            "ttft_p99_s": pct(self.ttft, 99),
            "ttft_max_s": max(self.ttft) if self.ttft else 0.0,
            "step_ms_p50": 1e3 * pct(self.step_latency, 50),
            "occupancy": (float(np.mean(self.occupancy))
                          if self.occupancy else 0.0),
            "prefills": self.prefills,
            "prefill_chunks": self.prefill_chunks,
            "prefill_tokens": self.prefill_tokens,
            "chunk_tokens_real": self.chunk_tokens_real,
            "chunk_tokens_padded": self.chunk_tokens_padded,
            "decode_steps": self.decode_steps,
            "packed_ticks": self.packed_ticks,
            "packed_decode_tokens": self.packed_decode_tokens,
            "packed_prefill_tokens": self.packed_prefill_tokens,
            "out_of_pages": self.out_of_pages,
            "prefix_hits": self.prefix_hits,
            "prefix_tokens_saved": self.prefix_tokens_saved,
            "preemptions": self.preemptions,
            "spilled_pages": self.spilled_pages,
            "restore_hits": self.restore_hits,
            "restore_misses": self.restore_misses,
            "restarts": self.restarts,
            "deadline_miss": self.deadline_miss,
            "deadline_miss_by_class": {
                str(k): v for k, v
                in sorted(self.deadline_miss_by_class.items())},
            "quarantined": self.quarantined,
            "failed_requests": self.failed_requests,
            "faults_injected": self.faults_injected,
            "faults_by_kind": {k: v for k, v
                               in sorted(self.faults_by_kind.items())},
            "store_get_retries": self.store_get_retries,
            "shard_lost": self.shard_lost,
            "degraded_ticks": self.degraded_ticks,
            "cancelled": self.cancelled,
            "ticks_idle": self.ticks_idle,
            "tokens_streamed": self.tokens_streamed,
            # host-side bookkeeping share of the streaming loop's wall
            # time (0 when the engine never ran the streaming loop) —
            # the overlap-efficiency number docs/streaming.md defines
            "host_overhead_fraction": (self.host_busy_s / self.loop_wall_s
                                       if self.loop_wall_s > 0 else 0.0),
            "phase_s": dict(sorted(self.phase_s.items())),
        }


class FifoScheduler:
    """FIFO-within-priority queue + slot pool + interleave policy.

    With every request at the default priority 0 and no preemption this
    is exactly the original FIFO scheduler (same admission order, same
    interleave bounds).  Priorities add per-class queues; preemption
    adds per-class *resume* queues of parked RequestStates."""

    def __init__(self, n_slots: int, *, decode_per_prefill: int = 4,
                 gang: bool = False):
        assert n_slots >= 1
        self.n_slots = n_slots
        self.decode_per_prefill = max(1, decode_per_prefill)
        self.gang = gang
        self.queues: dict = {}         # priority -> deque[Request], FIFO
        self.resume: dict = {}         # priority -> [RequestState] by arrival
        self.free_slots: list = list(range(n_slots))   # ascending order
        self.active: dict = {}                         # slot -> RequestState
        self.drain = False     # no more arrivals expected (gang flushes)
        self._decodes_since_prefill = self.decode_per_prefill

    # -- queue -------------------------------------------------------------
    def submit(self, req: Request):
        self.queues.setdefault(req.priority, deque()).append(req)

    @property
    def queued(self) -> int:
        """Pending admissions: fresh requests + parked preemptees."""
        return (sum(len(q) for q in self.queues.values())
                + sum(len(q) for q in self.resume.values()))

    @property
    def has_work(self) -> bool:
        return bool(self.queued or self.active)

    # -- views -------------------------------------------------------------
    def prefilling(self) -> list:
        """Mid-prefill RequestStates, ascending slot order."""
        return [st for _, st in sorted(self.active.items())
                if st.prefilling]

    def decoding(self) -> list:
        """Decode-phase RequestStates, ascending slot order."""
        return [st for _, st in sorted(self.active.items())
                if not st.prefilling]

    # -- policy ------------------------------------------------------------
    def _gang_ready(self) -> bool:
        """Static batching admits only a full gang into an EMPTY pool
        (or the drain-time remainder once no more arrivals come)."""
        return not self.active and (self.queued >= self.n_slots
                                    or self.drain)

    def want_admit(self) -> bool:
        """Chunked mode: admission is host-side bookkeeping (assign a
        slot, start chunking under the interleave policy), so it is
        never rate-limited — except in gang mode."""
        if not self.queued or not self.free_slots:
            return False
        return self._gang_ready() if self.gang else True

    def plan_tick(self, token_budget: int) -> tuple:
        """Sarathi-style token-budget plan for one packed tick:
        every decoding slot contributes its one decode token first
        (structural fairness — decodes are never starved), then the
        remaining budget fills with prompt-chunk tokens across ALL
        mid-prefill requests in ascending slot order, each request
        taking ``min(remaining prompt, remaining budget)``.  Returns
        ``(decode_states, [(prefill_state, n_tokens), ...])``; the
        total never exceeds ``token_budget``."""
        decode = self.decoding()
        budget = token_budget - len(decode)
        assert budget >= 0, (
            f"token_budget {token_budget} < {len(decode)} decoding "
            "slots — the engine must keep token_budget >= n_slots")
        prefill = []
        for st in self.prefilling():
            if budget <= 0:
                break
            take = min(budget, len(st.req.prompt) - st.nprefilled)
            if take > 0:
                prefill.append((st, take))
                budget -= take
        return decode, prefill

    def want_chunk(self) -> bool:
        """Run a prefill chunk now?  Always when nothing is decoding;
        otherwise at most one chunk per ``decode_per_prefill`` decode
        steps — the bound on how long a long prompt can hold decode
        bandwidth away from running streams."""
        if not any(st.prefilling for st in self.active.values()):
            return False
        if not any(not st.prefilling for st in self.active.values()):
            return True
        return self._decodes_since_prefill >= self.decode_per_prefill

    def want_prefill(self) -> bool:
        """Legacy padded mode: admit + full pad-to-length flush as one
        all-or-nothing step, same interleave bound."""
        if not self.queued or not self.free_slots:
            return False
        if self.gang:
            return self._gang_ready()
        if not self.active:
            return True
        return self._decodes_since_prefill >= self.decode_per_prefill

    def note_decode(self):
        self._decodes_since_prefill += 1

    def note_chunk(self):
        self._decodes_since_prefill = 0

    # -- admission order ---------------------------------------------------
    def peek_admit(self):
        """Next admission candidate without popping it: highest
        non-empty priority class first; within a class, resume
        candidates (parked RequestStates, ordered by original arrival —
        they already hold progress, evicting them forever would starve
        them) before fresh Requests, each FIFO.  Returns a Request, a
        RequestState, or None."""
        for prio in sorted(set(self.resume) | set(self.queues),
                           reverse=True):
            if self.resume.get(prio):
                return self.resume[prio][0]
            if self.queues.get(prio):
                return self.queues[prio][0]
        return None

    def _pop_head(self, cand) -> None:
        """Remove the candidate ``peek_admit`` just returned."""
        if isinstance(cand, RequestState):
            q = self.resume[cand.req.priority]
            assert q[0] is cand
            q.pop(0)
        else:
            q = self.queues[cand.priority]
            assert q[0] is cand
            q.popleft()

    # -- transitions -------------------------------------------------------
    def admit(self, now: float, gate=None) -> list:
        """Pop admission candidates into free slots (lowest slot first)
        and return the admitted RequestStates, in admission order.
        Candidate order is ``peek_admit``'s: priority classes high to
        low, resumes before fresh, FIFO within each.

        ``gate(candidate) -> bool`` is the page-aware admission check:
        it is consulted on the head candidate before the pop, and a
        False stops admission for this call (strict order — later,
        smaller requests never jump an out-of-pages head; the engine
        retries next tick once eviction, prefix reclaim, or preemption
        refills the free list).  A True gate may reserve resources, so
        the pop must follow it.  The candidate is a Request (fresh) or
        a RequestState (resume from the offload store); a resumed state
        keeps its progress and is re-bound to the new slot."""
        states = []
        while self.free_slots:
            cand = self.peek_admit()
            if cand is None:
                break
            if gate is not None and not gate(cand):
                break
            self._pop_head(cand)
            slot = self.free_slots.pop(0)
            if isinstance(cand, RequestState):
                st = cand
                st.slot = slot
            else:
                st = RequestState(cand, slot, now)
            self.active[slot] = st
            states.append(st)
        if states:
            self._decodes_since_prefill = 0
        return states

    def evict(self, st: RequestState, now: float):
        """Release a finished request's slot back to the pool."""
        assert self.active.get(st.slot) is st
        del self.active[st.slot]
        st.t_finish = now
        self.free_slots.append(st.slot)
        self.free_slots.sort()

    # -- preemption --------------------------------------------------------
    def pick_victim(self, below_priority: int, now: float = 0.0):
        """Preempt policy: among active requests with priority strictly
        below ``below_priority``, pick the lowest-priority one with the
        most deadline slack (``deadline - now - remaining``; no deadline
        counts as infinite slack — SLO-less work is always preempted
        before anything racing a deadline), then the most work
        remaining (ties: highest rid, i.e. latest arrival).
        Decode-phase requests are preferred victims — spilling one
        frees a full row at zero recompute; a mid-prefill victim is
        chosen only when nothing is decoding.  Returns None when no
        strictly-lower-priority victim exists (equal-priority
        preemption would thrash: the pool drains by itself)."""
        cands = [st for st in self.active.values()
                 if st.req.priority < below_priority]
        if not cands:
            return None
        decode = [st for st in cands if not st.prefilling]
        pool = decode or cands

        def slack(st):
            if st.req.deadline is None:
                return float("inf")
            return st.req.deadline - now - st.remaining

        return max(pool, key=lambda st: (-st.req.priority, slack(st),
                                         st.remaining, st.req.rid))

    def remove(self, st: RequestState) -> None:
        """Detach an active request from its slot WITHOUT finishing it
        (the spill half of preempt/suspend — the caller owns where the
        RequestState goes next)."""
        assert self.active.get(st.slot) is st
        del self.active[st.slot]
        self.free_slots.append(st.slot)
        self.free_slots.sort()
        st.slot = -1

    def push_resume(self, st: RequestState) -> None:
        """Park a spilled RequestState for re-admission, keeping the
        class's resume queue ordered by original arrival (fair resume
        ordering: earliest-arrived preemptee restores first no matter
        how many times it was bounced)."""
        q = self.resume.setdefault(st.req.priority, [])
        q.append(st)
        q.sort(key=lambda s: (s.req.arrival, s.req.rid))

    def preempt(self, st: RequestState) -> None:
        """Spill-side bookkeeping: free the slot and queue the state
        for automatic resume (the engine spills the KV footprint to the
        store before calling this)."""
        self.remove(st)
        self.push_resume(st)

    def cancel(self, rid: int):
        """Remove a not-yet-active request (queued fresh or parked for
        resume) by rid.  Returns the removed Request/RequestState, or
        None if the rid is not waiting here.  Active requests are
        cancelled by the engine (``ServingEngine.cancel`` frees their
        pages/slot); this method only covers the queued states."""
        for q in self.queues.values():
            for req in q:
                if req.rid == rid:
                    q.remove(req)
                    return req
        for q in self.resume.values():
            for st in q:
                if st.req.rid == rid:
                    q.remove(st)
                    return st
        return None
