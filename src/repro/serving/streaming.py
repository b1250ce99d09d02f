"""Asynchronous streaming front-end: overlapped host/device serving.

The synchronous engine loop (``ServingEngine.step``) serializes every
tick: plan on host -> run the program -> copy the full ``(rows, V)``
logits to host -> sample -> repeat.  The host is idle while the device
computes and the device is idle while the host copies and samples —
on an edge deployment that dead time, not FLOPs, bounds ITL.

This module is the real serving loop (ROADMAP item 3; JetStream's
``ResultTokens`` idiom — docs/streaming.md has the lifecycle diagram):

* **One small device array per tick.**  The tick's program output is
  reduced ON DEVICE to a ``(n_slots, 4)`` int32 ``ResultTokens`` array
  — ``[token, valid, length, finite]`` per slot (greedy argmax,
  did-this-slot-decode, cache length, NaN-guard verdict) — so the host
  copies ``4 * n_slots`` ints instead of ``rows x vocab`` floats.
* **Double-buffered dispatch.**  Tick N+1 is planned from host state
  and dispatched BEFORE tick N's results arrive; decode tokens that
  are still in flight are spliced in on device from tick N's
  ``ResultTokens`` (``make_result_pack``'s ``merge``), so the device
  never waits for the host round trip.  JAX's async dispatch plus the
  donated-storage chain serializes the ticks on device; the host
  reconciles tick N (one ``jax.device_get`` of the small array) while
  tick N+1 computes.
* **Per-request streams.**  ``submit_stream`` returns an
  ``AsyncIterator[int]`` (``TokenStream``) delivering tokens as their
  tick reconciles; ``cancel`` works mid-flight through the engine's
  zero-leak release path.

Speculative dispatch never changes a token: positions and prompt
prefill advance deterministically on the host, the device argmax is
bit-identical to the host ``np.argmax`` the sync engine samples with
(both take the first maximum), and every speculative K/V write lands
at a position strictly beyond the owner's reconciled frontier — masked
until (idempotently) rewritten, even across page free/rebind, because
a later owner's prefill rewrites every readable position after the
stale write in device order.  State that rewinds (quarantine, restart)
bumps ``RequestState.epoch`` so in-flight rows reconcile as stale and
are discarded.

Overlap requires greedy sampling (the splice re-feeds the device
argmax).  Ticks whose decode set contains a ``temperature > 0``
request fall back to the synchronous path for that tick — tokens still
stream, the pipeline just drains first (depth 1, full logits copy,
host RNG sampling).  Fault injection (``EngineConfig.faults``) also
forces the synchronous path: the chaos blast-radius contracts are
defined per synchronous tick — which is also what makes degraded-mesh
serving (``shard_loss``) safe to stream: the 'degraded' / 'recovered'
tick kinds only ever occur on the synchronous path, so no speculative
row is in flight when a shard dies or when recovery rewinds every
active slot.  A stream crossing a degraded window delivers its first
k tokens from the Segment-Means substitute path and the remainder
exact: the recovery ``reset_for_refill`` rewinds ``generated`` below
the delivered watermark, so re-decoded tokens only reach the stream
past what was already sent (total per stream = ``max_new_tokens``,
all finite).  Control-plane operations that move or free cache state
out of band — preemption, suspend, deadline expiry, snapshot, cancel
— drain the in-flight pipeline first.
"""
from __future__ import annotations

import asyncio
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime.serve import make_result_pack
from .engine import ServingEngine
from .spans import span, step_span


@dataclass
class ResultTokens:
    """One in-flight tick: the packed device array plus the host-side
    records needed to reconcile it.

    ``data`` is the ONE device-resident array the tick sends home —
    ``(n_slots, 4)`` int32, per-slot ``[token, valid, length,
    finite]`` (see ``runtime.serve.make_result_pack``).  ``records``
    holds ``(slot, state, epoch)`` for every decode row dispatched in
    the tick: reconciliation walks them, drops rows whose state
    rewound (epoch mismatch) or left the slot (evicted / preempted),
    and advances the rest with the device-sampled token."""
    data: object                       # device (n_slots, 4) int32
    records: list                      # [(slot, RequestState, epoch)]
    kind: str                          # 'packed' | 'decode'
    decode_slots: frozenset            # slots with a decode row this tick
    t_dispatch: float                  # engine-clock dispatch time

    def get(self) -> np.ndarray:
        """The single host copy (blocks until the tick's compute and
        transfer finish)."""
        return np.asarray(jax.device_get(self.data))


class TokenStream:
    """Per-request ``AsyncIterator[int]``: tokens arrive as their tick
    reconciles; iteration ends when the request finishes (``finished``
    holds the reason: ``'length'``, ``'eos'``, ``'cancelled'``,
    ``'deadline'``, ``'max_restarts'``).

    The producer (the engine loop, possibly running in an executor
    thread) calls ``put``/``finish``; consumers either ``async for``
    over the stream or poll ``drain()`` synchronously.  Cross-thread
    wakeups go through ``call_soon_threadsafe``, so the asyncio
    front-end can keep the blocking tick loop off the event loop."""

    def __init__(self, rid: int):
        self.rid = rid
        # the engine's RequestState from the first flush after
        # admission on, for its times (read only: req.arrival,
        # t_admit, t_first_dispatch, ttft); None before
        self.state = None
        self._q: deque = deque()
        self._fin: str | None = None
        self._loop = None
        self._event: asyncio.Event | None = None

    # -- producer side (engine loop) -----------------------------------
    def put(self, token: int) -> None:
        self._q.append(token)
        self._wake()

    def finish(self, reason: str) -> None:
        self._fin = reason
        self._wake()

    def _wake(self) -> None:
        if self._event is not None:
            self._loop.call_soon_threadsafe(self._event.set)

    # -- consumer side -------------------------------------------------
    @property
    def finished(self) -> str | None:
        """Finish reason once the request is done, else None."""
        return self._fin

    def drain(self) -> list:
        """Synchronously pop every token delivered so far."""
        out = []
        while self._q:
            out.append(self._q.popleft())
        return out

    def __aiter__(self):
        return self

    async def __anext__(self) -> int:
        while True:
            if self._q:
                return self._q.popleft()
            if self._fin is not None:
                raise StopAsyncIteration
            if self._event is None:
                self._loop = asyncio.get_running_loop()
                self._event = asyncio.Event()
            self._event.clear()
            await self._event.wait()


class StreamingEngine:
    """Overlapped streaming loop over a ``ServingEngine``.

    Owns the tick pipeline (a deque of in-flight ``ResultTokens``, at
    most ``depth`` deep; depth 2 = classic double buffering) and the
    per-request ``TokenStream`` registry.  The wrapped engine keeps all
    admission / paging / preemption / fault machinery; this class only
    changes WHEN programs run and HOW results come home.

    ``step()`` is one loop iteration: release arrivals, run any
    control-plane work that needs a drained pipeline, admit, dispatch
    one tick if the pipeline has room, reconcile the oldest tick if it
    is full (or nothing could be dispatched), and flush reconciled
    tokens to their streams.  ``run_sync()`` drives to completion;
    ``serve_stream`` is the asyncio front-end."""

    def __init__(self, engine: ServingEngine, *, overlap: bool = True,
                 depth: int = 2):
        self._eng = engine
        self.depth = max(1, int(depth))
        # overlap needs the packed/decode program pair and per-tick
        # chaos semantics off (fault blast radii are defined per
        # synchronous tick)
        self.overlap = bool(overlap and engine.prefill_mode == "packed"
                            and engine._injector is None)
        self._pack, self._merge = make_result_pack(engine.n_slots)
        self._pipe: deque = deque()    # in-flight ResultTokens, FIFO
        self._streams: dict = {}       # rid -> TokenStream
        self._delivered: dict = {}     # rid -> tokens already pushed
        self._last_token: dict = {}    # rid -> engine time of its last
        self._steps = 0                # loop iterations (profiler steps)
        self._zero = jnp.zeros((engine.n_slots, 4), jnp.int32)

    # ------------------------------------------------------------------
    # submission / streams
    # ------------------------------------------------------------------
    @property
    def engine(self) -> ServingEngine:
        return self._eng

    @property
    def has_work(self) -> bool:
        return (bool(self._pipe) or self._eng._sched.has_work
                or bool(self._eng._pending))

    def submit_stream(self, prompt, **kwargs) -> tuple:
        """``ServingEngine.submit`` plus a registered ``TokenStream``;
        returns ``(rid, stream)``."""
        rid = self._eng.submit(prompt, **kwargs)
        stream = TokenStream(rid)
        self._streams[rid] = stream
        self._delivered[rid] = 0
        return rid, stream

    def cancel(self, rid: int) -> bool:
        """Cancel anywhere in the lifecycle — including mid-decode.
        Drains the pipeline first so no in-flight row targets the
        freed slot, then releases through the engine's zero-leak
        path."""
        self.drain()
        ok = self._eng.cancel(rid)
        self._flush_streams()
        return ok

    def preempt(self, rid: int) -> bool:
        self.drain()
        return self._eng.preempt(rid)

    def suspend(self, rid: int) -> bool:
        self.drain()
        return self._eng.suspend(rid)

    def resume(self, rid: int) -> bool:
        return self._eng.resume(rid)

    def snapshot(self):
        self.drain()
        return self._eng.snapshot()

    # ------------------------------------------------------------------
    # the loop
    # ------------------------------------------------------------------
    def step(self) -> str:
        """One streaming-loop iteration.  Returns the dispatched tick
        kind ('packed' / 'decode'), 'reconcile' when the iteration only
        retired an in-flight tick, a synchronous-fallback kind, or
        'idle'."""
        t0 = time.perf_counter()
        with step_span(self._steps):
            kind = self._step_inner()
        self._steps += 1
        if kind != "idle":
            self._eng.stats.loop_wall_s += time.perf_counter() - t0
        return kind

    def _step_inner(self) -> str:
        eng = self._eng
        with span(eng.stats, "serve.admit"):
            eng._release_arrivals()
        if eng.stats.t_start is None:
            eng.stats.t_start = eng.now()
        if not self.overlap:
            kind = eng.step()          # sync semantics, still streaming
            self._flush_streams()
            return kind
        # control plane that frees/moves slots out of reconcile order
        # sees a drained pipeline
        if eng._has_deadlines and self._deadline_due():
            self.drain()
            eng._expire()
        if any(st.req.sampling.temperature > 0.0
               for st in eng._sched.active.values()
               if not st.prefilling):
            # host RNG sampling needs the full logits row: fall back to
            # one synchronous tick (depth-1; tokens still stream)
            self.drain()
            kind = eng.step()
            self._flush_streams()
            return kind
        if eng._store is not None and eng._sched.queued and self._pipe:
            # a blocked admission may preempt (device->host page
            # gather): conservative drain keeps spill/rebind races
            # impossible
            self.drain()
        with span(eng.stats, "serve.admit"):
            eng._admit_or_preempt()
        dispatched = None
        if len(self._pipe) < self.depth:
            dispatched = self._dispatch()
        if self._pipe and (dispatched is None
                           or len(self._pipe) >= self.depth):
            self._reconcile_one()
            self._flush_streams()
            return dispatched or "reconcile"
        if dispatched is None:
            eng.stats.ticks_idle += 1    # sync paths count their own
            return "idle"
        return dispatched

    def drain(self) -> None:
        """Reconcile every in-flight tick (blocks on the device)."""
        while self._pipe:
            self._reconcile_one()
            self._flush_streams()

    def run_sync(self) -> dict:
        """Drive to completion (the synchronous harness the equivalence
        tests and benches use).  Returns ``ServingEngine.results()``."""
        eng = self._eng
        while True:
            kind = self.step()
            if kind != "idle":
                continue
            if eng._pending:
                before = eng.now()
                dt = eng.next_arrival() - before
                if dt > 0:
                    time.sleep(min(dt, 0.05))
                    if eng.now() <= before:  # injected logical clock
                        eng._t0 -= dt
                continue
            if not self.has_work:
                break
        self.drain()
        self._flush_streams()
        return eng.results()

    # ------------------------------------------------------------------
    # dispatch side
    # ------------------------------------------------------------------
    def _dispatch(self):
        eng = self._eng
        sch = eng._sched
        if any(st.prefilling for st in sch.active.values()):
            return self._dispatch_packed()
        if sch.decoding():
            return self._dispatch_decode()
        return None

    def _can_decode(self, st) -> bool:
        """A slot may not overrun its generation budget with in-flight
        rows; EOS overruns (at most one row, unpredictable by design)
        reconcile as stale instead."""
        return len(st.generated) + st.inflight < st.req.max_new_tokens

    def _spec_token(self, st, tok, src, i) -> None:
        """Pick row i's token source: the previous in-flight tick's
        on-device sample when one exists, else the host-known value
        (first decode after a reconcile, or the rewind re-feed)."""
        if st.inflight > 0:
            prev = self._pipe[-1]
            assert st.slot in prev.decode_slots, (
                "double-buffer gap: in-flight decode row without a "
                "previous-tick sample")
            src[i] = st.slot
        else:
            tok[i] = st.next_token

    def _dispatch_packed(self):
        eng = self._eng
        sch = eng._sched
        with span(eng.stats, "serve.plan"):
            t0 = time.perf_counter()
            decode, prefill = sch.plan_tick(eng.token_budget)
            decode = [st for st in decode if self._can_decode(st)]
            if not decode and not prefill:
                return None
            tb = eng.token_budget
            tok = np.zeros(tb, np.int32)
            slot = np.full(tb, -1, np.int32)
            pos = np.full(tb, -1, np.int32)
            off = np.full(tb, -1, np.int32)
            pre = np.zeros(tb, np.int32)
            src = np.full(tb, -1, np.int32)
            lengths = np.zeros(eng.n_slots, np.int32)
            records = []
            i = 0
            for st in decode:
                p = st.pos + st.inflight
                self._spec_token(st, tok, src, i)
                slot[i] = st.slot
                pos[i] = off[i] = p
                lengths[st.slot] = p + 1
                records.append((st.slot, st, st.epoch))
                st.inflight += 1
                i += 1
            n_dec = i
            n_prefill = 0
            now = eng.now()
            for st, take in prefill:
                o = st.nprefilled
                tok[i:i + take] = st.req.prompt[o:o + take]
                slot[i:i + take] = st.slot
                pos[i:i + take] = np.arange(o, o + take)
                off[i:i + take] = o
                pre[i:i + take] = 1
                i += take
                n_prefill += take
                st.note_dispatch(now)
                # prefill progress is host-deterministic: advance at
                # dispatch so the NEXT tick plans past it (the rewind
                # re-feed token is host-known, so a request can finish
                # prefill and start decoding with zero pipeline stalls)
                st.nprefilled += take
                if not st.prefilling:
                    st.begin_decode()
            # the packed program's LM head covers the static decode
            # prefix (min(n_slots, token_budget) rows; decode rows pack
            # first)
            n_rows = min(eng.n_slots, tb)
            is_dec = np.zeros(n_rows, np.int32)
            is_dec[:n_dec] = 1
            prev_data = self._pipe[-1].data if self._pipe else self._zero
            eng.stats.host_busy_s += time.perf_counter() - t0
        with span(eng.stats, "serve.launch"):
            tok_dev = self._merge(jnp.asarray(tok), jnp.asarray(src),
                                  prev_data)
            logits, eng._kv.storage = eng._packed(
                eng.params, eng._kv.storage, tok_dev, jnp.asarray(slot),
                jnp.asarray(pos), jnp.asarray(off), jnp.asarray(pre),
                *eng._maps())
            data = self._pack(logits, jnp.asarray(slot[:n_rows].copy()),
                              jnp.asarray(is_dec), jnp.asarray(lengths))
        t1 = time.perf_counter()
        self._push(data, records, "packed")
        eng.stats.packed_ticks += 1
        eng.stats.packed_decode_tokens += n_dec
        eng.stats.packed_prefill_tokens += n_prefill
        eng.stats.prefill_tokens += n_prefill
        eng.stats.host_busy_s += time.perf_counter() - t1
        return "packed"

    def _dispatch_decode(self):
        eng = self._eng
        sch = eng._sched
        with span(eng.stats, "serve.plan"):
            t0 = time.perf_counter()
            decode = [st for st in sch.decoding() if self._can_decode(st)]
            if not decode:
                return None
            B = eng.n_slots
            tok = np.zeros(B, np.int32)
            pos = np.full(B, -1, np.int32)
            src = np.full(B, -1, np.int32)
            is_dec = np.zeros(B, np.int32)
            lengths = np.zeros(B, np.int32)
            records = []
            for st in decode:
                p = st.pos + st.inflight
                self._spec_token(st, tok, src, st.slot)
                pos[st.slot] = p
                is_dec[st.slot] = 1
                lengths[st.slot] = p + 1
                records.append((st.slot, st, st.epoch))
                st.inflight += 1
            prev_data = self._pipe[-1].data if self._pipe else self._zero
            eng.stats.host_busy_s += time.perf_counter() - t0
        with span(eng.stats, "serve.launch"):
            tok_dev = self._merge(jnp.asarray(tok), jnp.asarray(src),
                                  prev_data)
            logits, eng._kv.storage = eng._step(
                eng.params, eng._kv.storage, tok_dev, jnp.asarray(pos),
                *eng._maps())
            data = self._pack(logits, jnp.arange(B, dtype=jnp.int32),
                              jnp.asarray(is_dec), jnp.asarray(lengths))
        t1 = time.perf_counter()
        self._push(data, records, "decode")
        eng.stats.decode_steps += 1
        sch.note_decode()
        eng.stats.host_busy_s += time.perf_counter() - t1
        return "decode"

    def _push(self, data, records, kind: str) -> None:
        eng = self._eng
        eng.stats.occupancy.append(
            len(eng._sched.active) / eng.n_slots)
        self._pipe.append(ResultTokens(
            data=data, records=records, kind=kind,
            decode_slots=frozenset(s for s, _, _ in records),
            t_dispatch=eng.now()))

    # ------------------------------------------------------------------
    # reconcile side
    # ------------------------------------------------------------------
    def _reconcile_one(self) -> None:
        eng = self._eng
        tick = self._pipe.popleft()
        with span(eng.stats, "serve.wait"):
            data = tick.get()          # THE host copy; blocks on device
        with span(eng.stats, "serve.reconcile"):
            t0 = time.perf_counter()
            now = eng.now()
            eng.stats.step_latency.append(now - tick.t_dispatch)
            for slot, st, epoch in tick.records:
                st.inflight -= 1
                if st.epoch != epoch:
                    continue           # rewound (quarantine/restart)
                if eng._sched.active.get(slot) is not st:
                    continue           # evicted / preempted / cancelled
                token, valid, _, finite = data[slot]
                if not valid:
                    continue
                if eng._nan_guard and not finite:
                    eng._quarantine(st)  # bumps epoch: later rows stale
                    continue
                eng._advance_token(st, int(token), now)
            eng.stats.t_end = eng.now()
            eng.stats.host_busy_s += time.perf_counter() - t0

    def _deadline_due(self) -> bool:
        now = self._eng.now()
        return any(r.deadline is not None and now >= r.deadline
                   for r in self._eng._live_requests())

    # ------------------------------------------------------------------
    # stream delivery
    # ------------------------------------------------------------------
    def _flush_streams(self) -> None:
        eng = self._eng
        if not self._streams:
            return
        with span(eng.stats, "serve.flush"):
            done = []
            now = eng.now()
            for rid, stream in self._streams.items():
                fin = None
                st = eng._results.get(rid)
                if st is not None:
                    fin = ("eos" if (st.req.eos_id is not None
                                     and st.generated
                                     and st.generated[-1] == st.req.eos_id)
                           else "length")
                elif rid in eng._failed:
                    fin = eng._failed[rid]
                    st = None
                else:
                    st = next((s for s in eng._sched.active.values()
                               if s.req.rid == rid), None)
                if st is not None:
                    stream.state = st
                    sent = self._delivered.get(rid, 0)
                    fresh = st.generated[sent:]
                    if fresh:
                        for t in fresh:
                            stream.put(int(t))
                        self._delivered[rid] = sent + len(fresh)
                        eng.stats.tokens_streamed += len(fresh)
                        self._note_itl(rid, now, len(fresh))
                if fin is not None:
                    stream.finish(fin)
                    done.append(rid)
            for rid in done:
                del self._streams[rid]
                self._delivered.pop(rid, None)
                self._last_token.pop(rid, None)

    def _note_itl(self, rid: int, now: float, n: int) -> None:
        """Record the gaps before ``n`` tokens of ``rid`` delivered at
        ``now``: the gap since its previous delivery, then zeros (they
        arrived in one reconcile)."""
        last = self._last_token.get(rid)
        gaps = ([now - last] if last is not None else []) + [0.0] * (n - 1)
        self._eng.stats.itl.extend(gaps)
        self._last_token[rid] = now

    def itl_samples(self) -> list:
        """Inter-token latencies of streamed requests in engine-clock
        units, from the stream delivery timestamps: the most recent
        ``STATS_WINDOW`` gaps (``EngineStats.itl``)."""
        return list(self._eng.stats.itl)


async def serve_stream(seng: StreamingEngine, requests: list,
                       *, idle_sleep: float = 0.002) -> dict:
    """Asyncio front-end: submit every request (dicts of
    ``submit_stream`` kwargs — typically with Poisson ``arrival``
    times), run the blocking tick loop in the default executor so
    consumer coroutines interleave with device work, and collect each
    stream.  Returns ``{rid: {"tokens": [...], "times": [...],
    "finished": reason}}`` with wall-clock delivery times."""
    loop = asyncio.get_running_loop()
    out: dict = {}

    async def consume(rid: int, stream: TokenStream) -> None:
        toks, times = [], []
        async for t in stream:
            toks.append(t)
            times.append(time.perf_counter())
        out[rid] = {"tokens": toks, "times": times,
                    "finished": stream.finished}

    tasks = []
    for kw in requests:
        rid, stream = seng.submit_stream(**kw)
        tasks.append(asyncio.ensure_future(consume(rid, stream)))
    while seng.has_work:
        kind = await loop.run_in_executor(None, seng.step)
        if kind == "idle":
            await asyncio.sleep(idle_sleep)
    seng.drain()
    seng._flush_streams()
    await asyncio.gather(*tasks)
    return out
