"""Drive the serving engine with generated traffic on one clock.

The measured entry is the main serving path: ``StreamingEngine``
(overlapped ticks) over ``ServingEngine`` (packed ticks, paged KV
cache, prefix cache where the decode mode allows it, Pallas kernels).
Requests go in through ``submit_stream`` when they are due, with
``arrival`` set to the due time on the engine's clock, so the time to
first token runs from when the request was due and counts any wait
the loop imposed.  Tokens are timed when they reach their streams, on
the same clock.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from .generator import Spec, Traffic

#: Counters of ``EngineStats`` snapshotted at the window's edges.
STAT_FIELDS = ("packed_ticks", "decode_steps", "packed_prefill_tokens",
               "packed_decode_tokens", "prefix_tokens_saved",
               "prefix_hits", "generated_tokens", "host_busy_s",
               "loop_wall_s", "ticks_idle", "out_of_pages", "preemptions")

#: Seconds profiled before the traced window and not counted in it
#: (``trace_reduce.reduce_trace``'s ``lead_s``): the profiler's start
#: can hold the device idle for over a second (TPU v5e).
TRACE_LEAD_S = 3.0

#: Seconds after the window within which every request due in it has
#: to get its first token.  No request is sent after the window, so a
#: request that waits behind others' prefill (a closed loop's
#: documents: up to 7 x 3584 tokens at about 500 a second) gets its
#: turn within this.
GRACE_S = 120.0


@dataclass
class Served:
    """One request as the benchmark saw it."""
    spec: Spec
    due: float                         # engine clock
    client: int = -1
    rid: int = -1
    stream: object = None
    tokens: list = field(default_factory=list)
    times: list = field(default_factory=list)
    finished: str | None = None
    t_done: float | None = None


@dataclass
class Window:
    """What one measured window produced."""
    t0: float                          # engine clock, window start
    t1: float                          # engine clock, window end
    served: list                       # every submitted request
    stats0: dict
    stats1: dict
    occupancy: list                    # per tick, ticks in the window
    steps: list                        # (t_start, t_end, kind) per step
    compiles: int                      # compiles + cache loads in window
    traces: int                        # program retraces in window
    late_s: float                      # worst lateness of a submission
    trace_span: tuple | None = None    # engine clock of the profile
    t0_mono: float = 0.0               # time.monotonic() at window start


def build_engine(cfg, mesh, weights, cell: dict):
    """``StreamingEngine(ServingEngine(...))`` as the cell states."""
    from repro.core.protocol import PrismConfig
    from repro.runtime.serve import ServeHParams
    from repro.serving import EngineConfig, ServingEngine, StreamingEngine

    mode = cell["decode_mode"]
    cr = float(cell.get("cr") or ServeHParams.means_cr)
    hp = ServeHParams(decode_mode=mode, means_cr=cr)
    prism = PrismConfig(P=mesh.shape["model"], cr=cr,
                        mode="prism" if mode == "prism" else "voltage")
    eng = ServingEngine(cfg, mesh, weights,
                        EngineConfig(**cell["engine"], hp=hp, prism=prism))
    return StreamingEngine(eng)


def warm_up(seng, vocab: int, seed: int) -> None:
    """Run the cell's programs once each: two short requests give
    packed ticks (prefill), then decode ticks, and the result-pack and
    merge programs with them.  The tokens share nothing with the
    traffic."""
    rng = np.random.default_rng(seed)
    for n in (24, 40):
        seng.submit_stream(rng.integers(1, vocab, size=n).astype(np.int32),
                           max_new_tokens=4)
    while seng.has_work:
        seng.step()


def snapshot(stats) -> dict:
    return {k: getattr(stats, k) for k in STAT_FIELDS}


class CompileWatch:
    """Counts compiles and compile-cache loads (JAX monitoring
    events) and program retraces (``runtime.serve.trace_counts``)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.n += 1

    @staticmethod
    def traces() -> int:
        from repro.runtime.serve import trace_counts
        return sum(trace_counts.values())


def run_window(seng, traffic: Traffic, *, preroll_s: float,
               seconds: float, grace_s: float = GRACE_S, watch=None,
               trace_dir: str | None = None, trace_s: float = 0.0,
               annotate: bool = False) -> Window:
    """Offer ``traffic`` from now on: ``preroll_s`` seconds to reach a
    steady state, then the measured window of ``seconds``.  After the
    window no request is sent, and the engine serves until every
    request due in the window has its first token, or ``grace_s`` has
    passed.  With
    ``trace_dir`` the profiler records the window's last ``trace_s``
    seconds, after a lead-in of ``TRACE_LEAD_S``."""
    import jax
    eng = seng.engine
    span = (jax.profiler.TraceAnnotation if annotate
            else (lambda name: contextlib.nullcontext()))
    start = eng.now()
    t0, t1 = start + preroll_s, start + preroll_s + seconds
    # the profile ends with the window: stopping the profiler stalls
    # the loop for seconds, which then falls after the measured window
    tr0, tr1 = t1 - trace_s - TRACE_LEAD_S, t1
    served: list = []
    live: dict = {}
    if traffic.kind == "open":
        pending = deque(Served(spec=s, due=start + s.due)
                        for s in traffic.open)
        clients = []
    else:
        pending = deque()
        clients = [deque(c) for c in traffic.clients]
        for c, q in enumerate(clients):
            if q:
                pending.append(Served(spec=q.popleft(), client=c,
                                      due=start + traffic.starts[c]))
        pending = deque(sorted(pending, key=lambda r: r.due))
    stats0 = stats1 = None
    ticks0 = ticks1 = 0
    occ = []
    steps = []
    c0 = c1 = tr_c0 = tr_c1 = 0
    late = 0.0
    tracing = traced = False
    trace_span = None
    t0_mono = 0.0

    def ticks():
        return eng.stats.packed_ticks + eng.stats.decode_steps

    while True:
        now = eng.now()
        with span("bench.submit"):
            while pending and pending[0].due <= now and pending[0].due < t1:
                r = pending.popleft()
                late = max(late, now - r.due)
                r.rid, r.stream = seng.submit_stream(
                    r.spec.prompt, max_new_tokens=r.spec.max_new,
                    arrival=r.due)
                served.append(r)
                live[r.rid] = r
        if stats0 is None and now >= t0:
            stats0, ticks0 = snapshot(eng.stats), ticks()
            t0_mono = time.monotonic()
            c0 = watch.n if watch else 0
            tr_c0 = CompileWatch.traces() if watch else 0
        if stats1 is None and now >= t1:
            stats1, ticks1 = snapshot(eng.stats), ticks()
            occ = list(eng.stats.occupancy)[-(ticks1 - ticks0):] \
                if ticks1 > ticks0 else []
            c1 = watch.n if watch else 0
            tr_c1 = CompileWatch.traces() if watch else 0
        if trace_dir and not tracing and not traced and now >= tr0:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing, trace_span = True, [eng.now(), None]
        if tracing and now >= tr1:
            jax.profiler.stop_trace()
            tracing, traced = False, True
            trace_span[1] = eng.now()
        if stats1 is not None:
            due_in = [r for r in served if t0 <= r.due < t1]
            if (all(r.times for r in due_in) and not pending_due(pending, t1)
                    or now >= t1 + grace_s):
                break
        a = eng.now()
        with span("engine.step"):
            kind = seng.step()
        b = eng.now()
        steps.append((a, b, kind))
        with span("bench.deliver"):
            done = []
            for rid, r in live.items():
                toks = r.stream.drain()
                if toks:
                    r.tokens.extend(toks)
                    r.times.extend([b] * len(toks))
                if r.stream.finished is not None:
                    r.finished, r.t_done = r.stream.finished, b
                    done.append(rid)
            for rid in done:
                r = live.pop(rid)
                if r.client >= 0 and clients[r.client]:
                    nxt = Served(spec=clients[r.client].popleft(),
                                 client=r.client, due=b)
                    _insert(pending, nxt)
        if kind == "idle":
            with span("bench.idle"):
                wait = (pending[0].due - eng.now()) if pending else 0.002
                time.sleep(min(max(wait, 0.0), 0.002))
    if tracing:
        jax.profiler.stop_trace()
        trace_span[1] = eng.now()
    seng.drain()
    return Window(t0=t0, t1=t1, served=served, stats0=stats0, stats1=stats1,
                  occupancy=occ, steps=steps, compiles=c1 - c0,
                  traces=tr_c1 - tr_c0, late_s=late,
                  trace_span=tuple(trace_span) if trace_span else None,
                  t0_mono=t0_mono)


def pending_due(pending, t1: float) -> bool:
    return bool(pending) and pending[0].due < t1


def _insert(pending: deque, r: Served) -> None:
    """Keep ``pending`` ordered by due time."""
    i = len(pending)
    while i > 0 and pending[i - 1].due > r.due:
        i -= 1
    pending.insert(i, r)
