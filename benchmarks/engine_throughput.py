"""Serving-engine benchmark: token-packed ticks vs chunked prefill vs
padded flushes vs static batching, on staggered-arrival traces.

Replays identical Poisson traces through ServingEngine instances that
differ only in admission policy:

  * packed  — the system: ONE compiled program per engine tick over a
              flat token batch of every live decode token + prompt
              tokens from every mid-prefill request (Sarathi-style
              token-budget planning); per-tick cost ∝ REAL tokens
  * chunked — the PR-4 path: prompts prefilled ``chunk_len`` tokens at
              a time in a full (n_slots, chunk_len) program, chunk
              steps interleaved with decodes
  * padded  — PR-2 continuous batching: one monolithic right-padded
              prefill flush per admission
  * gang    — classic static batching (admit into an empty pool only,
              drain completely): the head-of-line-blocking baseline

Five traces: the moderate-load ``main`` trace (chat regime), the
``short``-prompt trace (pad-to-length waste), the ``saturated`` trace
(arrivals far above the service rate — the regime where PR-4's FLOP
clock recorded gang flushes out-amortizing per-row chunk calls, and
where token packing closes that gap), the shared-``prefix`` trace
(every prompt opens with the same system prompt; the paged engine's
prefix cache maps the shared pages copy-on-write and must cut prefill
work without changing a token), and the ``overload`` trace (arrivals
demand more KV pages than the pool holds; the host offload tier must
cut the interactive class's TTFT by preempting background decodes —
spill to host, restore later — without changing a token).

To keep the comparison deterministic on noisy shared CPUs — and
gateable in CI (``benchmarks/compare.py``) — the engines run on a
*logical* clock whose step costs come from the ANALYTIC FLOP model in
``benchmarks/common.py``: one decode step costs 1 unit; a chunk step
and a padded flush cost their FLOP multiple of a decode step; a packed
tick costs its real-token FLOPs (``packed_step_flops``), read from the
engine's per-tick token counters.  Every logical metric (requests per
kstep, TTFT in steps, prefill FLOPs per request) is a pure function of
the code + trace seed.  Measured wall-clock per step kind is reported
alongside for the wall-time conversions, but nothing gated depends on
it.

The ``stream`` section (PR 9) measures the async streaming loop
(``serving/streaming.py``) both ways: ``stream_token_match`` drives the
double-buffered engine on the logical clock over the identical main
trace and requires token-identical streams, and the wall-clock sweep
(``run_stream_wall``) replays a Poisson trace at three offered loads
with overlap on vs off on REAL time — TTFT/ITL percentiles in seconds
plus ``host_overhead_fraction``, the host-bookkeeping share of the
loop's non-idle wall time (docs/streaming.md defines the measurement
model).  The wall numbers are hardware-dependent and only
coarse-gated (fraction < 0.9); token identity is gated exactly.

Run standalone (writes the ``BENCH_engine.json`` artifact)::

    PYTHONPATH=src python -m benchmarks.engine_throughput \
        --json BENCH_engine.json

or via the harness (``python -m benchmarks.run --only engine``).
"""
from __future__ import annotations

import time

N_SLOTS, PREFILL_LEN, MAX_CACHE = 4, 32, 96
CHUNK_LEN, DECODE_PER_PREFILL = 8, 2
TOKEN_BUDGET = N_SLOTS + CHUNK_LEN


class StepClock:
    """Logical clock in decode-step units, advanced by the drive loop."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def bench_config():
    from repro.models.config import ModelConfig
    return ModelConfig(
        name="bench-dense", arch_type="dense", n_layers=4, d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
        mlp_kind="swiglu", norm_kind="rmsnorm", pos="rope",
        tie_embeddings=True)


def logical_costs(cfg) -> dict:
    """Analytic per-step costs in decode-step units (deterministic)."""
    from .common import serve_step_flops
    decode = serve_step_flops(cfg, rows=N_SLOTS, nq_per_row=1,
                              m=MAX_CACHE, lm_head=True)
    chunk = serve_step_flops(cfg, rows=N_SLOTS, nq_per_row=CHUNK_LEN,
                             m=PREFILL_LEN)
    flush = serve_step_flops(cfg, rows=N_SLOTS, nq_per_row=PREFILL_LEN,
                             m=PREFILL_LEN, lm_head=True)
    return {"decode": 1.0, "chunk": chunk / decode,
            "padded_flush": flush / decode, "decode_flops": decode}


def prefill_flops_per_request(cfg, plens, mode: str) -> float:
    """Mean per-request prefill FLOPs over a trace's prompt lengths:
    packed pays exactly one query per REAL prompt token; chunked pays
    ceil(len/chunk) chunks of chunk_len queries against the prefill
    region; padded always pays the full pad-to-length forward."""
    from .common import serve_step_flops
    total = 0.0
    for plen in plens:
        if mode == "packed":
            total += serve_step_flops(cfg, rows=plen, nq_per_row=1,
                                      m=PREFILL_LEN)
        elif mode == "chunked":
            n_chunks = -(-plen // CHUNK_LEN)
            total += n_chunks * serve_step_flops(
                cfg, rows=1, nq_per_row=CHUNK_LEN, m=PREFILL_LEN)
        else:
            total += serve_step_flops(cfg, rows=1,
                                      nq_per_row=PREFILL_LEN,
                                      m=PREFILL_LEN, lm_head=True)
    return total / max(1, len(plens))


def build_engine(mode: str, *, prefix_cache: bool | None = None,
                 offload: bool = False, n_pages: int | None = None,
                 faults=None, max_restarts: int = 3, wall: bool = False):
    import jax
    from repro.models import transformer as T
    from repro.runtime.serve import ServeHParams
    from repro.serving import EngineConfig, ServingEngine
    from repro.launch.mesh import make_mesh

    cfg = bench_config()
    mesh = make_mesh((1, 1), ("data", "model"))
    params = T.init(cfg, jax.random.PRNGKey(0))
    # wall=True keeps the engine on real time (time.monotonic) — the
    # streaming wall-clock mode measures seconds, not decode-steps
    clock = time.monotonic if wall else StepClock()
    prefill_mode = {"packed": "packed", "padded": "padded"}.get(
        mode, "chunked")
    ecfg = EngineConfig(
        n_slots=N_SLOTS, prefill_len=PREFILL_LEN, max_cache=MAX_CACHE,
        hp=ServeHParams(decode_mode="exact", ssm_chunk=8),
        decode_per_prefill=DECODE_PER_PREFILL,
        chunk_len=CHUNK_LEN, token_budget=TOKEN_BUDGET,
        prefill_mode=prefill_mode, gang=(mode == "gang"),
        prefix_cache=prefix_cache, offload=offload, n_pages=n_pages,
        faults=faults, max_restarts=max_restarts)
    eng = ServingEngine(cfg, mesh, params, ecfg, clock=clock)
    return eng, clock, cfg


def make_trace(cfg, *, n_requests, arrival_gap, plen_range, gen_range,
               seed=0):
    """Shared deterministic Poisson trace: [(arrival, prompt, gen)]."""
    import numpy as np
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(arrival_gap, size=n_requests))
    out = []
    for i in range(n_requests):
        plen = int(rng.integers(*plen_range))
        prompt = rng.integers(1, cfg.vocab_size, size=plen).tolist()
        out.append((float(arrivals[i]), prompt,
                    int(rng.integers(*gen_range))))
    return out


def make_prefix_trace(cfg, *, n_requests, arrival_gap, prefix_len,
                      suffix_range, gen_range, seed=0):
    """System-prompt trace: every request's prompt opens with the SAME
    ``prefix_len``-token prefix (a shared system prompt) followed by a
    short random suffix.  Arrivals are spaced so requests mostly
    serialize — the first completion registers the prefix pages, every
    later admission maps them copy-on-write and skips prefilling the
    covered tokens."""
    import numpy as np
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, cfg.vocab_size, size=prefix_len).tolist()
    arrivals = np.cumsum(rng.exponential(arrival_gap, size=n_requests))
    out = []
    for i in range(n_requests):
        suffix = rng.integers(1, cfg.vocab_size,
                              size=int(rng.integers(*suffix_range)))
        out.append((float(arrivals[i]), prefix + suffix.tolist(),
                    int(rng.integers(*gen_range))))
    return out


def make_overload_trace(cfg, *, seed=4):
    """Overload trace: arrivals demand more pages than the pool holds.
    Eight priority-0 background requests (long generations, 4-6 pages
    each against a 14-page pool) arrive in a burst, then six priority-1
    interactive requests (one page each) land inside the busy window.
    Items are (arrival, prompt, gen, priority) 4-tuples: with the
    offload tier ON a blocked interactive arrival spills the
    lowest-priority longest-remaining decode to host memory and admits
    immediately; OFF it queues until a background request drains."""
    import numpy as np
    rng = np.random.default_rng(seed)
    out = []
    t = 0.0
    for _ in range(8):
        t += float(rng.exponential(2.0))
        plen = int(rng.integers(12, 25))
        prompt = rng.integers(1, cfg.vocab_size, size=plen).tolist()
        out.append((t, prompt, int(rng.integers(40, 61)), 0))
    for k in range(6):
        plen = int(rng.integers(4, 9))
        prompt = rng.integers(1, cfg.vocab_size, size=plen).tolist()
        out.append((30.0 + 25.0 * k, prompt, int(rng.integers(4, 9)), 1))
    out.sort(key=lambda item: item[0])
    return out


def run_trace(mode: str, trace, costs, *,
              prefix_cache: bool | None = None, offload: bool = False,
              n_pages: int | None = None) -> tuple:
    """Drive one engine over a trace on the analytic logical clock.
    Returns (logical metrics plus measured wall ms per step kind,
    {trace index: generated token ids}) — the token lists let the
    harness gate packed ≡ chunked token-for-token."""
    import numpy as np
    from repro.serving import EngineStats, SamplingParams
    from .common import packed_step_flops

    eng, clock, cfg = build_engine(mode, prefix_cache=prefix_cache,
                                   offload=offload, n_pages=n_pages)
    # compile warmup outside the measured window (one multi-chunk
    # prompt + one short, through eviction)
    eng.submit(list(range(1, 20)), max_new_tokens=2)
    eng.submit([1, 2, 3], max_new_tokens=2)
    eng.run()
    warmed = len(eng.results())
    eng.stats = EngineStats(n_slots=eng.n_slots)

    t0_trace = clock.t
    for i, item in enumerate(trace):
        arrival, prompt, gen = item[0], item[1], item[2]
        eng.submit(prompt, max_new_tokens=gen,
                   sampling=SamplingParams(seed=i),
                   arrival=t0_trace + arrival,
                   priority=item[3] if len(item) > 3 else 0)

    cost = {"decode": costs["decode"],
            "prefill": (costs["chunk"] if mode != "padded"
                        else costs["padded_flush"])}
    wall = {"decode": [], "prefill": [], "packed": []}
    while eng._sched.has_work or eng._pending:
        d0 = eng.stats.packed_decode_tokens
        p0 = eng.stats.packed_prefill_tokens
        w0 = time.perf_counter()
        kind = eng.step()
        if kind == "packed":
            # cost ∝ the tick's REAL packed tokens, from the engine's
            # own counters — not n_slots × chunk_len
            wall[kind].append(time.perf_counter() - w0)
            clock.t += packed_step_flops(
                cfg,
                decode_tokens=eng.stats.packed_decode_tokens - d0,
                prefill_tokens=eng.stats.packed_prefill_tokens - p0,
                m_decode=MAX_CACHE,
                m_prefill=PREFILL_LEN) / costs["decode_flops"]
        elif kind in cost:
            wall[kind].append(time.perf_counter() - w0)
            clock.t += cost[kind]
        else:                               # idle: jump to next arrival
            clock.t += max(0.0, eng.next_arrival() - eng.now())
    steps = clock.t - t0_trace
    assert len(eng.results()) == len(trace) + warmed

    s = eng.stats.summary()
    med = (lambda xs: 1e3 * float(np.median(xs)) if xs else 0.0)
    results = {rid - warmed: toks for rid, toks in eng.results().items()
               if rid >= warmed}
    # per-priority-class TTFT: the preemption gate compares the
    # interactive class directly, not the pooled percentile
    by_class: dict = {}
    for i, item in enumerate(trace):
        pri = item[3] if len(item) > 3 else 0
        by_class.setdefault(pri, []).append(eng._results[i + warmed].ttft)
    ttft_by_class = {str(p): float(np.median(v))
                     for p, v in sorted(by_class.items())}
    return {
        "requests_per_ksteps": 1e3 * len(trace) / steps,
        "ttft_p50_steps": s["ttft_p50_s"],   # logical-clock units
        "ttft_p90_steps": s["ttft_p90_s"],
        "ttft_p99_steps": s["ttft_p99_s"],
        "ttft_p50_by_class": ttft_by_class,
        "preemptions": s["preemptions"],
        "spilled_pages": s["spilled_pages"],
        "restore_hits": s["restore_hits"],
        "restore_misses": s["restore_misses"],
        "ttft_max_steps": s["ttft_max_s"],
        "occupancy": s["occupancy"],
        "prefills": s["prefills"],
        "prefill_chunks": s["prefill_chunks"],
        "prefill_tokens": s["prefill_tokens"],
        "chunk_tokens_real": s["chunk_tokens_real"],
        "chunk_tokens_padded": s["chunk_tokens_padded"],
        "decode_steps": s["decode_steps"],
        "packed_ticks": s["packed_ticks"],
        "packed_decode_tokens": s["packed_decode_tokens"],
        "packed_prefill_tokens": s["packed_prefill_tokens"],
        "out_of_pages": s["out_of_pages"],
        "prefix_hits": s["prefix_hits"],
        "prefix_tokens_saved": s["prefix_tokens_saved"],
        "restarts": s["restarts"],
        "deadline_miss": s["deadline_miss"],
        "quarantined": s["quarantined"],
        "failed_requests": s["failed_requests"],
        "faults_injected": s["faults_injected"],
        "elapsed_steps": steps,
        "wall_decode_ms": med(wall["decode"]),
        "wall_prefill_ms": med(wall["prefill"]),
        "wall_packed_ms": med(wall["packed"]),
    }, results


def run_chaos(trace, clean_toks, *, seed: int) -> dict:
    """Chaos soak: the page-starved overload trace through the packed
    offload engine with EVERY fault kind enabled (``FaultPlan.chaos``)
    — store put/get loss, page poisoning, admission stalls, tick
    delays.  Not a throughput measurement: the return value carries
    the correctness verdicts compare.py gates —

      * ``token_match``: every request the faulted engine COMPLETED
        emitted exactly the clean run's tokens (per-request seeded
        sampling makes tokens independent of timing, slots, and
        restarts, so recovery is provably lossless);
      * ``zero_leak``: after the drain, page refcounts audit clean,
        every page/state row/slot is back in its pool, and the host
        store holds zero bytes;
      * fault/recovery counters for the report.

    Runs its own drive loop (not ``run_trace``): failed requests mean
    ``results() != trace length``, stalled ticks need a clock bump,
    and a stuck-admission tick must still advance the logical clock."""
    from repro.serving import FaultPlan, SamplingParams

    eng, clock, cfg = build_engine(
        "packed", prefix_cache=False, offload=True, n_pages=14,
        faults=FaultPlan.chaos(seed), max_restarts=8)
    for i, (arrival, prompt, gen, pri) in enumerate(trace):
        eng.submit(prompt, max_new_tokens=gen,
                   sampling=SamplingParams(seed=i), arrival=arrival,
                   priority=pri)
    for _ in range(200_000):
        kind = eng.step()
        if kind != "idle":
            clock.t += 1.0
        elif eng._sched.has_work:
            clock.t += 1.0             # stalled admission: retry
        elif eng._pending:
            clock.t += max(1.0, eng.next_arrival() - eng.now())
        else:
            break
    else:
        raise RuntimeError(f"chaos seed {seed} did not drain")

    results = eng.results()
    failed = eng.failed()
    token_match = all(toks == clean_toks[rid]
                      for rid, toks in results.items())
    kv, store = eng.kv_cache, eng.kv_store
    kv.check()
    zero_leak = (not kv.slot_pages and not kv.slot_state
                 and kv.table.free_pages == kv.paging.n_pages
                 and sorted(kv._state_free)
                 == list(range(kv.paging.n_state_pages))
                 and len(store) == 0 and store.bytes_used == 0
                 and sorted(eng._sched.free_slots) == list(range(N_SLOTS)))
    s = eng.stats.summary()
    return {
        "seed": seed,
        "completed": len(results),
        "failed": len(failed),
        "token_match": bool(token_match),
        "zero_leak": bool(zero_leak),
        "accounted": len(results) + len(failed) == len(trace),
        "faults_injected": s["faults_injected"],
        "injected_by_kind": dict(eng._injector.injected),
        "restarts": s["restarts"],
        "quarantined": s["quarantined"],
        "restore_misses": s["restore_misses"],
        "preemptions": s["preemptions"],
    }


def run_degraded(trace) -> dict:
    """Degraded-availability trace: the packed streaming engine with a
    SCHEDULED ``shard_loss`` fault — the (bench mesh's only) sequence
    shard dies mid-decode, so the degraded window serves entirely from
    the Segment-Means standby replicas before the deterministic
    re-prefill recovery.  Returns the verdicts behind the three
    compare.py gates:

      * ``streams_finite``: every stream closed with exactly its
        requested token count, all finite (the degraded window never
        leaks a NaN or stalls a stream);
      * ``zero_leak``: the drained engine audits clean;
      * ``recovery_token_match``: every request — the ones rewound by
        recovery AND the ones admitted after it — finished
        token-identical to the clean run, with the degraded window
        actually observed (``shard_lost``/``degraded_ticks`` >= 1)."""
    from repro.runtime.faults import FaultSpec
    from repro.serving import (FaultPlan, SamplingParams,
                               StreamingEngine)

    def drive_sync(eng, clock, step):
        for _ in range(200_000):
            kind = step()
            if kind != "idle":
                clock.t += 1.0
            elif eng._sched.has_work:
                clock.t += 1.0
            elif eng._pending:
                clock.t += max(1.0, eng.next_arrival() - eng.now())
            else:
                return
        raise RuntimeError("degraded trace did not drain")

    clean, clock, cfg = build_engine("packed", prefix_cache=False)
    for i, (arrival, prompt, gen) in enumerate(trace):
        clean.submit(prompt, max_new_tokens=gen,
                     sampling=SamplingParams(seed=i), arrival=arrival)
    drive_sync(clean, clock, clean.step)
    clean_toks = clean.results()

    plan = FaultPlan(shard_loss=FaultSpec(at=(12,), shard=0))
    eng, clock, cfg = build_engine("packed", prefix_cache=False,
                                   faults=plan, max_restarts=8)
    seng = StreamingEngine(eng)         # injector forces sync ticks
    streams = {}
    for i, (arrival, prompt, gen) in enumerate(trace):
        _, streams[i] = seng.submit_stream(
            prompt, max_new_tokens=gen, sampling=SamplingParams(seed=i),
            arrival=arrival)
    drive_sync(eng, clock, seng.step)
    seng.drain()
    seng._flush_streams()

    delivered = {i: streams[i].drain() for i in range(len(trace))}
    streams_finite = all(
        len(delivered[i]) == trace[i][2]
        and all(isinstance(t, int) for t in delivered[i])
        and streams[i].finished is not None
        for i in range(len(trace)))
    results = eng.results()
    s = eng.stats.summary()
    token_match = (len(results) == len(trace)
                   and all(toks == clean_toks[rid]
                           for rid, toks in results.items())
                   and not eng.failed()
                   and s["shard_lost"] >= 1
                   and s["degraded_ticks"] >= 1)
    kv = eng.kv_cache
    kv.check()
    zero_leak = (not kv.slot_pages and not kv.slot_state
                 and kv.table.free_pages == kv.paging.n_pages
                 and sorted(kv._state_free)
                 == list(range(kv.paging.n_state_pages))
                 and sorted(eng._sched.free_slots) == list(range(N_SLOTS)))
    return {
        "streams_finite": bool(streams_finite),
        "zero_leak": bool(zero_leak),
        "recovery_token_match": bool(token_match),
        "shard_lost": s["shard_lost"],
        "degraded_ticks": s["degraded_ticks"],
        "restarts": s["restarts"],
        "replica_captures": (eng._replica.stats()["captures"]
                             if eng._replica is not None else 0),
        "injected_by_kind": dict(eng._injector.injected),
    }


def run_stream_match(trace, sync_toks, costs) -> dict:
    """Streamed ≡ synchronous tokens on the identical trace.  Drives a
    ``StreamingEngine`` (overlap ON, depth 2) on the same logical
    StepClock ``run_trace`` uses; greedy per-request seeded sampling
    makes tokens scheduling-independent, so every stream must deliver
    exactly the sync packed engine's token list — the
    ``stream_token_match`` gate."""
    from repro.serving import EngineStats, SamplingParams, StreamingEngine
    from .common import packed_step_flops

    eng, clock, cfg = build_engine("packed")
    seng = StreamingEngine(eng, overlap=True)
    eng.submit(list(range(1, 20)), max_new_tokens=2)
    eng.submit([1, 2, 3], max_new_tokens=2)
    seng.run_sync()                    # compile warmup, as in run_trace
    eng.stats = EngineStats(n_slots=eng.n_slots)

    t0_trace = clock.t
    streams = {}
    for i, item in enumerate(trace):
        arrival, prompt, gen = item[0], item[1], item[2]
        _, streams[i] = seng.submit_stream(
            prompt, max_new_tokens=gen, sampling=SamplingParams(seed=i),
            arrival=t0_trace + arrival,
            priority=item[3] if len(item) > 3 else 0)
    # the clock charges device work at DISPATCH (that is when the
    # program is enqueued); reconcile-only iterations are free — they
    # overlap the next tick's compute
    while seng.has_work:
        d0 = eng.stats.packed_decode_tokens
        p0 = eng.stats.packed_prefill_tokens
        kind = seng.step()
        if kind == "packed":
            clock.t += packed_step_flops(
                cfg,
                decode_tokens=eng.stats.packed_decode_tokens - d0,
                prefill_tokens=eng.stats.packed_prefill_tokens - p0,
                m_decode=MAX_CACHE,
                m_prefill=PREFILL_LEN) / costs["decode_flops"]
        elif kind == "decode":
            clock.t += costs["decode"]
        elif kind == "idle" and eng._pending:
            clock.t += max(0.0, eng.next_arrival() - eng.now())
    streamed = {i: streams[i].drain() for i in range(len(trace))}
    finished = {i: streams[i].finished for i in range(len(trace))}
    s = eng.stats.summary()
    return {
        "token_match": all(streamed[i] == sync_toks[i]
                           for i in range(len(trace))),
        "all_finished": all(f is not None for f in finished.values()),
        "tokens_streamed": s["tokens_streamed"],
        "packed_ticks": s["packed_ticks"],
        "decode_steps": s["decode_steps"],
        "ticks_idle": s["ticks_idle"],
    }


def run_stream_wall(trace, *, overlap: bool) -> dict:
    """Wall-clock streaming measurement: the SAME trace on real time
    (arrivals in seconds), TTFT/ITL percentiles in wall seconds, and
    the host-overhead fraction — host bookkeeping seconds over the
    loop's non-idle wall seconds, the number double-buffering exists to
    shrink (docs/streaming.md defines the measurement model).  Run with
    overlap on and off for the A/B the EXPERIMENTS entry reports."""
    import numpy as np
    from repro.serving import EngineStats, SamplingParams, StreamingEngine

    eng, _, cfg = build_engine("packed", wall=True)
    seng = StreamingEngine(eng, overlap=overlap)
    eng.submit(list(range(1, 20)), max_new_tokens=2)
    eng.submit([1, 2, 3], max_new_tokens=2)
    seng.run_sync()                    # compile warmup, unmeasured
    eng.stats = EngineStats(n_slots=eng.n_slots)

    t0 = eng.now()
    streams = {}
    for i, item in enumerate(trace):
        arrival, prompt, gen = item[0], item[1], item[2]
        _, streams[i] = seng.submit_stream(
            prompt, max_new_tokens=gen, sampling=SamplingParams(seed=i),
            arrival=t0 + arrival)
    w0 = time.perf_counter()
    seng.run_sync()
    wall_s = time.perf_counter() - w0
    itl = seng.itl_samples()
    s = eng.stats.summary()
    pct = lambda xs, q: float(np.percentile(xs, q)) if xs else 0.0
    return {
        "overlap": overlap,
        "requests": len(trace),
        "wall_s": wall_s,
        "decode_tokens_per_s": (eng.stats.generated_tokens / wall_s
                                if wall_s > 0 else 0.0),
        "ttft_p50_s": s["ttft_p50_s"],
        "ttft_p99_s": s["ttft_p99_s"],
        "itl_p50_s": pct(itl, 50),
        "itl_p99_s": pct(itl, 99),
        "host_overhead_fraction": s["host_overhead_fraction"],
        "ticks": s["packed_ticks"] + s["decode_steps"],
        "ticks_idle": s["ticks_idle"],
        "tokens_streamed": s["tokens_streamed"],
    }


def packed_cache_sized_concats() -> int:
    """Structural proof that the packed program never materializes a
    cache-sized concatenate: walk the traced jaxpr (same technique as
    the decode microbench) and count concatenate eqns whose output
    carries >= MAX_CACHE elements in any dim.  Walks the PAGED packed
    program — the production default — so the page-indirection gathers
    are covered by the gate too."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as T
    from repro.runtime.paging import make_paged_layout
    from repro.runtime.serve import (ServeHParams, make_kv_cache,
                                     make_layout, make_packed_step)
    from repro.launch.mesh import make_mesh

    cfg = bench_config()
    mesh = make_mesh((1, 1), ("data", "model"))
    params = T.init(cfg, jax.random.PRNGKey(0))
    hp = ServeHParams(decode_mode="exact", ssm_chunk=8)
    base = make_layout(cfg, mesh, N_SLOTS, MAX_CACHE, hp, PREFILL_LEN)
    paging = make_paged_layout(base, page_tokens=16, n_pages=None,
                               n_slots=N_SLOTS)
    step, lay, _, _ = make_packed_step(
        cfg, mesh, params, batch=N_SLOTS, cap=MAX_CACHE,
        prefill_len=PREFILL_LEN, token_budget=TOKEN_BUDGET, hp=hp,
        paging=paging)
    kv = make_kv_cache(cfg, mesh, lay, N_SLOTS, hp, paging=paging)
    tb = TOKEN_BUDGET
    args = (params, kv.storage, jnp.zeros(tb, jnp.int32),
            jnp.full(tb, -1, jnp.int32), jnp.full(tb, -1, jnp.int32),
            jnp.full(tb, -1, jnp.int32), jnp.zeros(tb, jnp.int32),
            jnp.asarray(kv.page_map(N_SLOTS)),
            jnp.asarray(kv.state_map(N_SLOTS)))

    def walk(jx):
        n = 0
        for e in jx.eqns:
            if (e.primitive.name == "concatenate"
                    and any(d >= MAX_CACHE
                            for d in e.outvars[0].aval.shape)):
                n += 1
            for sub in e.params.values():
                subs = sub if isinstance(sub, (list, tuple)) else [sub]
                n += sum(walk(s.jaxpr) for s in subs
                         if hasattr(s, "jaxpr"))
        return n
    return walk(jax.make_jaxpr(step)(*args).jaxpr)


def run_all() -> dict:
    """All traces through every relevant engine; the BENCH_engine.json
    payload, including the structural gates compare.py enforces."""
    import jax

    cfg = bench_config()
    costs = logical_costs(cfg)
    # main trace: generation-dominated serving at moderate load (chat
    # regime — decode work ≫ prefill work, generation lengths highly
    # variable, arrivals near the service rate) — the regime users
    # feel, where head-of-line blocking shows.
    main_trace = make_trace(cfg, n_requests=24, arrival_gap=30.0,
                            plen_range=(8, 33), gen_range=(8, 65), seed=0)
    # short-prompt trace: where pad-to-prefill_len waste is largest
    short_trace = make_trace(cfg, n_requests=16, arrival_gap=2.0,
                             plen_range=(4, 9), gen_range=(8, 25), seed=1)
    # saturated trace: arrivals far above the service rate, queue
    # always deep — the regime where PR-4's FLOP clock recorded gang
    # flushes out-amortizing per-row chunk calls; token packing is the
    # answer, and this trace gates it.
    sat_trace = make_trace(cfg, n_requests=24, arrival_gap=0.5,
                           plen_range=(8, 33), gen_range=(8, 33), seed=2)

    res, toks = {}, {}
    for trace_name, trace, modes in (
            ("main", main_trace, ("packed", "chunked", "padded", "gang")),
            ("short", short_trace, ("packed", "chunked", "padded")),
            ("saturated", sat_trace, ("packed", "chunked", "gang"))):
        res[trace_name], toks[trace_name] = {}, {}
        for m in modes:
            res[trace_name][m], toks[trace_name][m] = run_trace(
                m, trace, costs)

    # shared-prefix (system-prompt) trace: identical trace through the
    # packed engine with prefix reuse ON vs OFF — the tokens must match
    # exactly and ON must prefill strictly fewer prompt tokens
    prefix_trace = make_prefix_trace(
        cfg, n_requests=12, arrival_gap=120.0, prefix_len=24,
        suffix_range=(4, 9), gen_range=(8, 17), seed=3)
    res["prefix"], toks["prefix"] = {}, {}
    for name, on in (("prefix_on", True), ("prefix_off", False)):
        res["prefix"][name], toks["prefix"][name] = run_trace(
            "packed", prefix_trace, costs, prefix_cache=on)

    # overload (preemption) trace: identical page-starved trace with the
    # host offload tier ON vs OFF — spill/restore must not change a
    # token, and the interactive class's TTFT must not get worse (the
    # whole point of preempting background work).  prefix reuse is off
    # so page accounting is exact in both runs.
    overload_trace = make_overload_trace(cfg, seed=4)
    res["overload"], toks["overload"] = {}, {}
    for name, on in (("preempt_on", True), ("preempt_off", False)):
        res["overload"][name], toks["overload"][name] = run_trace(
            "packed", overload_trace, costs, prefix_cache=False,
            offload=on, n_pages=14)

    # chaos soak: the same overload trace under seeded all-kinds fault
    # injection, three seeds — surviving requests must emit the clean
    # run's exact tokens and the drained engine must audit leak-free
    res["chaos"] = {}
    for seed in (0, 1, 2):
        res["chaos"][f"seed{seed}"] = run_chaos(
            overload_trace, toks["overload"]["preempt_on"], seed=seed)

    # degraded-availability: a scheduled shard_loss kills the bench
    # mesh's only sequence shard mid-decode — the window must serve
    # finite tokens from the Segment-Means replicas and recovery must
    # restore token identity with the clean run
    deg_trace = make_trace(cfg, n_requests=10, arrival_gap=2.0,
                           plen_range=(8, 33), gen_range=(8, 25),
                           seed=6)
    res["degraded"] = run_degraded(deg_trace)

    # streaming: token identity vs the sync packed run on the identical
    # main trace (logical clock), then the wall-clock load sweep —
    # offered load rises low -> high; TTFT/ITL tails and the idle-tick
    # count locate the saturation knee, host_overhead_fraction is the
    # overlap-efficiency number the compare gate bounds
    res["stream"] = {"match": run_stream_match(
        main_trace, toks["main"]["packed"], costs)}
    res["stream"]["wall"] = {}
    for rate_name, gap in (("low", 0.10), ("mid", 0.02),
                           ("high", 0.004)):
        wtrace = make_trace(cfg, n_requests=10, arrival_gap=gap,
                            plen_range=(8, 33), gen_range=(8, 25),
                            seed=5)
        res["stream"]["wall"][rate_name] = {
            "overlap_on": run_stream_wall(wtrace, overlap=True),
            "overlap_off": run_stream_wall(wtrace, overlap=False)}

    flops = {}
    for trace_name, trace in (("main", main_trace),
                              ("short", short_trace)):
        for m in ("packed", "chunked", "padded"):
            flops[f"{trace_name}_{m}"] = prefill_flops_per_request(
                cfg, [len(p) for _, p, _ in trace], m)
    # measured (not analytic) prefill work on the prefix trace: packed
    # pays one query per token it ACTUALLY prefills, so per-request
    # FLOPs scale down with the prefix tokens never laid down
    from .common import serve_step_flops
    per_tok = serve_step_flops(cfg, rows=1, nq_per_row=1, m=PREFILL_LEN)
    for name in ("prefix_on", "prefix_off"):
        flops[f"prefix_{name}"] = (
            per_tok * res["prefix"][name]["prefill_tokens"]
            / len(prefix_trace))

    n_concats = packed_cache_sized_concats()
    gates = {
        # chunked prefill must cost fewer FLOPs per request AND no
        # worse median TTFT than the padded baseline on short prompts
        "short_prefill_flops_lower": (flops["short_chunked"]
                                      < flops["short_padded"]),
        "short_ttft_no_worse": (
            res["short"]["chunked"]["ttft_p50_steps"]
            <= res["short"]["padded"]["ttft_p50_steps"] + 1e-9),
        # chunked beats the padded-flush admission it replaces
        "chunked_vs_padded_ttft_no_worse": (
            res["main"]["chunked"]["ttft_p50_steps"]
            <= res["main"]["padded"]["ttft_p50_steps"] + 1e-9),
        # continuous batching vs static: TTFT is the classic win
        "continuous_vs_gang_ttft_speedup": (
            res["main"]["gang"]["ttft_p50_steps"]
            / max(res["main"]["chunked"]["ttft_p50_steps"], 1e-9)),
        "continuous_vs_gang_speedup": (
            res["main"]["chunked"]["requests_per_ksteps"]
            / res["main"]["gang"]["requests_per_ksteps"]),
        # ---- packed structural gates ---------------------------------
        # kernel-match analog: packed serving is token-identical to the
        # chunked oracle on the identical main trace
        "packed_token_match": all(
            toks["main"]["packed"][i] == toks["main"]["chunked"][i]
            for i in range(len(main_trace))),
        # the packed program materializes no cache-sized concatenate
        "packed_concat_free": n_concats == 0,
        "packed_cache_sized_concats": n_concats,
        # packing may not regress the moderate-load regime it inherits
        "packed_vs_chunked_no_regression": (
            res["main"]["packed"]["requests_per_ksteps"]
            >= 0.999 * res["main"]["chunked"]["requests_per_ksteps"]),
        # THE saturation claim: packed logical throughput >= gang while
        # TTFT p50 <= chunked — per-tick cost now scales with real
        # tokens, so packing out-amortizes the gang flush too
        "packed_vs_gang_saturated": (
            res["saturated"]["packed"]["requests_per_ksteps"]
            >= res["saturated"]["gang"]["requests_per_ksteps"]),
        "packed_ttft_no_worse_saturated": (
            res["saturated"]["packed"]["ttft_p50_steps"]
            <= res["saturated"]["chunked"]["ttft_p50_steps"] + 1e-9),
        "packed_vs_gang_saturated_speedup": (
            res["saturated"]["packed"]["requests_per_ksteps"]
            / max(res["saturated"]["gang"]["requests_per_ksteps"],
                  1e-9)),
        # ---- prefix-reuse gates --------------------------------------
        # COW sharing must not change a single token ...
        "prefix_token_match": all(
            toks["prefix"]["prefix_on"][i] == toks["prefix"]["prefix_off"][i]
            for i in range(len(prefix_trace))),
        # ... while strictly reducing the prompt tokens prefilled (the
        # saved fraction of the OFF run's prefill work)
        "prefix_reuse_savings": (
            (res["prefix"]["prefix_off"]["prefill_tokens"]
             - res["prefix"]["prefix_on"]["prefill_tokens"])
            / max(res["prefix"]["prefix_off"]["prefill_tokens"], 1)),
        "prefix_hits": res["prefix"]["prefix_on"]["prefix_hits"],
        "prefix_ttft_no_worse": (
            res["prefix"]["prefix_on"]["ttft_p50_steps"]
            <= res["prefix"]["prefix_off"]["ttft_p50_steps"] + 1e-9),
        # ---- preemption gates ----------------------------------------
        # spill -> host store -> restore must not change a single token
        # vs the same page-starved trace served without preemption ...
        "preempt_token_match": all(
            toks["overload"]["preempt_on"][i]
            == toks["overload"]["preempt_off"][i]
            for i in range(len(overload_trace))),
        # ... the overload trace must actually exercise the tier ...
        "preempt_fired": (
            res["overload"]["preempt_on"]["preemptions"] > 0
            and res["overload"]["preempt_on"]["restore_hits"] > 0),
        # ... and the interactive class (priority 1) must reach its
        # first token no later than when it has to queue behind
        # background decodes for free pages
        "preempt_ttft_no_worse": (
            res["overload"]["preempt_on"]["ttft_p50_by_class"]["1"]
            <= res["overload"]["preempt_off"]["ttft_p50_by_class"]["1"]
            + 1e-9),
        "preempt_interactive_ttft_speedup": (
            res["overload"]["preempt_off"]["ttft_p50_by_class"]["1"]
            / max(res["overload"]["preempt_on"]["ttft_p50_by_class"]["1"],
                  1e-9)),
        # ---- chaos-soak gates ----------------------------------------
        # every request a faulted engine completed is token-identical
        # to the clean run, on every seed ...
        "chaos_token_match": all(
            c["token_match"] and c["accounted"]
            for c in res["chaos"].values()),
        # ... the drained engine leaks nothing (pages, state rows,
        # store bytes, slots) on every seed ...
        "chaos_zero_leak": all(
            c["zero_leak"] for c in res["chaos"].values()),
        # ... and each seed actually injected faults AND completed
        # requests (an empty soak proves nothing)
        "chaos_faults_fired": all(
            c["faults_injected"] > 0 and c["completed"] > 0
            for c in res["chaos"].values()),
        # ---- degraded-mesh gates -------------------------------------
        # every stream crossing the shard-loss window still closed with
        # exactly its requested (finite) token count ...
        "degraded_streams_finite": res["degraded"]["streams_finite"],
        # ... the recovered engine audits clean ...
        "degraded_zero_leak": res["degraded"]["zero_leak"],
        # ... and after the re-prefill recovery every request finished
        # token-identical to the clean run, with the degraded window
        # actually observed (shard_lost/degraded_ticks >= 1)
        "degraded_recovery_token_match": (
            res["degraded"]["recovery_token_match"]),
        # ---- streaming gates -----------------------------------------
        # the overlapped double-buffered loop must deliver EXACTLY the
        # synchronous engine's tokens on the identical trace, and every
        # stream must close with a finish reason
        "stream_token_match": (res["stream"]["match"]["token_match"]
                               and res["stream"]["match"]["all_finished"]),
        "stream_overlap_ran": res["stream"]["match"]["packed_ticks"] > 0,
        # host bookkeeping share of the wall loop, worst overlap-on run
        # — a generous ceiling (the loop must be device-bound, not
        # host-bound; exact values vary with CI hardware)
        "host_overhead_fraction": max(
            w["overlap_on"]["host_overhead_fraction"]
            for w in res["stream"]["wall"].values()),
        "host_overhead_ok": all(
            0.0 <= w["overlap_on"]["host_overhead_fraction"] < 0.9
            for w in res["stream"]["wall"].values()),
    }
    return {
        "bench": "engine_throughput",
        "platform": jax.default_backend(),
        "config": {"n_slots": N_SLOTS, "prefill_len": PREFILL_LEN,
                   "max_cache": MAX_CACHE, "chunk_len": CHUNK_LEN,
                   "decode_per_prefill": DECODE_PER_PREFILL,
                   "token_budget": TOKEN_BUDGET,
                   "n_layers": cfg.n_layers, "d_model": cfg.d_model},
        "logical_costs": {k: v for k, v in costs.items()
                          if k != "decode_flops"},
        "traces": res,
        "prefill_flops_per_request": flops,
        "gates": gates,
    }


def main(report):
    payload = run_all()
    res, flops = payload["traces"], payload["prefill_flops_per_request"]
    for name in ("packed", "chunked", "padded", "gang"):
        s = res["main"][name]
        report(f"engine/{name}/requests_per_ksteps", 0.0,
               f"{s['requests_per_ksteps']:.1f}")
        report(f"engine/{name}/ttft_p50_steps", 0.0,
               f"{s['ttft_p50_steps']:.1f} (p90 {s['ttft_p90_steps']:.1f})")
        report(f"engine/{name}/occupancy", 0.0, f"{s['occupancy']:.2f}")
        report(f"engine/{name}/wall_ms", s["wall_decode_ms"] * 1e3,
               f"decode {s['wall_decode_ms']:.1f}ms "
               f"prefill {s['wall_prefill_ms']:.1f}ms "
               f"packed {s['wall_packed_ms']:.1f}ms")
    for name in ("packed", "chunked", "gang"):
        s = res["saturated"][name]
        report(f"engine/saturated/{name}/requests_per_ksteps", 0.0,
               f"{s['requests_per_ksteps']:.1f}")
        report(f"engine/saturated/{name}/ttft_p50_steps", 0.0,
               f"{s['ttft_p50_steps']:.1f}")
    for name in ("packed", "chunked", "padded"):
        s = res["short"][name]
        report(f"engine/short/{name}/ttft_p50_steps", 0.0,
               f"{s['ttft_p50_steps']:.1f}")
        report(f"engine/short/{name}/prefill_mflops_per_req", 0.0,
               f"{flops['short_' + name] / 1e6:.2f}")
    for name in ("prefix_on", "prefix_off"):
        s = res["prefix"][name]
        report(f"engine/prefix/{name}/ttft_p50_steps", 0.0,
               f"{s['ttft_p50_steps']:.1f}")
        report(f"engine/prefix/{name}/prefill_tokens", 0.0,
               f"{s['prefill_tokens']} (hits {s['prefix_hits']}, "
               f"saved {s['prefix_tokens_saved']})")
        report(f"engine/prefix/{name}/prefill_mflops_per_req", 0.0,
               f"{flops['prefix_' + name] / 1e6:.2f}")
    for name, c in res["chaos"].items():
        report(f"engine/chaos/{name}", 0.0,
               f"completed {c['completed']} failed {c['failed']} "
               f"faults {c['faults_injected']} "
               f"(restarts {c['restarts']}, quarantined "
               f"{c['quarantined']}) token_match={c['token_match']} "
               f"zero_leak={c['zero_leak']}")
    for name in ("preempt_on", "preempt_off"):
        s = res["overload"][name]
        report(f"engine/overload/{name}/requests_per_ksteps", 0.0,
               f"{s['requests_per_ksteps']:.1f}")
        report(f"engine/overload/{name}/ttft_p50_steps", 0.0,
               f"{s['ttft_p50_steps']:.1f} (p99 {s['ttft_p99_steps']:.1f})")
        report(f"engine/overload/{name}/interactive_ttft_p50", 0.0,
               f"{s['ttft_p50_by_class'].get('1', 0.0):.1f}")
        report(f"engine/overload/{name}/preemptions", 0.0,
               f"{s['preemptions']} (spilled {s['spilled_pages']} pages, "
               f"{s['restore_hits']} restores)")
    d = res["degraded"]
    report("engine/degraded/shard_loss", 0.0,
           f"shard_lost {d['shard_lost']} degraded_ticks "
           f"{d['degraded_ticks']} restarts {d['restarts']} "
           f"replica_captures {d['replica_captures']} "
           f"streams_finite={d['streams_finite']} "
           f"zero_leak={d['zero_leak']} "
           f"recovery_token_match={d['recovery_token_match']}")
    m = res["stream"]["match"]
    report("engine/stream/token_match", 0.0,
           f"{m['token_match']} ({m['tokens_streamed']} streamed over "
           f"{m['packed_ticks']} packed + {m['decode_steps']} decode "
           "ticks)")
    for rate_name, w in res["stream"]["wall"].items():
        for key in ("overlap_on", "overlap_off"):
            s = w[key]
            report(f"engine/stream/{rate_name}/{key}", s["wall_s"] * 1e6,
                   f"ttft p50 {s['ttft_p50_s'] * 1e3:.1f}ms "
                   f"p99 {s['ttft_p99_s'] * 1e3:.1f}ms, "
                   f"itl p50 {s['itl_p50_s'] * 1e3:.1f}ms "
                   f"p99 {s['itl_p99_s'] * 1e3:.1f}ms, "
                   f"{s['decode_tokens_per_s']:.0f} tok/s, "
                   f"host {100 * s['host_overhead_fraction']:.1f}% "
                   f"({s['ticks']} ticks, {s['ticks_idle']} idle)")
    g = payload["gates"]
    for gate in ("short_prefill_flops_lower", "short_ttft_no_worse",
                 "chunked_vs_padded_ttft_no_worse", "packed_token_match",
                 "packed_concat_free", "packed_vs_chunked_no_regression",
                 "packed_vs_gang_saturated",
                 "packed_ttft_no_worse_saturated", "prefix_token_match",
                 "prefix_ttft_no_worse", "preempt_token_match",
                 "preempt_fired", "preempt_ttft_no_worse",
                 "chaos_token_match", "chaos_zero_leak",
                 "chaos_faults_fired", "degraded_streams_finite",
                 "degraded_zero_leak", "degraded_recovery_token_match",
                 "stream_token_match",
                 "stream_overlap_ran", "host_overhead_ok"):
        report(f"engine/gate/{gate}", 0.0, str(g[gate]))
    report("engine/stream/host_overhead_fraction", 0.0,
           f"{100 * g['host_overhead_fraction']:.1f}% (worst overlap-on "
           "run)")
    report("engine/preempt_interactive_ttft_speedup", 0.0,
           f"x{g['preempt_interactive_ttft_speedup']:.2f}")
    report("engine/prefix_reuse_savings", 0.0,
           f"{100 * g['prefix_reuse_savings']:.1f}% of prefill tokens "
           f"({g['prefix_hits']} hits)")
    report("engine/continuous_vs_static_ttft_speedup", 0.0,
           f"x{g['continuous_vs_gang_ttft_speedup']:.2f}")
    report("engine/continuous_vs_static_speedup", 0.0,
           f"x{g['continuous_vs_gang_speedup']:.2f}")
    report("engine/packed_vs_gang_saturated_speedup", 0.0,
           f"x{g['packed_vs_gang_saturated_speedup']:.2f}")
    return payload


if __name__ == "__main__":
    import argparse
    import json
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_engine.json",
                    help="where to write the engine-bench artifact")
    args = ap.parse_args()

    def _report(name, us, derived=""):
        print(f"{name},{us:.1f},{derived}")
        sys.stdout.flush()

    payload = main(_report)
    with open(args.json, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {args.json}")
    g = payload["gates"]
    if not (g["short_prefill_flops_lower"] and g["short_ttft_no_worse"]
            and g["chunked_vs_padded_ttft_no_worse"]
            and g["packed_token_match"] and g["packed_concat_free"]
            and g["packed_vs_chunked_no_regression"]
            and g["packed_vs_gang_saturated"]
            and g["packed_ttft_no_worse_saturated"]
            and g["prefix_token_match"] and g["prefix_ttft_no_worse"]
            and g["prefix_reuse_savings"] > 0
            and g["preempt_token_match"] and g["preempt_fired"]
            and g["preempt_ttft_no_worse"]
            and g["chaos_token_match"] and g["chaos_zero_leak"]
            and g["chaos_faults_fired"]
            and g["degraded_streams_finite"] and g["degraded_zero_leak"]
            and g["degraded_recovery_token_match"]
            and g["stream_token_match"]
            and g["stream_overlap_ran"] and g["host_overhead_ok"]):
        sys.exit(1)
