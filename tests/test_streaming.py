"""Async streaming front-end tests (single CPU device, mesh 1x1; the
2x4 sharded equivalence runs via tests/engine_equiv_runner.py):

  * the double-buffered overlapped loop streams EXACTLY the tokens the
    synchronous engine produces, per request, in order;
  * overlap off (synchronous ticks, streaming delivery) matches too;
  * the asyncio front-end (``serve_stream``) delivers the same tokens
    through real ``async for`` consumers;
  * mid-flight cancellation drains the tick pipeline and releases the
    slot/pages with zero leaks while every OTHER stream is unaffected;
  * a forced preemption (spill to the host store) mid-pipeline
    reconciles cleanly — in-flight speculative rows are discarded as
    stale, the restored request continues token-identically.
"""
import asyncio

import numpy as np
import jax

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving import ServingEngine, StreamingEngine, serve_stream
from repro.launch.mesh import make_mesh

TINY = ModelConfig(
    name="tiny-stream", arch_type="dense", n_layers=2, d_model=32,
    n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=61,
    mlp_kind="swiglu", norm_kind="rmsnorm", pos="rope",
    tie_embeddings=True)

_CACHE: dict = {}


def _setup():
    """Shared params/mesh/prompts + the synchronous reference tokens
    (computed once — every test compares against the same oracle)."""
    if _CACHE:
        return _CACHE
    mesh = make_mesh((1, 1), ("data", "model"))
    params = T.init(TINY, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, TINY.vocab_size,
                            size=int(rng.integers(3, 9))).tolist()
               for _ in range(6)]
    eng = _engine(params, mesh)
    for p in prompts:
        eng.submit(p, max_new_tokens=6)
    _CACHE.update(mesh=mesh, params=params, prompts=prompts,
                  ref=eng.run())
    return _CACHE


def _engine(params, mesh, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("prefill_len", 8)
    kw.setdefault("max_cache", 24)
    return ServingEngine(TINY, mesh, params, **kw)


def test_stream_tokens_match_sync_engine():
    """Overlapped double-buffered streaming is token-identical to the
    synchronous engine, stream-by-stream, and the overlap counters
    land in the stats summary."""
    s = _setup()
    eng = _engine(s["params"], s["mesh"])
    seng = StreamingEngine(eng, overlap=True)
    assert seng.overlap                  # packed + no injector: armed
    streams = {}
    for p in s["prompts"]:
        rid, stream = seng.submit_stream(p, max_new_tokens=6)
        streams[rid] = stream
    out = seng.run_sync()
    assert out == s["ref"]
    for rid, stream in streams.items():
        assert stream.drain() == s["ref"][rid]
        assert stream.finished == "length"
    summ = eng.stats.summary()
    assert summ["tokens_streamed"] == sum(
        len(v) for v in s["ref"].values())
    assert summ["packed_ticks"] > 0      # the overlapped path ran
    assert 0.0 <= summ["host_overhead_fraction"] < 1.0


def test_stream_overlap_off_matches():
    """overlap=False degrades to synchronous ticks with streaming
    delivery — same tokens, no in-flight pipeline ever builds."""
    s = _setup()
    eng = _engine(s["params"], s["mesh"])
    seng = StreamingEngine(eng, overlap=False)
    assert not seng.overlap
    streams = [seng.submit_stream(p, max_new_tokens=6)[1]
               for p in s["prompts"]]
    out = seng.run_sync()
    assert out == s["ref"]
    assert not seng._pipe
    for rid, stream in enumerate(streams):
        assert stream.drain() == s["ref"][rid]


def test_stream_async_frontend():
    """serve_stream: Poisson-style staggered arrivals consumed by real
    ``async for`` loops deliver the reference tokens and finish
    reasons."""
    s = _setup()
    eng = _engine(s["params"], s["mesh"])
    seng = StreamingEngine(eng, overlap=True)
    reqs = [dict(prompt=p, max_new_tokens=6, arrival=0.002 * i)
            for i, p in enumerate(s["prompts"])]
    got = asyncio.run(serve_stream(seng, reqs))
    assert {rid: g["tokens"] for rid, g in sorted(got.items())} == s["ref"]
    assert all(g["finished"] == "length" for g in got.values())
    # wall-clock delivery timestamps are monotone within a stream
    for g in got.values():
        assert g["times"] == sorted(g["times"])


def test_stream_cancel_mid_flight_zero_leak():
    """Cancelling a decoding request mid-pipeline drains in-flight
    ticks, frees its pages/slot (zero leaks), closes its stream with
    reason 'cancelled', and leaves every other stream token-identical.
    prefix_cache off so the page pool must return to exactly full."""
    s = _setup()
    eng = _engine(s["params"], s["mesh"], prefix_cache=False)
    seng = StreamingEngine(eng, overlap=True)
    rids, streams = [], {}
    for p in s["prompts"]:
        rid, stream = seng.submit_stream(p, max_new_tokens=6)
        rids.append(rid)
        streams[rid] = stream
    for _ in range(6):                   # some ticks in flight
        seng.step()
    victim = rids[0]
    assert seng.cancel(victim)
    assert not seng._pipe                # cancel drained the pipeline
    assert streams[victim].finished == "cancelled"
    out = seng.run_sync()
    assert victim not in out
    assert eng.failed()[victim] == "cancelled"
    for rid in rids[1:]:
        assert out[rid] == s["ref"][rid]
        assert streams[rid].drain() == s["ref"][rid]
    assert eng.stats.cancelled == 1
    # zero-leak audit: every page, state row, and slot back in its pool
    kv = eng.kv_cache
    kv.check()
    assert not kv.slot_pages and not kv.slot_state
    assert kv.table.free_pages == kv.paging.n_pages
    assert sorted(eng._sched.free_slots) == list(range(4))


def test_stream_forced_preemption_reconciles():
    """Double-buffer reconciliation under preemption: spill an active
    request to the host store while speculative rows are in flight.
    preempt() drains first, the spilled request restores through
    normal admission, and every stream still matches the reference —
    the epoch/identity staleness checks make the race unobservable."""
    s = _setup()
    eng = _engine(s["params"], s["mesh"], offload=True,
                  prefix_cache=False)
    seng = StreamingEngine(eng, overlap=True)
    rids, streams = [], {}
    for p in s["prompts"]:
        rid, stream = seng.submit_stream(p, max_new_tokens=6)
        rids.append(rid)
        streams[rid] = stream
    # run until something is decoding with ticks in flight
    for _ in range(32):
        seng.step()
        victim = next((st.req.rid for st in eng._sched.active.values()
                       if not st.prefilling), None)
        if victim is not None and seng._pipe:
            break
    assert victim is not None and seng._pipe
    assert seng.preempt(victim)
    assert not seng._pipe                # preempt drained first
    out = seng.run_sync()
    assert out == s["ref"]               # spill/restore changed nothing
    assert eng.stats.preemptions >= 1
    assert eng.stats.restore_hits >= 1
    for rid in rids:
        assert streams[rid].drain() == s["ref"][rid]
        assert streams[rid].finished == "length"
    # no in-flight bookkeeping left behind
    assert all(st.inflight == 0
               for st in eng._sched.active.values())
    assert len(eng.kv_store) == 0
