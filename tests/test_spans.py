"""The serving loop's host spans, phase counters, named step programs
and per-request times (single CPU device, mesh 1x1):

  * under a profiler session the loop's spans nest inside one
    ``serve.step`` per iteration, in the loop's order, and name the
    ``EngineStats.phase_s`` counters;
  * the phase counters never exceed the loop's own wall time;
  * without a profiler session the spans record nothing;
  * every step program lowers under its own module name;
  * arrival <= admission <= first dispatch <= first token, per request;
  * the streamed inter-token gaps are one bounded sample and per-request
    delivery state is freed when a stream finishes.
"""
import glob
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving import ServingEngine, StreamingEngine
from repro.serving.scheduler import STATS_WINDOW

TINY = ModelConfig(
    name="tiny-spans", arch_type="dense", n_layers=2, d_model=32,
    n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=61,
    mlp_kind="swiglu", norm_kind="rmsnorm", pos="rope",
    tie_embeddings=True)

#: the loop's phases in the order one iteration runs them
ORDER = ("serve.admit", "serve.plan", "serve.launch", "serve.wait",
         "serve.reconcile", "serve.flush")

_CACHE: dict = {}


def _setup():
    if not _CACHE:
        rng = np.random.default_rng(0)
        _CACHE.update(
            mesh=make_mesh((1, 1), ("data", "model")),
            params=T.init(TINY, jax.random.PRNGKey(0)),
            prompts=[rng.integers(1, TINY.vocab_size,
                                  size=int(rng.integers(3, 9))).tolist()
                     for _ in range(5)])
    return _CACHE


def _engine(**kw):
    s = _setup()
    kw.setdefault("n_slots", 4)
    kw.setdefault("prefill_len", 8)
    kw.setdefault("max_cache", 24)
    return ServingEngine(TINY, s["mesh"], s["params"], **kw)


def _submit(seng, n=5):
    return [seng.submit_stream(p, max_new_tokens=4)[1]
            for p in _setup()["prompts"][:n]]


def _host_events(trace_dir) -> list:
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    pd = ProfileData.from_file(path)
    return sorted((e.start_ns, e.end_ns, e.name.split("#")[0])
                  for pl in pd.planes if pl.name.startswith("/host:")
                  for ln in pl.lines for e in ln.events)


def test_spans_nest_in_loop_order(tmp_path):
    eng = _engine()
    seng = StreamingEngine(eng)
    _submit(seng, 2)
    seng.run_sync()                      # compile outside the profile
    _submit(seng)
    jax.profiler.start_trace(str(tmp_path))
    try:
        seng.run_sync()
    finally:
        jax.profiler.stop_trace()
    evs = [e for e in _host_events(tmp_path) if e[2].startswith("serve.")]
    steps = [e for e in evs if e[2] == "serve.step"]
    phases = [e for e in evs if e[2] != "serve.step"]
    assert steps and {e[2] for e in phases} == set(ORDER)
    assert set(eng.stats.phase_s) == set(ORDER)
    for a, b, name in phases:            # each inside one iteration
        assert sum(s0 <= a and b <= s1 for s0, s1, _ in steps) == 1, name
    for s0, s1, _ in steps:
        inner = [e for e in phases if s0 <= e[0] and e[1] <= s1]
        for (a0, a1, _), (b0, _, _) in zip(inner, inner[1:]):
            assert a1 <= b0              # siblings, never overlapping
        ranks = [ORDER.index(n) for _, _, n in inner]
        assert ranks == sorted(ranks)    # admit .. flush, in that order


def test_phase_totals_within_loop_wall():
    eng = _engine()
    seng = StreamingEngine(eng)
    _submit(seng)
    idle = 0.0
    while seng.has_work:
        t0 = time.perf_counter()
        kind = seng.step()
        if kind == "idle":
            idle += time.perf_counter() - t0
    ph = eng.stats.phase_s
    assert ph["serve.launch"] > 0 and ph["serve.wait"] >= 0
    assert sum(ph.values()) <= eng.stats.loop_wall_s + idle
    assert eng.stats.summary()["phase_s"] == dict(sorted(ph.items()))


def test_spans_record_nothing_without_profiler(tmp_path):
    eng = _engine()
    seng = StreamingEngine(eng)
    _submit(seng)
    seng.run_sync()                      # no profiler session
    assert eng.stats.phase_s             # the counters are always on
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("marker"):
            pass
    finally:
        jax.profiler.stop_trace()
    names = {e[2] for e in _host_events(tmp_path)}
    assert "marker" in names
    assert not {n for n in names if n.startswith("serve.")}


def _vec(n):
    return jnp.zeros((n,), jnp.int32)


@pytest.mark.parametrize("kind,mode,name", [
    ("packed", "packed", "jit_packed_step"),
    ("decode", "packed", "jit_decode_step"),
    ("chunk", "chunked", "jit_chunk_prefill_step"),
    ("padded_prefill", "padded", "jit_prefill_step")])
def test_step_program_names(kind, mode, name):
    eng = _engine(prefill_mode=mode, paged=(mode != "chunked"))
    prog, b = eng._program(kind, eng.token_budget if kind == "packed"
                           else None), eng.n_slots
    if kind == "packed":
        tb = eng.token_budget
        args = [_vec(tb)] * 5 + list(eng._maps())
    elif kind == "decode":
        args = [_vec(b), _vec(b)] + list(eng._maps())
    elif kind == "chunk":
        args = [jnp.zeros((b, eng.chunk_len), jnp.int32), _vec(b), _vec(b)]
    else:
        text = prog.lower(eng.params, {"tokens": jnp.zeros(
            (b, eng.prefill_len), jnp.int32)}).as_text()
        assert f"module @{name}" in text
        return
    text = prog.lower(eng.params, eng._kv.storage, *args).as_text()
    assert f"module @{name}" in text
    assert "body_core" not in text


@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "sync"])
def test_request_times_ordered(overlap):
    eng = _engine()
    seng = StreamingEngine(eng, overlap=overlap)
    streams = _submit(seng)
    seng.run_sync()
    stats = eng.request_stats()
    assert sorted(stats) == [s.rid for s in streams]
    for s in streams:
        r = stats[s.rid]
        assert r["arrival"] <= r["t_admit"] <= r["t_first_dispatch"]
        assert r["t_first_dispatch"] <= r["arrival"] + r["ttft_s"]
        assert s.state is not None
        assert s.state.t_first_dispatch == r["t_first_dispatch"]


def test_itl_sample_is_bounded_and_freed():
    eng = _engine()
    seng = StreamingEngine(eng)
    streams = _submit(seng)
    out = seng.run_sync()
    gaps = seng.itl_samples()
    # one gap between each pair of consecutive tokens of a request
    assert len(gaps) == sum(len(t) for t in out.values()) - len(streams)
    assert all(g >= 0.0 for g in gaps)
    assert eng.stats.itl.maxlen == STATS_WINDOW
    assert not seng._last_token and not seng._streams
