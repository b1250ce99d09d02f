"""Token-packed unified serving-step tests on the single real CPU
device (mesh 1x1; the sharded versions run via
tests/engine_equiv_runner.py):

* the packed program writes the SAME cache the chunk program writes
  (mixed slots, ragged offsets, dead tail entries);
* packed serving is token-identical to sequential serving and to the
  chunked oracle, including ragged token budgets (T_budget not a
  multiple of the live token count) and prompt lengths off the budget
  boundary;
* ADVERSARIAL cross-request isolation: two requests with IDENTICAL
  prompts packed into one tick must not leak softmax stats into each
  other — each must generate exactly what it generates alone;
* prism Segment-Means state written by packed prefill is pinned
  against the PR-4 UNPADDED monolithic reference (gz/vz/zsum);
* the engine's compiled-program cache keeps the number of traces
  bounded while ticks alternate packed <-> decode (jit-lowering
  counter), and the chunk path reports its real-vs-padded token split.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.protocol import PrismConfig
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.runtime.serve import (ServeHParams, init_cache,
                                 make_chunk_prefill_step,
                                 make_packed_step, make_prefill_step,
                                 trace_counts)
from repro.serving import ServingEngine
from repro.launch.mesh import make_mesh


TINY = ModelConfig(
    name="tiny-serve", arch_type="dense", n_layers=2, d_model=32,
    n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=61,
    mlp_kind="swiglu", norm_kind="rmsnorm", pos="rope",
    tie_embeddings=True)


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def _oracle(params, prompt, n_gen):
    seq = list(prompt)
    for _ in range(n_gen):
        logits, _ = T.forward(TINY, params, jnp.asarray([seq]), chunk=8)
        seq.append(int(np.argmax(np.asarray(logits[0, -1]))))
    return seq[len(prompt):]


def test_packed_program_writes_same_cache_as_chunk():
    """Driving the packed program with a flat mixed-slot token batch
    (ragged offsets, dead tail) lays down the same K/V the chunk
    program does."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    n0, cap, B, TB = 8, 16, 4, 7
    hp = ServeHParams(decode_mode="exact", ssm_chunk=8)
    rng = np.random.default_rng(0)
    p1 = rng.integers(1, TINY.vocab_size, size=n0)
    p3 = rng.integers(1, TINY.vocab_size, size=5)

    chunk, lay, _ = make_chunk_prefill_step(
        TINY, mesh, params, batch=B, cap=cap, prefill_len=n0,
        chunk_len=n0, hp=hp)
    ref = init_cache(TINY, lay, B, hp)
    toks = np.zeros((B, n0), np.int32)
    off = np.full(B, -1, np.int32)
    nreal = np.zeros(B, np.int32)
    toks[1], off[1], nreal[1] = p1, 0, n0
    toks[3, :5], off[3], nreal[3] = p3, 0, 5
    ref = chunk(params, ref, jnp.asarray(toks), jnp.asarray(off),
                jnp.asarray(nreal))

    packed, lp, _, _ = make_packed_step(
        TINY, mesh, params, batch=B, cap=cap, prefill_len=n0,
        token_budget=TB, hp=hp)
    assert lp == lay
    got = init_cache(TINY, lay, B, hp)
    # three ragged ticks: 7 + 5 + 1 tokens (last tick mostly dead)
    work = ([(1, i) for i in range(n0)] + [(3, i) for i in range(5)])
    offs = {1: 0, 3: 0}
    while work:
        take, work = work[:TB], work[TB:]
        tok = np.zeros(TB, np.int32)
        slot = np.full(TB, -1, np.int32)
        pos = np.full(TB, -1, np.int32)
        offv = np.full(TB, -1, np.int32)
        pre = np.zeros(TB, np.int32)
        starts = {}
        for i, (s, p) in enumerate(take):
            tok[i] = (p1 if s == 1 else p3)[p]
            slot[i], pos[i], pre[i] = s, p, 1
            starts.setdefault(s, p)
        for i, (s, p) in enumerate(take):
            offv[i] = starts[s]
        _, got = packed(params, got, jnp.asarray(tok), jnp.asarray(slot),
                        jnp.asarray(pos), jnp.asarray(offv),
                        jnp.asarray(pre))
    for u in range(2):
        for key in ("k", "v"):
            a = np.asarray(ref["scan"][0][key][u])
            b = np.asarray(got["scan"][0][key][u])
            assert np.abs(a[1, :n0] - b[1, :n0]).max() < 1e-5, (u, key)
            assert np.abs(a[3, :5] - b[3, :5]).max() < 1e-5, (u, key)


@pytest.mark.parametrize("mode", ["exact", "prism"])
def test_packed_matches_sequential_and_chunked(mode):
    """Concurrent packed serving == sequential serving == the chunked
    oracle, token for token, in both decode modes."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    hp = ServeHParams(decode_mode=mode, ssm_chunk=8, means_cr=4.0)
    kw = dict(n_slots=3, prefill_len=8, max_cache=24, hp=hp)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, TINY.vocab_size,
                            size=int(rng.integers(4, 9))).tolist()
               for _ in range(5)]

    def drive(engine):
        for p in prompts[:3]:
            engine.submit(p, max_new_tokens=6)
        for _ in range(3):
            engine.step()
        for p in prompts[3:]:
            engine.submit(p, max_new_tokens=6)
        return engine.run()

    packed = drive(ServingEngine(TINY, mesh, params, token_budget=7,
                                 **kw))
    chunked = drive(ServingEngine(TINY, mesh, params, chunk_len=4,
                                  prefill_mode="chunked", **kw))
    for i, p in enumerate(prompts):
        seq = ServingEngine(TINY, mesh, params, token_budget=7, **kw)
        rid = seq.submit(p, max_new_tokens=6)
        want = seq.run()[rid]
        assert packed[i] == want, (mode, i)
        assert packed[i] == chunked[i], (mode, i)


def test_packed_cross_request_isolation_identical_prompts():
    """ADVERSARIAL: two requests with IDENTICAL prompts admitted
    together land in the same packed tick; a stats leak between their
    (identical-content, different-slot) tokens would shift both away
    from the solo generation.  Both must match the solo run exactly —
    and so must a third, different, request sharing the ticks."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    kw = dict(n_slots=3, prefill_len=8, max_cache=24, token_budget=9)
    prompt = [7, 19, 3, 42, 11, 23]
    other = [5, 50, 2]

    eng = ServingEngine(TINY, mesh, params, **kw)
    r0 = eng.submit(prompt, max_new_tokens=6)
    r1 = eng.submit(prompt, max_new_tokens=6)
    r2 = eng.submit(other, max_new_tokens=6)
    got = eng.run()

    solo = ServingEngine(TINY, mesh, params, **kw)
    rid = solo.submit(prompt, max_new_tokens=6)
    want = solo.run()[rid]
    assert got[r0] == want
    assert got[r1] == want
    assert got[r2] == _oracle(params, other, 6)
    # all three prompts (6+6+3 = 15 tokens > budget 9) really were
    # packed concurrently
    assert eng.stats.packed_ticks >= 2
    assert eng.stats.packed_prefill_tokens == 15


def test_packed_ragged_budgets_match_oracle():
    """T_budget values that never divide the live token count (prompt
    lengths at/off the budget boundary, budget smaller than a prompt,
    mostly-dead final ticks) all match the teacher-forced oracle."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    for tb, plen in ((2, 5), (3, 3), (5, 8), (7, 4)):
        prompt = rng.integers(1, TINY.vocab_size, size=plen).tolist()
        eng = ServingEngine(TINY, mesh, params, n_slots=2,
                            prefill_len=8, max_cache=24,
                            token_budget=tb)
        rid = eng.submit(prompt, max_new_tokens=4)
        got = eng.run()[rid]
        assert got == _oracle(params, prompt, 4), (tb, plen)
        s = eng.stats.summary()
        # prefill spreads over ceil(plen / (tb - decodes)) ticks; with
        # nothing decoding the whole budget is prompt tokens
        assert s["packed_prefill_tokens"] == plen
        assert s["packed_ticks"] >= -(-plen // tb)


def test_packed_prism_means_pinned_against_unpadded_reference():
    """A short prompt whose prefill arrives PACKED (split across ragged
    ticks) produces the same Segment-Means state (gz / vz / zsum) as
    the PR-4 unpadded monolithic reference — real columns only, no pad
    contamination."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    n0, cap, plen = 8, 16, 6
    hp = ServeHParams(decode_mode="prism", ssm_chunk=8, means_cr=8.0)
    prompt = [7, 19, 3, 42, 11, 23]

    # paged=False: this test reads the DENSE cache leaves by slot row
    # (the paged prism engine keeps this state in the pooled state rows;
    # its token-level equivalence runs via tests/engine_equiv_runner.py)
    eng = ServingEngine(TINY, mesh, params, n_slots=2, prefill_len=n0,
                        max_cache=cap, hp=hp, token_budget=4,
                        paged=False)
    assert eng.layout.L == 1
    eng.submit(prompt, max_new_tokens=1)
    eng.run()
    assert eng.stats.packed_ticks >= 2   # 6 prompt tokens over budget 4
    cache = eng.kv_cache.storage

    prism = PrismConfig(P=1, cr=8.0, mode="voltage")
    pre, _, _, _ = make_prefill_step(TINY, mesh, params, prism,
                                     batch=1, n=plen, hp=hp)
    _, ref = pre(params, {"tokens": jnp.asarray(np.asarray(
        prompt, np.int32)[None])})

    for u in range(2):
        gz = np.asarray(cache["scan"][0]["gz"][u, 0])
        assert gz.tolist() == [float(plen)], gz   # real count, NOT n0
        for key in ("vz", "zsum"):
            a = np.asarray(ref["scan"][0][key][u, 0])
            b = np.asarray(cache["scan"][0][key][u, 0])
            scale = max(np.abs(a).max(), 1e-6)
            assert np.abs(a - b).max() / scale < 1e-5, (u, key)


def test_program_cache_bounds_traces():
    """Alternating packed <-> decode ticks reuse the cached compiled
    programs: each engine traces the packed program at most once and
    the decode program at most once for a whole staggered run (the
    jit-lowering counters in runtime.serve pin it)."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    eng = ServingEngine(TINY, mesh, params, n_slots=2, prefill_len=8,
                        max_cache=24, token_budget=4)
    before = dict(trace_counts)
    # staggered arrivals force packed ticks (admissions mid-decode)
    # interleaved with decode-only ticks
    eng.submit([1, 2, 3, 4, 5], max_new_tokens=6)
    for _ in range(4):
        eng.step()
    eng.submit([6, 7, 8], max_new_tokens=6)
    eng.run()
    s = eng.stats.summary()
    assert s["packed_ticks"] >= 2 and s["decode_steps"] >= 2
    delta = {k: trace_counts[k] - before.get(k, 0)
             for k in ("packed_step", "serve_step")}
    assert delta["packed_step"] <= 1, delta
    assert delta["serve_step"] <= 1, delta
    # and the program cache holds exactly the two programs, keyed by
    # (kind, token_budget)
    assert set(eng._programs) == {("decode", None), ("packed", 4)}


def test_packed_is_default_and_budget_validated():
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    eng = ServingEngine(TINY, mesh, params, n_slots=2, prefill_len=8,
                        max_cache=16)
    assert eng.prefill_mode == "packed"
    assert eng.token_budget == 2 + eng.chunk_len
    with pytest.raises(ValueError):
        ServingEngine(TINY, mesh, params, n_slots=2, prefill_len=8,
                      max_cache=16, token_budget=1)
    with pytest.raises(ValueError):
        ServingEngine(TINY, mesh, params, n_slots=2, prefill_len=8,
                      max_cache=16, prefill_mode="bogus")


def test_chunk_step_reports_real_vs_padded_tokens():
    """Satellite of the packing work: chunked mode now accounts the
    real-vs-padded split of every launched chunk program, so the
    1-real-row waste the FLOP model exposed is visible in
    EngineStats.summary()."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    eng = ServingEngine(TINY, mesh, params, n_slots=2, prefill_len=8,
                        max_cache=16, prefill_mode="chunked",
                        chunk_len=4)
    eng.submit([7, 19, 3, 42, 11], max_new_tokens=2)
    eng.run()
    s = eng.stats.summary()
    # one request, 5 prompt tokens over 2 chunk calls of a 2x4 program
    assert s["chunk_tokens_real"] == 5
    assert s["chunk_tokens_padded"] == 2 * 2 * 4 - 5
    # a tick with nothing mid-prefill never launches the chunk program
    assert eng._chunk_step() == "idle"
