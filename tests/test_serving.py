"""Serving-engine tests: scheduler policy, sampling, the slot-insert
round trip, and the 6-requests/4-slots continuous-batching equivalence
— all on the single real CPU device (mesh 1x1; the sharded version runs
via tests/engine_equiv_runner.py)."""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core.protocol import PrismConfig
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.runtime.serve import (ServeHParams, grow_cache, init_cache,
                                 insert_cache_row, make_prefill_step,
                                 make_serve_step, reset_cache_row)
from repro.serving import (FifoScheduler, Request, SamplingParams,
                           ServingEngine, sample_token)
from repro.launch.mesh import make_mesh


TINY = ModelConfig(
    name="tiny-serve", arch_type="dense", n_layers=2, d_model=32,
    n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=61,
    mlp_kind="swiglu", norm_kind="rmsnorm", pos="rope",
    tie_embeddings=True)


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def _req(rid, prompt=(1, 2, 3), **kw):
    kw.setdefault("max_new_tokens", 4)
    return Request(rid=rid, prompt=tuple(prompt), **kw)


# --------------------------------------------------------------------------
# scheduler
# --------------------------------------------------------------------------

def test_scheduler_fifo_admission_order():
    s = FifoScheduler(2)
    for i in range(3):
        s.submit(_req(i))
    assert s.want_prefill()                        # idle pool -> admit now
    states = s.admit(now=0.0)
    # FIFO into ascending slots
    assert [st.req.rid for st in states] == [0, 1]
    assert [st.slot for st in states] == [0, 1]
    assert not s.want_prefill()                    # queue yes, but no slot


def test_scheduler_interleave_ratio_protects_decode():
    s = FifoScheduler(4, decode_per_prefill=3)
    s.submit(_req(0))
    s.admit(now=0.0)
    s.submit(_req(1))
    # slots are free but a stream is decoding: hold the prefill until
    # `decode_per_prefill` decode steps have run
    assert not s.want_prefill()
    for _ in range(3):
        s.note_decode()
    assert s.want_prefill()


def test_scheduler_eviction_recycles_lowest_slot():
    s = FifoScheduler(3)
    for i in range(3):
        s.submit(_req(i))
    states = s.admit(now=0.0)
    s.evict(states[1], now=1.0)                    # free middle slot 1
    s.evict(states[0], now=1.0)                    # free slot 0
    assert s.free_slots == [0, 1]
    s.submit(_req(3))
    s.submit(_req(4))
    for _ in range(10):
        s.note_decode()
    new = s.admit(now=2.0)
    assert [st.slot for st in new] == [0, 1]       # lowest slot first
    assert states[1].t_finish == 1.0


def test_scheduler_chunk_interleave_fairness():
    """A long prompt mid-prefill can never starve decoding slots: while
    anything is decoding, chunks are granted at most once per
    ``decode_per_prefill`` decode steps — never back-to-back."""
    s = FifoScheduler(4, decode_per_prefill=3)
    s.submit(_req(0))
    s.admit(now=0.0)[0].begin_decode()             # a running stream
    s.submit(_req(1, prompt=tuple(range(1, 33))))  # long prompt
    assert s.want_admit()
    s.admit(now=0.0)
    assert s.prefilling() and s.decoding()
    grants = []
    for _ in range(12):                            # drive the policy
        if s.want_chunk():
            grants.append("chunk")
            s.note_chunk()
        else:
            grants.append("decode")
            s.note_decode()
    # never two chunks in a row, and >= decode_per_prefill decodes
    # between consecutive chunk grants
    last = None
    for i, g in enumerate(grants):
        if g == "chunk":
            if last is not None:
                assert i - last > 3, grants
            last = i
    assert grants.count("chunk") >= 2              # prefill does advance

    # nothing decoding -> chunks run back-to-back (TTFT is all that
    # matters for an otherwise-idle engine)
    s2 = FifoScheduler(2, decode_per_prefill=3)
    s2.submit(_req(0, prompt=tuple(range(1, 20))))
    s2.admit(now=0.0)
    assert s2.want_chunk()
    s2.note_chunk()
    assert s2.want_chunk()


def test_scheduler_want_admit_gang_vs_fifo():
    """Chunked admission is host-side and immediate in FIFO mode, but
    gang mode still only admits a full gang into an empty pool."""
    s = FifoScheduler(2)
    s.submit(_req(0))
    assert s.want_admit()                          # free slot + queue
    s.admit(now=0.0)[0].begin_decode()
    s.submit(_req(1))
    assert s.want_admit()                          # decode never blocks it

    g = FifoScheduler(2, gang=True)
    g.submit(_req(0))
    assert not g.want_admit()                      # waits for a full gang
    g.submit(_req(1))
    assert g.want_admit()
    states = g.admit(now=0.0)
    g.submit(_req(2))
    assert not g.want_admit()                      # pool busy
    g.evict(states[0], now=1.0)
    g.evict(states[1], now=1.0)
    g.drain = True
    assert g.want_admit()                          # drain-time remainder


def test_scheduler_gang_is_static_batching():
    s = FifoScheduler(2, gang=True)
    s.submit(_req(0))
    assert not s.want_prefill()                    # waits for a full gang
    s.submit(_req(1))
    s.submit(_req(2))
    assert s.want_prefill()
    states = s.admit(now=0.0)
    assert len(states) == 2
    s.submit(_req(3))
    assert not s.want_prefill()                    # pool busy: no admission
    s.evict(states[0], now=1.0)
    assert not s.want_prefill()                    # still draining
    s.evict(states[1], now=1.0)
    s.drain = True                                 # no more arrivals
    assert s.want_prefill()                        # flush the partial gang


# --------------------------------------------------------------------------
# sampling
# --------------------------------------------------------------------------

def test_sampling_greedy_temperature_topk():
    logits = np.array([0.1, 3.0, -1.0, 2.9, 0.0], np.float32)
    sp = SamplingParams()
    assert sample_token(logits, sp, sp.make_rng()) == 1

    # top-k=2 restricts support to the two largest logits
    sp = SamplingParams(temperature=5.0, top_k=2, seed=0)
    rng = sp.make_rng()
    draws = {sample_token(logits, sp, rng) for _ in range(64)}
    assert draws <= {1, 3} and len(draws) == 2

    # per-seed determinism
    sp = SamplingParams(temperature=1.0, seed=7)
    a = [sample_token(logits, sp, sp.make_rng()) for _ in range(1)]
    b = [sample_token(logits, sp, sp.make_rng()) for _ in range(1)]
    assert a == b


# --------------------------------------------------------------------------
# slot insert round trip
# --------------------------------------------------------------------------

def test_slot_insert_round_trip():
    """Prefill one request, insert its cache row into slot 2 of a 4-slot
    decode cache, and decode: the slot must match the plain batch=1
    serve path token for token."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    n0, cap, gen = 8, 16, 4
    hp = ServeHParams(decode_mode="exact", ssm_chunk=8)
    prism = PrismConfig(P=1, mode="voltage")
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, n0), 1,
                                TINY.vocab_size)

    # reference: batch=1 prefill + decode
    pre1, lp1, _, _ = make_prefill_step(TINY, mesh, params, prism,
                                        batch=1, n=n0, hp=hp)
    logits1, cache1 = pre1(params, {"tokens": prompt})
    step1, ld1, _, _ = make_serve_step(TINY, mesh, params, batch=1,
                                       cap=cap, prefill_len=n0, hp=hp)
    cache1 = grow_cache(cache1, lp1, ld1)

    # engine-style: batch=4 prefill (row 0 = the request), insert row 0
    # into slot 2 of a zeroed 4-slot cache
    pre4, lp4, _, _ = make_prefill_step(TINY, mesh, params, prism,
                                        batch=4, n=n0, hp=hp)
    junk = jax.random.randint(jax.random.PRNGKey(2), (4, n0), 1,
                              TINY.vocab_size)
    batch4 = jnp.concatenate([prompt, junk[1:]], axis=0)
    _, cache4 = pre4(params, {"tokens": batch4})
    step4, ld4, _, _ = make_serve_step(TINY, mesh, params, batch=4,
                                       cap=cap, prefill_len=n0, hp=hp)
    big = init_cache(TINY, ld4, 4, hp)
    big = insert_cache_row(big, grow_cache(cache4, lp4, ld4), 0, 2)

    tok = int(jnp.argmax(logits1[0]))
    for g in range(gen):
        pos1 = jnp.full((1,), n0 + g, jnp.int32)
        logits1, cache1 = step1(params, cache1,
                                jnp.full((1,), tok, jnp.int32), pos1)
        pos4 = jnp.asarray([-1, -1, n0 + g, -1], jnp.int32)
        tok4 = jnp.asarray([0, 0, tok, 0], jnp.int32)
        logits4, big = step4(params, big, tok4, pos4)
        got, ref = np.asarray(logits4[2]), np.asarray(logits1[0])
        err = np.abs(got - ref).max() / max(1e-6, np.abs(ref).max())
        assert err < 1e-5, (g, err)
        tok = int(np.argmax(ref))

    # reset_cache_row zeroes exactly the one batch row
    assert np.asarray(big["scan"][0]["k"][:, 2]).any()
    wiped = reset_cache_row(big, 2)
    leaf = np.asarray(wiped["scan"][0]["k"])        # (n_units, B, cap, H, hd)
    assert not leaf[:, 2].any()


# --------------------------------------------------------------------------
# engine end-to-end
# --------------------------------------------------------------------------

def _engine(params, mesh, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("prefill_len", 8)
    kw.setdefault("max_cache", 24)
    return ServingEngine(TINY, mesh, params, **kw)


def test_engine_six_staggered_requests_match_sequential():
    """6 requests through a 4-slot engine — the last two admitted
    mid-flight into evicted slots — terminate with exactly the tokens
    sequential (one-at-a-time) serving produces."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, TINY.vocab_size,
                            size=int(rng.integers(3, 9))).tolist()
               for _ in range(6)]

    eng = _engine(params, mesh)
    for p in prompts[:4]:
        eng.submit(p, max_new_tokens=6)
    for _ in range(3):                     # stagger: decode before arrivals
        eng.step()
    for p in prompts[4:]:
        eng.submit(p, max_new_tokens=6)
    concurrent = eng.run()
    assert eng.stats.completed == 6
    # late arrivals joined mid-flight: their prompt tokens packed into
    # ticks beyond the opening burst (default mode is 'packed')
    assert eng.stats.packed_ticks >= 2
    assert eng.stats.packed_prefill_tokens == sum(len(p) for p in prompts)

    seq_eng = _engine(params, mesh)
    for i, p in enumerate(prompts):
        rid = seq_eng.submit(p, max_new_tokens=6)
        out = seq_eng.run()[rid]
        assert concurrent[i] == out, (i, concurrent[i], out)

    stats = eng.stats.summary()
    assert stats["requests"] == 6
    assert 0.0 < stats["occupancy"] <= 1.0
    assert len(eng.stats.ttft) == 6


def test_engine_short_prompt_matches_full_forward():
    """Ground truth independent of the engine: a SHORT prompt (< the
    pad length) decoded greedily through the engine must match a
    teacher-forced T.forward loop — pins the pad+rewind admission
    against an oracle that shares none of its code."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    prompt = [7, 19, 3, 42, 11]                    # 5 < prefill_len = 8
    gen = 5

    eng = _engine(params, mesh)
    rid = eng.submit(prompt, max_new_tokens=gen)
    got = eng.run()[rid]

    seq = list(prompt)
    for _ in range(gen):
        logits, _ = T.forward(TINY, params, jnp.asarray([seq]), chunk=8)
        seq.append(int(np.argmax(np.asarray(logits[0, -1]))))
    assert got == seq[len(prompt):], (got, seq[len(prompt):])


def test_engine_eos_and_max_tokens_evict():
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    eng = _engine(params, mesh, n_slots=2)
    rid0 = eng.submit([5, 6, 7], max_new_tokens=4)
    out0 = eng.run()[rid0]
    assert len(out0) == 4                  # max-tokens eviction

    # use the first generated token as EOS: the request must stop at 1
    eng2 = _engine(params, mesh, n_slots=2)
    rid1 = eng2.submit([5, 6, 7], max_new_tokens=4, eos_id=out0[0])
    out1 = eng2.run()[rid1]
    assert out1 == [out0[0]]


def test_engine_eviction_mid_prefill():
    """A decoding request finishes and is evicted WHILE another request
    is mid-prefill; a third request is admitted into the freed slot and
    its chunks interleave — everything still matches sequential
    serving."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    pa = rng.integers(1, TINY.vocab_size, size=3).tolist()
    pb = rng.integers(1, TINY.vocab_size, size=8).tolist()   # 4 chunks
    pc = rng.integers(1, TINY.vocab_size, size=5).tolist()

    kw = dict(n_slots=2, prefill_len=8, max_cache=24, chunk_len=2,
              decode_per_prefill=1)
    eng = _engine(params, mesh, **kw)
    ra = eng.submit(pa, max_new_tokens=2)
    while not eng._sched.decoding():               # finish A's prefill
        eng.step()
    rb = eng.submit(pb, max_new_tokens=4)
    saw_mid_prefill_evict = False
    while eng._sched.has_work:
        eng.step()
        if (ra in eng._results and eng._sched.prefilling()):
            saw_mid_prefill_evict = True
            break
    assert saw_mid_prefill_evict                   # A gone, B mid-prefill
    rc = eng.submit(pc, max_new_tokens=3)
    out = eng.run()
    assert set(out) == {ra, rb, rc}
    # slot reuse: C landed in A's freed slot
    assert eng._results[rc].slot == eng._results[ra].slot

    seq = _engine(params, mesh, **kw)
    for rid, p, g in ((ra, pa, 2), (rb, pb, 4), (rc, pc, 3)):
        srid = seq.submit(p, max_new_tokens=g)
        assert seq.run()[srid] == out[rid], rid


def test_engine_rejects_recurrent_and_ring_archs():
    """The padded-prefill + rewind admission scheme is only sound for
    position-addressed global attention caches — SSM state consumes pad
    tokens and the ring window cache holds the padded tail."""
    ssm = ModelConfig(
        name="tiny-xlstm", arch_type="ssm", n_layers=2, d_model=32,
        n_heads=2, n_kv_heads=2, d_ff=0, vocab_size=61,
        blocks=("mlstm", "slstm"), norm_kind="rmsnorm", pos="none",
        ssm_heads=2, tie_embeddings=False)
    mesh = _mesh()
    params = T.init(ssm, jax.random.PRNGKey(0))
    try:
        ServingEngine(ssm, mesh, params, n_slots=2, prefill_len=8,
                      max_cache=16)
        raise AssertionError("SSM arch must be rejected")
    except ValueError as e:
        assert "mlstm" in str(e)


def test_engine_rejects_embed_frontends():
    """vlm/audio configs need 'embeds' prefill inputs the token-only
    admission path never builds — reject at construction, not with a
    pytree mismatch at the first flush.  (The guard runs before params
    are touched, so none are needed.)"""
    vlm = ModelConfig(
        name="tiny-vlm", arch_type="vlm", n_layers=2, d_model=32,
        n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=61,
        norm_kind="rmsnorm", pos="rope", prefix_len=4)
    try:
        ServingEngine(vlm, _mesh(), None, n_slots=2, prefill_len=8,
                      max_cache=16)
        raise AssertionError("vlm arch must be rejected")
    except ValueError as e:
        assert "embedding inputs" in str(e)


def test_engine_run_with_logical_clock_terminates():
    """run() must finish under an injected non-wall clock: future
    arrivals fast-forward instead of spinning on time.sleep."""

    class Frozen:
        t = 0.0

        def __call__(self):
            return self.t

    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    eng = _engine(params, mesh, n_slots=2, clock=Frozen())
    rid = eng.submit([4, 5, 6], max_new_tokens=3, arrival=7.5)
    out = eng.run()
    assert len(out[rid]) == 3
    # the clock was fast-forwarded past the arrival, not slept through
    assert eng.now() >= 7.5


def test_engine_rejects_oversized_requests():
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    eng = _engine(params, mesh)
    try:
        eng.submit(list(range(1, 12)), max_new_tokens=2)
        raise AssertionError("prompt > prefill_len must be rejected")
    except ValueError:
        pass
    try:
        eng.submit([1, 2, 3], max_new_tokens=1000)
        raise AssertionError("prompt+gen > cache cap must be rejected")
    except ValueError:
        pass
