"""Fault-tolerant serving tests (PR 8).

Four layers, mirroring the subsystem's split:

  * ``FaultSpec`` / ``FaultPlan`` / ``FaultInjector`` units: validation,
    per-kind stream independence, deterministic replay, scheduled
    ``at`` hits never shifting later Bernoulli decisions;
  * ``KVStore`` seams: an injected ``store_put_loss`` drops the put, an
    injected ``store_get_loss`` loses an existing entry at read time —
    both with exact byte accounting;
  * engine end-to-end on the 1x1 mesh: per-request deadlines cancel
    cleanly from every lifecycle state, a poisoned cache page
    quarantines exactly its own slot (neighbour tokens untouched),
    repeated lost restores re-prefill deterministically and
    ``max_restarts`` fails hard with everything reclaimed, and a full
    all-kinds chaos run stays token-identical to the clean run;
  * crash-consistent ``snapshot()`` / ``restore()``: a mid-flight
    engine journalled, torn down, and rebuilt resumes token-identically
    (the sharded 2x4 exact+prism version runs in
    ``tests/engine_equiv_runner.py``).
"""
import numpy as np
import jax
import pytest

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.runtime.faults import (KINDS, FaultInjector, FaultPlan,
                                  FaultSpec)
from repro.runtime.offload import KVStore
from repro.serving import SamplingParams, ServingEngine
from repro.launch.mesh import make_mesh


TINY = ModelConfig(
    name="tiny-serve", arch_type="dense", n_layers=2, d_model=32,
    n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=61,
    mlp_kind="swiglu", norm_kind="rmsnorm", pos="rope",
    tie_embeddings=True)


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def _engine(params, mesh, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("prefill_len", 8)
    kw.setdefault("max_cache", 24)
    kw.setdefault("prefix_cache", False)
    return ServingEngine(TINY, mesh, params, **kw)


def _submit_mix(eng, n=4, gen=6, **kw):
    rng = np.random.default_rng(3)
    for i in range(n):
        plen = int(rng.integers(3, 9))
        prompt = rng.integers(1, TINY.vocab_size, size=plen)
        eng.submit(prompt, max_new_tokens=gen,
                   sampling=SamplingParams(seed=i), **kw)


class _Clock:
    """Injectable logical clock: deadlines in these tests are measured
    in plain step units, not wall seconds."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# --------------------------------------------------------------------------
# FaultSpec / FaultPlan / FaultInjector units
# --------------------------------------------------------------------------

def test_fault_spec_validation():
    with pytest.raises(ValueError, match="probability"):
        FaultSpec(p=1.5)
    with pytest.raises(ValueError, match="probability"):
        FaultSpec(p=-0.1)
    assert FaultSpec().enabled is False
    assert FaultSpec(p=0.5).enabled and FaultSpec(at=(3,)).enabled
    assert FaultSpec(at=[1.0, 2]).at == (1, 2)   # coerced to int tuple


def test_fault_spec_shard_validation():
    with pytest.raises(ValueError, match="shard"):
        FaultSpec(shard=-1)
    assert FaultSpec(shard=2.0).shard == 2       # coerced to int
    assert FaultSpec().shard is None
    assert FaultSpec(at=(1,), shard=1).enabled


def test_fault_plan_lookup_and_chaos():
    plan = FaultPlan()
    assert not plan.any_enabled
    for kind in KINDS:
        assert plan.spec(kind) == FaultSpec()
    with pytest.raises(KeyError, match="unknown fault kind"):
        plan.spec("cosmic_ray")
    chaos = FaultPlan.chaos(7)
    assert chaos.seed == 7 and chaos.any_enabled
    assert all(chaos.spec(k).enabled for k in KINDS)
    # overrides replace the per-kind default
    quiet = FaultPlan.chaos(7, page_poison=FaultSpec())
    assert not quiet.spec("page_poison").enabled
    assert quiet.spec("tick_delay").enabled


def test_injector_deterministic_replay():
    plan = FaultPlan.chaos(42)
    a, b = FaultInjector(plan), FaultInjector(plan)
    for _ in range(300):
        for kind in KINDS:
            assert a.fire(kind) == b.fire(kind)
    assert a.injected == b.injected and a.ops == b.ops
    assert a.total_injected > 0
    assert a.stats()["seed"] == 42
    # a different seed gives a different schedule
    c, d = FaultInjector(FaultPlan.chaos(43)), FaultInjector(plan)
    seq_c = [c.fire("store_put_loss") for _ in range(200)]
    seq_d = [d.fire("store_put_loss") for _ in range(200)]
    assert seq_c != seq_d


def test_injector_streams_are_per_kind_independent():
    """Enabling / drawing one kind never perturbs another kind's
    schedule: the tick_delay decisions must be identical whether or not
    store_put_loss draws in between."""
    only_delay = FaultPlan(seed=9, tick_delay=FaultSpec(p=0.5))
    both = FaultPlan(seed=9, tick_delay=FaultSpec(p=0.5),
                     store_put_loss=FaultSpec(p=0.5))
    a, b = FaultInjector(only_delay), FaultInjector(both)
    for _ in range(200):
        b.fire("store_put_loss")         # interleaved draws on b only
        assert a.fire("tick_delay") == b.fire("tick_delay")


def test_injector_at_schedule_exact_and_stream_stable():
    plan = FaultPlan(seed=0, tick_delay=FaultSpec(at=(2, 5)))
    inj = FaultInjector(plan)
    fired = [inj.fire("tick_delay") for _ in range(8)]
    assert fired == [i in (2, 5) for i in range(8)]
    assert inj.injected["tick_delay"] == 2 and inj.ops["tick_delay"] == 8
    # a scheduled hit must not shift later Bernoulli decisions: with
    # p > 0 the stream draws on EVERY op, so the only index where the
    # two plans may differ is the scheduled one
    p_only = FaultInjector(FaultPlan(seed=1,
                                     tick_delay=FaultSpec(p=0.4)))
    p_and_at = FaultInjector(FaultPlan(seed=1,
                                       tick_delay=FaultSpec(p=0.4,
                                                            at=(3,))))
    for i in range(100):
        a, b = p_only.fire("tick_delay"), p_and_at.fire("tick_delay")
        if i == 3:
            assert b
        else:
            assert a == b


def test_injector_pick_deterministic():
    a = FaultInjector(FaultPlan.chaos(5))
    b = FaultInjector(FaultPlan.chaos(5))
    picks = [(a.pick("page_poison", 7), b.pick("page_poison", 7))
             for _ in range(100)]
    assert all(x == y for x, y in picks)
    assert all(0 <= x < 7 for x, _ in picks)


# --------------------------------------------------------------------------
# KVStore fault seams
# --------------------------------------------------------------------------

def test_store_put_loss_drops_the_put():
    inj = FaultInjector(FaultPlan(
        seed=0, store_put_loss=FaultSpec(at=(0,))))
    s = KVStore(injector=inj)
    assert not s.put("a", 3, None)           # injected loss
    assert "a" not in s and s.drops == 1 and s.bytes_used == 0
    assert s.put("b", 2, None)               # next op unaffected
    assert "b" in s and s.bytes_used == 2


def test_store_get_loss_tears_peek_and_pop():
    inj = FaultInjector(FaultPlan(
        seed=0, store_get_loss=FaultSpec(at=(0, 1))))
    s = KVStore(injector=inj)
    assert s.put("a", 3, None) and s.put("b", 2, None)
    assert s.peek("a") is None               # op 0: torn at read time
    assert "a" not in s and s.misses == 1
    assert s.pop("b") is None                # op 1: lost in flight
    assert "b" not in s and s.bytes_used == 0 and s.misses == 2
    assert s.put("c", 1, None)
    assert s.peek("c") is not None           # op 2: unscheduled, intact
    assert s.pop("c").n_pages == 1 and s.hits == 1


def test_store_get_bounded_retry_then_drop():
    """``KVStore.get`` retries a transient torn read (entry RETAINED
    across non-final losses) and only drops the entry when the final
    attempt loses too — the seam the restore path's bounded
    retry-with-backoff rides before downgrading to re-prefill."""
    inj = FaultInjector(FaultPlan(
        seed=0, store_get_loss=FaultSpec(at=(0,))))
    s = KVStore(injector=inj)
    assert s.put("a", 3, None)
    ent = s.get("a", retries=2)              # op 0 torn -> op 1 clean
    assert ent is not None and ent.n_pages == 3
    assert "a" in s and s.get_retries == 1
    assert s.stats()["get_retries"] == 1
    ent = s.get("a", retries=0, consume=True)   # op 2: clean pop
    assert ent is not None and "a" not in s and s.hits == 1

    # every attempt torn: the final loss keeps the old drop semantics
    inj2 = FaultInjector(FaultPlan(
        seed=0, store_get_loss=FaultSpec(at=(0, 1, 2))))
    s2 = KVStore(injector=inj2)
    assert s2.put("b", 2, None)
    assert s2.get("b", retries=2) is None
    assert "b" not in s2 and s2.bytes_used == 0
    assert s2.get_retries == 2 and s2.misses == 1

    # retries=0 is exactly the one-draw torn read
    inj3 = FaultInjector(FaultPlan(
        seed=0, store_get_loss=FaultSpec(at=(0,))))
    s3 = KVStore(injector=inj3)
    assert s3.put("c", 1, None)
    assert s3.get("c") is None and "c" not in s3
    assert s3.get_retries == 0


# --------------------------------------------------------------------------
# per-request deadlines
# --------------------------------------------------------------------------

def test_deadline_validation():
    params = T.init(TINY, jax.random.PRNGKey(0))
    eng = _engine(params, _mesh())
    with pytest.raises(ValueError, match="deadline"):
        eng.submit((1, 2, 3), max_new_tokens=2, arrival=5.0, deadline=5.0)
    with pytest.raises(ValueError, match="deadline"):
        eng.submit((1, 2, 3), max_new_tokens=2, arrival=5.0, deadline=1.0)


def test_deadline_generous_never_fires():
    clk = _Clock()
    params = T.init(TINY, jax.random.PRNGKey(0))
    eng = _engine(params, _mesh(), clock=clk)
    _submit_mix(eng, n=2, deadline=1e9)
    for _ in range(200):
        clk.t += 1.0
        if eng.step() == "idle" and not eng._sched.has_work:
            break
    assert eng.stats.completed == 2 and eng.stats.deadline_miss == 0
    assert not eng.failed()


def test_deadline_expiry_across_lifecycle_states():
    """One engine, four doomed requests in four different states when
    the clock passes their deadline — active (decoding), spilled on the
    resume queue, queued fresh, and suspended.  Every cancellation
    reclaims exactly what that state holds: pages + state row + slot,
    store bytes, or just the queue position."""
    clk = _Clock()
    params = T.init(TINY, jax.random.PRNGKey(0))
    eng = _engine(params, _mesh(), offload=True, n_slots=3, clock=clk)
    rng = np.random.default_rng(0)
    p = lambda n: rng.integers(1, TINY.vocab_size, size=n)
    a = eng.submit(p(6), max_new_tokens=8, deadline=500.0, priority=1)
    b = eng.submit(p(6), max_new_tokens=8, deadline=500.0)
    d = eng.submit(p(5), max_new_tokens=8, deadline=500.0)
    for _ in range(100):
        clk.t += 1.0
        eng.step()
        sts = [eng._find_active(r) for r in (a, b, d)]
        if all(st is not None and st.generated for st in sts):
            break
    else:
        raise AssertionError("never reached steady decode")
    assert eng.preempt(b)                       # b: spilled, resume-parked
    assert eng.suspend(d)                       # d: suspended
    c = eng.submit(p(4), max_new_tokens=4, deadline=500.0)   # c: queued
    assert b in eng.kv_store and d in eng.kv_store

    clk.t = 500.0                               # every deadline passes
    assert eng.step() == "idle"
    assert eng.stats.deadline_miss == 4
    assert eng.stats.deadline_miss_by_class == {0: 3, 1: 1}
    assert eng.failed() == {r: "deadline" for r in (a, b, c, d)}
    assert not eng._sched.has_work and not eng._suspended
    # zero leak: pages, state rows, store bytes, slots all reclaimed
    kv = eng.kv_cache
    kv.check()
    assert not kv.slot_pages and not kv.slot_state
    assert kv.table.free_pages == kv.paging.n_pages
    assert len(eng.kv_store) == 0 and eng.kv_store.bytes_used == 0
    assert sorted(eng._sched.free_slots) == [0, 1, 2]
    assert eng.run() == {}                      # nothing left to serve
    assert eng.stats.deadline_miss_by_class == {0: 3, 1: 1}
    s = eng.stats.summary()
    assert s["deadline_miss"] == 4
    assert s["deadline_miss_by_class"] == {"0": 3, "1": 1}


def test_deadline_mixed_with_survivors():
    """A doomed request expiring mid-decode never perturbs the tokens
    of a surviving neighbour."""
    clk = _Clock()
    params = T.init(TINY, jax.random.PRNGKey(0))
    mesh = _mesh()
    oracle = _engine(params, mesh, clock=_Clock())
    oracle.submit(tuple(range(1, 7)), max_new_tokens=6,
                  sampling=SamplingParams(seed=1))
    want = oracle.run()[0]

    eng = _engine(params, mesh, clock=clk)
    doomed = eng.submit(tuple(range(2, 8)), max_new_tokens=18,
                        deadline=4.0, sampling=SamplingParams(seed=0))
    keep = eng.submit(tuple(range(1, 7)), max_new_tokens=6,
                      sampling=SamplingParams(seed=1))
    for _ in range(100):
        clk.t += 1.0
        if eng.step() == "idle" and not eng._sched.has_work:
            break
    out = eng.results()
    assert doomed not in out
    assert out[keep] == want
    assert eng.failed() == {doomed: "deadline"}
    assert eng.stats.deadline_miss == 1


# --------------------------------------------------------------------------
# NaN/inf guard + quarantine
# --------------------------------------------------------------------------

def test_poisoned_page_quarantines_only_that_slot():
    """NaN-poison one request's private cache page mid-decode: the
    isfinite guard must quarantine exactly that slot (re-prefill in
    place, seeded RNG re-armed) and the neighbour must finish with
    tokens UNTOUCHED — both end token-identical to the clean oracle."""
    params = T.init(TINY, jax.random.PRNGKey(0))
    mesh = _mesh()
    oracle = _engine(params, mesh)
    _submit_mix(oracle, n=2)
    want = oracle.run()

    eng = _engine(params, mesh)
    _submit_mix(eng, n=2)
    poisoned = False
    for _ in range(400):
        if not eng._sched.has_work and not eng._pending:
            break
        st = eng._find_active(0)
        if (not poisoned and st is not None and not st.prefilling
                and len(st.generated) >= 2 and not st.finished()):
            kv = eng.kv_cache
            kv.poison_page(kv.slot_pages[st.slot][0])
            poisoned = True
        eng.step()
    assert poisoned
    assert eng.stats.quarantined == 1 and eng.stats.restarts == 1
    assert eng.results() == want          # rid 0 reran, rid 1 untouched
    assert eng._results[0].restarts == 1
    assert eng._results[1].restarts == 0
    assert not eng.failed()
    kv = eng.kv_cache
    kv.check()
    assert kv.table.free_pages == kv.paging.n_pages


def test_quarantine_max_restarts_fails_hard():
    """A slot that keeps producing NaNs exhausts ``max_restarts`` and
    fails hard: pages scrubbed + reclaimed, the request lands in
    ``failed()``, and the neighbour still matches the oracle."""
    params = T.init(TINY, jax.random.PRNGKey(0))
    mesh = _mesh()
    oracle = _engine(params, mesh)
    _submit_mix(oracle, n=2)
    want = oracle.run()

    eng = _engine(params, mesh, max_restarts=1)
    _submit_mix(eng, n=2)
    for _ in range(400):
        if not eng._sched.has_work and not eng._pending:
            break
        st = eng._find_active(0)
        if (st is not None and not st.prefilling
                and not st.finished()):
            kv = eng.kv_cache
            kv.poison_page(kv.slot_pages[st.slot][0])   # every decode tick
        eng.step()
    assert eng.failed() == {0: "max_restarts"}
    assert eng.stats.quarantined == 2          # one reset + one fail-hard
    assert eng.stats.restarts == 1
    assert eng.stats.failed_requests == 1
    out = eng.results()
    assert 0 not in out and out[1] == want[1]
    kv = eng.kv_cache
    kv.check()
    assert kv.table.free_pages == kv.paging.n_pages
    assert not kv.slot_pages and not kv.slot_state


# --------------------------------------------------------------------------
# repeated lost restores (satellite: reset_for_refill under restarts)
# --------------------------------------------------------------------------

def test_three_lost_restores_still_emit_oracle_tokens():
    """Three consecutive lost restores (zero-capacity store) re-seed
    the sampler RNG deterministically each time and the request still
    finishes with EXACTLY the oracle's tokens (default max_restarts=3
    permits all three resets)."""
    params = T.init(TINY, jax.random.PRNGKey(0))
    mesh = _mesh()
    oracle = _engine(params, mesh)
    _submit_mix(oracle, n=2)
    want = oracle.run()

    eng = _engine(params, mesh, offload=True)
    eng._store = KVStore(capacity_bytes=0)       # every spill is lost
    _submit_mix(eng, n=2)
    times = 0
    for _ in range(600):
        if not eng._sched.has_work and not eng._pending:
            break
        st = eng._find_active(0)
        if (times < 3 and st is not None and not st.prefilling
                and len(st.generated) >= 1 and not st.finished()):
            assert eng.preempt(0)
            times += 1
        eng.step()
    assert times == 3
    assert eng.results() == want
    assert eng.stats.restore_misses == 3 and eng.stats.restore_hits == 0
    assert eng.stats.restarts == 3
    assert eng._results[0].restarts == 3
    assert not eng.failed()
    assert eng.kv_cache.table.free_pages == eng.kv_cache.paging.n_pages


def test_max_restarts_exceeded_fails_cleanly():
    """With max_restarts=2 the third lost restore gives up: the request
    fails (never hangs, never blocks the admission queue), its pages
    and store bytes are reclaimed, and the neighbour is untouched."""
    params = T.init(TINY, jax.random.PRNGKey(0))
    mesh = _mesh()
    oracle = _engine(params, mesh)
    _submit_mix(oracle, n=2)
    want = oracle.run()

    eng = _engine(params, mesh, offload=True, max_restarts=2)
    eng._store = KVStore(capacity_bytes=0)
    _submit_mix(eng, n=2)
    times = 0
    for _ in range(600):
        if not eng._sched.has_work and not eng._pending:
            break
        st = eng._find_active(0)
        if (times < 3 and st is not None and not st.prefilling
                and len(st.generated) >= 1 and not st.finished()):
            assert eng.preempt(0)
            times += 1
        eng.step()
    assert times == 3
    assert eng.failed() == {0: "max_restarts"}
    assert eng.stats.failed_requests == 1
    assert eng.stats.restarts == 2               # budget fully used first
    out = eng.results()
    assert 0 not in out and out[1] == want[1]
    kv = eng.kv_cache
    kv.check()
    assert kv.table.free_pages == kv.paging.n_pages
    assert not kv.slot_pages and not kv.slot_state
    assert len(eng.kv_store) == 0 and eng.kv_store.bytes_used == 0
    assert sorted(eng._sched.free_slots) == list(range(4))


def test_restore_retries_transient_store_loss():
    """A transient ``store_get_loss`` during restore is retried away
    (``EngineConfig.restore_retries``) instead of downgrading to
    re-prefill — and with retries off, the SAME plan downgrades."""
    params = T.init(TINY, jax.random.PRNGKey(0))
    mesh = _mesh()
    oracle = _engine(params, mesh)
    _submit_mix(oracle, n=2)
    want = oracle.run()

    def drive(**kw):
        plan = FaultPlan(store_get_loss=FaultSpec(at=(0,)))
        eng = _engine(params, mesh, offload=True, faults=plan, **kw)
        _submit_mix(eng, n=2)
        pre = False
        for _ in range(600):
            if not eng._sched.has_work and not eng._pending:
                break
            st = eng._find_active(0)
            if (not pre and st is not None and not st.prefilling
                    and len(st.generated) >= 1 and not st.finished()):
                assert eng.preempt(0)
                pre = True
            eng.step()
        assert pre and eng.results() == want and not eng.failed()
        return eng

    eng = drive()                            # default restore_retries=2
    assert eng.stats.restore_hits == 1 and eng.stats.restore_misses == 0
    assert eng.stats.restarts == 0
    assert eng.stats.store_get_retries >= 1
    assert eng.stats.summary()["store_get_retries"] >= 1

    eng0 = drive(restore_retries=0)          # same plan, no retry budget
    assert eng0.stats.restore_misses == 1 and eng0.stats.restarts == 1
    assert eng0.stats.store_get_retries == 0


# --------------------------------------------------------------------------
# packed-path isfinite guard (satellite: forced non-finite decode row)
# --------------------------------------------------------------------------

def test_packed_nonfinite_row_quarantines_exactly_one_slot():
    """Force a non-finite decode row out of a PACKED tick (mixed
    prefill + decode) and assert the packed-path isfinite guard
    quarantines exactly that one slot: the poisoned request re-prefills
    and recovers, the mid-prefill neighbour is untouched, and both end
    token-identical to the clean oracle."""
    params = T.init(TINY, jax.random.PRNGKey(0))
    mesh = _mesh()
    pA, pB = tuple(range(1, 7)), tuple(range(2, 8))
    oracle = _engine(params, mesh)
    oracle.submit(pA, max_new_tokens=6, sampling=SamplingParams(seed=0))
    oracle.submit(pB, max_new_tokens=6, sampling=SamplingParams(seed=1))
    want = oracle.run()

    eng = _engine(params, mesh)
    eng.submit(pA, max_new_tokens=6, sampling=SamplingParams(seed=0))
    for _ in range(100):                     # drive A into steady decode
        eng.step()
        st = eng._find_active(0)
        if (st is not None and not st.prefilling
                and len(st.generated) >= 1 and not st.finished()):
            break
    else:
        raise AssertionError("request 0 never reached decode")
    # B admits this tick -> mixed packed tick; decode rows pack first,
    # so row 0 is A's decode row
    eng.submit(pB, max_new_tokens=6, sampling=SamplingParams(seed=1))
    real = eng._packed

    def nan_row0(*a, **k):
        logits, storage = real(*a, **k)
        return logits.at[0].set(float("nan")), storage

    eng._packed = nan_row0
    assert eng.step() == "packed"
    eng._packed = real
    assert eng.stats.quarantined == 1        # exactly one slot
    assert eng.stats.restarts == 1
    eng.run()
    assert eng.results() == want and not eng.failed()
    assert eng._results[0].restarts == 1
    assert eng._results[1].restarts == 0


# --------------------------------------------------------------------------
# shard loss: degraded window + standby replicas (1x1 total loss; the
# 2x4 exact+prism cells run in engine_equiv_runner.py)
# --------------------------------------------------------------------------

def test_shard_loss_degraded_window_recovers_token_identical():
    """Kill the (only) sequence shard mid-decode: the engine serves a
    bounded degraded window through the Segment-Means standby replicas
    (finite tokens, no failures), then recovers via the deterministic
    re-prefill and finishes token-identical to the clean oracle, with
    the loss visible in the stats and the drained engine leak-free."""
    params = T.init(TINY, jax.random.PRNGKey(0))
    mesh = _mesh()
    oracle = _engine(params, mesh)
    _submit_mix(oracle, n=3)
    want = oracle.run()

    plan = FaultPlan(shard_loss=FaultSpec(at=(6,), shard=0))
    eng = _engine(params, mesh, faults=plan)
    assert eng._replica is not None          # standby layer armed
    _submit_mix(eng, n=3)
    kinds = []
    for _ in range(600):
        if not eng._sched.has_work and not eng._pending:
            break
        kinds.append(eng.step())
    assert "degraded" in kinds and "recovered" in kinds
    assert eng.results() == want and not eng.failed()
    s = eng.stats.summary()
    assert s["shard_lost"] == 1 and s["degraded_ticks"] >= 1
    assert s["faults_by_kind"]["shard_loss"] == 1
    assert eng._replica.stats()["captures"] >= 1
    kv = eng.kv_cache
    kv.check()
    assert not kv.slot_pages and not kv.slot_state
    assert kv.table.free_pages == kv.paging.n_pages
    assert sorted(eng._sched.free_slots) == list(range(4))


def test_shard_loss_snapshot_refused_while_degraded():
    """The snapshot gather reads every shard; while one is lost the
    journal would be torn — snapshot() must refuse until recovery."""
    params = T.init(TINY, jax.random.PRNGKey(0))
    plan = FaultPlan(shard_loss=FaultSpec(at=(4,), shard=0),
                     # hold the degraded window open long enough to
                     # catch it mid-flight
                     seed=0)
    eng = _engine(params, _mesh(), faults=plan, degraded_grace=50)
    _submit_mix(eng, n=2)
    for _ in range(200):
        if eng.step() == "degraded":
            break
    else:
        raise AssertionError("never entered the degraded window")
    with pytest.raises(ValueError, match="degraded"):
        eng.snapshot()
    for _ in range(600):                     # drain through recovery
        if not eng._sched.has_work and not eng._pending:
            break
        eng.step()
    assert eng.snapshot() is not None        # recovered: journal fine


# --------------------------------------------------------------------------
# all-kinds chaos, engine end-to-end
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_chaos_engine_token_identical_and_leak_free(seed):
    """The full chaos plan (store loss, page poisoning, admission
    stalls, tick delays) plus forced preemptions: every request that
    completes is token-identical to the clean run, every request is
    accounted for, and the drained engine audits leak-free."""
    params = T.init(TINY, jax.random.PRNGKey(0))
    mesh = _mesh()
    oracle = _engine(params, mesh)
    _submit_mix(oracle, n=4)
    want = oracle.run()

    eng = _engine(params, mesh, offload=True,
                  faults=FaultPlan.chaos(seed), max_restarts=8)
    _submit_mix(eng, n=4)
    hit = set()
    for _ in range(3000):
        if not eng._sched.has_work and not eng._pending:
            break
        eng.step()
        for st in list(eng._sched.active.values()):
            rid = st.req.rid
            if (rid not in hit and not st.prefilling
                    and len(st.generated) >= 1 and not st.finished()):
                assert eng.preempt(rid)
                hit.add(rid)
    else:
        raise AssertionError("chaos run did not drain")
    out, failed = eng.results(), eng.failed()
    assert set(out) | set(failed) == set(range(4))
    assert not (set(out) & set(failed))
    for rid, toks in out.items():
        assert toks == want[rid], f"rid {rid} diverged under faults"
    assert eng._injector.total_injected > 0
    assert eng.stats.faults_injected == eng._injector.total_injected
    kv = eng.kv_cache
    kv.check()
    assert not kv.slot_pages and not kv.slot_state
    assert kv.table.free_pages == kv.paging.n_pages
    assert len(eng.kv_store) == 0 and eng.kv_store.bytes_used == 0
    assert sorted(eng._sched.free_slots) == list(range(4))


# --------------------------------------------------------------------------
# crash-consistent snapshot / restore (1x1; the 2x4 exact+prism cells
# run in engine_equiv_runner.py)
# --------------------------------------------------------------------------

def test_snapshot_restore_mid_flight_token_identical():
    params = T.init(TINY, jax.random.PRNGKey(0))
    mesh = _mesh()
    eng = _engine(params, mesh, offload=True, n_slots=2)
    _submit_mix(eng, n=3)                        # 2 active + 1 queued
    for _ in range(100):
        eng.step()
        if any(st.generated for st in eng._sched.active.values()):
            break
    assert eng.preempt(0)                        # >= 1 spilled at the cut
    snap = eng.snapshot()
    ref = eng.run()                              # snapshot is non-destructive
    assert sorted(ref) == [0, 1, 2]

    eng2 = _engine(params, mesh, offload=True, n_slots=2)
    eng2.restore(snap)
    assert 0 in eng2.kv_store                    # spilled entry journalled
    out2 = eng2.run()
    assert out2 == ref                           # token-identical resume
    assert len(eng2.kv_store) == 0
    eng2.kv_cache.check()

    # the journal is re-restorable: a third engine from the SAME snap
    eng3 = _engine(params, mesh, offload=True, n_slots=2)
    eng3.restore(snap)
    assert eng3.run() == ref


def test_snapshot_restore_validation():
    params = T.init(TINY, jax.random.PRNGKey(0))
    mesh = _mesh()
    dense = _engine(params, mesh, paged=False, prefix_cache=False)
    with pytest.raises(ValueError, match="paged"):
        dense.snapshot()

    eng = _engine(params, mesh, offload=True)
    _submit_mix(eng, n=2)
    eng.step()
    snap = eng.snapshot()
    with pytest.raises(ValueError, match="fresh"):
        eng.restore(snap)                        # target must be fresh
    eng.run()
