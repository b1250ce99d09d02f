"""Offload-tier + preemption tests.

Three layers, mirroring the subsystem's split:

  * ``KVStore`` unit behavior (LRU, capacity, drop accounting);
  * churn property tests driving the REAL scheduler + KVCache +
    KVStore host-side bookkeeping through seeded random 300+-op
    sequences of submit/admit/tick/preempt/cancel, asserting the full
    accounting invariants after every op (no slot or page leak,
    refcounts balanced across every spill/restore round-trip, no
    starvation, FIFO within each priority class);
  * device-level engine tests on the 1x1 mesh: preempted-then-restored
    requests are token-identical to the uninterrupted oracle, priority
    pressure preempts automatically, a store that LOSES entries
    triggers clean per-request re-prefill (never a hang or a corrupted
    neighbor), and suspend/resume parks an idle session without
    holding pages.

The sharded (2x4) equivalence — exact AND prism — runs in
``tests/engine_equiv_runner.py`` via its forced-preemption variant.
"""
import numpy as np
import jax
import pytest

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.runtime.offload import KVStore
from repro.runtime.paging import KVCache, PagedLayout, PrefixCache
from repro.runtime.serve import ServeHParams
from repro.serving import (EngineConfig, FifoScheduler, Request,
                           RequestState, SamplingParams, ServingEngine)
from repro.launch.mesh import make_mesh


TINY = ModelConfig(
    name="tiny-serve", arch_type="dense", n_layers=2, d_model=32,
    n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=61,
    mlp_kind="swiglu", norm_kind="rmsnorm", pos="rope",
    tie_embeddings=True)


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def _req(rid, plen=6, max_new=4, priority=0, arrival=0.0,
         deadline=None):
    return Request(rid=rid, prompt=tuple(range(1, plen + 1)),
                   max_new_tokens=max_new, priority=priority,
                   arrival=arrival, deadline=deadline)


def _host_kv(n_pages=14, ppr=4, n_state=4, prefix=False):
    paging = PagedLayout(page_cols=4, n_seq=1, pages_per_row=ppr,
                         n_pages=n_pages, n_state_pages=n_state)
    kv = KVCache(storage=None, layout=None, paging=paging)
    if prefix:
        kv.prefix = PrefixCache(kv.table)
    return kv


# --------------------------------------------------------------------------
# KVStore
# --------------------------------------------------------------------------

def test_kvstore_put_peek_pop():
    s = KVStore()
    assert s.put("a", 3, None, tokens=9)
    assert "a" in s and len(s) == 1 and s.bytes_used == 3
    ent = s.peek("a")
    assert ent.n_pages == 3 and ent.tokens == 9 and ent.payload is None
    ent = s.pop("a")
    assert ent is not None and "a" not in s and s.bytes_used == 0
    assert s.pop("a") is None
    assert s.hits == 1 and s.misses == 1


def test_kvstore_capacity_evicts_lru():
    s = KVStore(capacity_bytes=5)
    s.put("a", 2, None)
    s.put("b", 2, None)
    s.peek("a")                       # a becomes MRU
    s.put("c", 2, None)               # evicts b (LRU)
    assert "a" in s and "c" in s and "b" not in s
    assert s.evictions == 1 and s.bytes_used == 4
    assert not s.put("big", 9, None)  # larger than capacity: dropped
    assert s.drops == 1 and "big" not in s


def test_kvstore_zero_capacity_drops_everything():
    s = KVStore(capacity_bytes=0)
    assert not s.put("a", 1, None)
    assert len(s) == 0 and s.drops == 1 and s.peek("a") is None


def test_kvstore_replace_same_key():
    s = KVStore()
    s.put("a", 2, None)
    s.put("a", 4, None)
    assert len(s) == 1 and s.bytes_used == 4 and s.peek("a").n_pages == 4


# --------------------------------------------------------------------------
# KVCache spill/restore (host-side refcount handoff)
# --------------------------------------------------------------------------

def test_spill_restore_refcount_roundtrip():
    kv, store = _host_kv(), KVStore()
    plan = kv.plan(range(1, 7), max_new_tokens=3)       # 9 tok -> 3 pages
    assert kv.reserve(7, plan)
    kv.bind(7, slot=0)
    kv.check()
    free0, state0 = kv.table.free_pages, len(kv._state_free)

    n = kv.spill(7, 0, store, tokens=6)
    assert n == 3 and 7 in store
    assert kv.table.free_pages == free0 + 3              # pages handed back
    assert len(kv._state_free) == state0 + 1             # state row too
    assert 0 not in kv.slot_pages and 0 not in kv.slot_state
    kv.check()

    rplan = kv.plan_restore(7, store)
    assert (rplan.total_pages, rplan.fresh_pages, rplan.covered) == (3, 3, 6)
    assert kv.can_admit(rplan) and kv.reserve(7, rplan)
    kv.bind(7, slot=2)                                   # different slot
    assert kv.restore(7, 2, store)
    assert 7 not in store and len(kv.slot_pages[2]) == 3
    kv.check()
    kv.free(2)
    kv.check()
    assert kv.table.free_pages == kv.paging.n_pages


def test_spill_with_shared_prefix_pages_decrefs():
    """A victim holding COW prefix pages spills cleanly: the gathered
    copy is private, the shared pages just decref under their cache
    entries."""
    kv, store = _host_kv(prefix=True), KVStore()
    span = kv.paging.span
    prompt = list(range(1, 10))                          # 9 tok, 2 full pages
    plan = kv.plan(prompt, max_new_tokens=3)
    assert kv.reserve(1, plan)
    kv.bind(1, 0)
    kv.free(0, prompt)                                   # registers prefix
    kv.check()
    plan2 = kv.plan(prompt, max_new_tokens=3)
    assert plan2.covered == 2 * span and len(plan2.shared) == 2
    assert kv.reserve(2, plan2)
    kv.bind(2, 0)
    kv.check()
    kv.spill(2, 0, store, tokens=9)
    kv.check()                                           # refcounts balanced
    rplan = kv.plan_restore(2, store)
    assert rplan.fresh_pages == rplan.total_pages == 3   # all private now
    assert kv.reserve(2, rplan)
    kv.bind(2, 1)
    assert kv.restore(2, 1, store)
    kv.check()
    kv.free(1)
    kv.check()


def test_restore_miss_leaves_pages_bound():
    kv = _host_kv()
    store = KVStore(capacity_bytes=0)                    # loses everything
    plan = kv.plan(range(1, 5), max_new_tokens=4)        # 8 tok -> 2 pages
    assert kv.reserve(3, plan)
    kv.bind(3, 0)
    kv.spill(3, 0, store, tokens=4)                      # dropped on put
    assert 3 not in store
    assert kv.plan_restore(3, store) is None             # caller re-plans
    plan2 = kv.plan(range(1, 5), max_new_tokens=4)
    assert kv.reserve(3, plan2)
    kv.bind(3, 1)
    assert not kv.restore(3, 1, store)                   # miss reported...
    assert len(kv.slot_pages[1]) == 2                    # ...pages intact
    kv.check()


# --------------------------------------------------------------------------
# scheduler policy: priority classes, resume ordering, victim pick
# --------------------------------------------------------------------------

def test_priority_classes_admit_high_first_fifo_within():
    s = FifoScheduler(1)
    for rid, prio in [(0, 0), (1, 1), (2, 0), (3, 1)]:
        s.submit(_req(rid, priority=prio))
    order = []
    while s.queued:
        st = s.admit(now=0.0)[0]
        order.append(st.req.rid)
        s.evict(st, now=1.0)
    assert order == [1, 3, 0, 2]      # class 1 first, FIFO inside each


def test_resume_goes_before_fresh_in_same_class():
    s = FifoScheduler(1)
    s.submit(_req(0))
    (st,) = s.admit(now=0.0)
    st.begin_decode()
    s.preempt(st)                     # park it
    s.submit(_req(1))                 # fresh, same class
    assert s.peek_admit() is st
    (back,) = s.admit(now=2.0)
    assert back is st and back.slot == 0
    assert back.pos == len(back.req.prompt) - 1          # progress kept


def test_fair_resume_ordering_by_arrival():
    s = FifoScheduler(3)
    for rid, arr in [(0, 0.0), (1, 1.0), (2, 2.0)]:
        s.submit(_req(rid, arrival=arr))
    sts = s.admit(now=2.0)
    s.preempt(sts[2])                 # park out of arrival order
    s.preempt(sts[0])
    s.preempt(sts[1])
    order = [st.req.rid for st in s.resume[0]]
    assert order == [0, 1, 2]         # earliest arrival resumes first


def test_pick_victim_lowest_priority_longest_remaining():
    s = FifoScheduler(4)
    specs = [(0, 0, 8), (1, 0, 2), (2, 1, 8), (3, 2, 8)]
    for rid, prio, gen in specs:
        s.submit(_req(rid, plen=4, max_new=gen, priority=prio))
    sts = {st.req.rid: st for st in s.admit(now=0.0)}
    for st in sts.values():
        st.begin_decode()
    # among priorities < 2: class 0 beats class 1; rid0 has more
    # remaining than rid1
    assert s.pick_victim(2) is sts[0]
    sts[0].generated = [5] * 7        # rid0 nearly done: rid1 now longer
    assert s.pick_victim(2) is sts[1]
    assert s.pick_victim(1) in (sts[0], sts[1])
    assert s.pick_victim(0) is None   # nothing strictly below class 0


def test_pick_victim_prefers_decoding_over_prefilling():
    s = FifoScheduler(2)
    s.submit(_req(0, plen=8, max_new=8))
    s.submit(_req(1, plen=4, max_new=2))
    sts = {st.req.rid: st for st in s.admit(now=0.0)}
    sts[1].begin_decode()             # rid1 decoding, rid0 mid-prefill
    assert s.pick_victim(5) is sts[1]


def test_pick_victim_breaks_ties_by_deadline_slack():
    """Deadline/SLO-aware victim policy: within a priority class, the
    victim with the MOST slack (``deadline - now - remaining``) spills
    first; the request racing its deadline is preempted last."""
    s = FifoScheduler(3)
    # same priority, same remaining work — slack alone decides
    s.submit(_req(0, plen=4, max_new=8, deadline=100.0))  # loose SLO
    s.submit(_req(1, plen=4, max_new=8, deadline=12.0))   # tight SLO
    s.submit(_req(2, plen=4, max_new=8))                  # no deadline
    sts = {st.req.rid: st for st in s.admit(now=0.0)}
    for st in sts.values():
        st.begin_decode()
    # no deadline = infinite slack: always the first victim
    assert s.pick_victim(5, now=0.0) is sts[2]
    s.preempt(sts[2])
    # loose SLO spills before tight SLO
    assert s.pick_victim(5, now=0.0) is sts[0]
    s.preempt(sts[0])
    assert s.pick_victim(5, now=0.0) is sts[1]


def test_pick_victim_slack_moves_with_the_clock():
    """Slack is evaluated at ``now``: the same pair of requests swaps
    victim order as one request's deadline closes in."""
    s = FifoScheduler(2)
    s.submit(_req(0, plen=4, max_new=4, deadline=20.0))
    s.submit(_req(1, plen=4, max_new=8, deadline=21.0))
    sts = {st.req.rid: st for st in s.admit(now=0.0)}
    for st in sts.values():
        st.begin_decode()
    # t=0: slack0 = 20-0-4 = 16, slack1 = 21-0-8 = 13 -> rid0 spills
    assert s.pick_victim(9, now=0.0) is sts[0]
    # rid1 finishes most of its work: slack1 = 21-10-1 = 10,
    # slack0 = 20-10-4 = 6 -> victim order flips at t=10
    sts[1].generated = [5] * 7
    assert s.pick_victim(9, now=10.0) is sts[1]


def test_pick_victim_priority_still_dominates_slack():
    """Slack is a TIE-BREAK inside a priority class, never a way for a
    low-priority deadline to outrank a higher class."""
    s = FifoScheduler(2)
    s.submit(_req(0, plen=4, max_new=4, priority=0, deadline=9.0))
    s.submit(_req(1, plen=4, max_new=4, priority=1))      # no deadline
    sts = {st.req.rid: st for st in s.admit(now=0.0)}
    for st in sts.values():
        st.begin_decode()
    # class 0 spills first even though its deadline is tight and the
    # class-1 request has infinite slack
    assert s.pick_victim(5, now=8.0) is sts[0]


def test_scheduler_cancel_queued_and_parked():
    s = FifoScheduler(1)
    s.submit(_req(0))
    s.submit(_req(1))
    (st,) = s.admit(now=0.0)
    s.preempt(st)
    assert s.cancel(1).rid == 1                          # queued fresh
    assert s.cancel(0) is st                             # parked resume
    assert s.cancel(42) is None and s.queued == 0


# --------------------------------------------------------------------------
# churn property test: scheduler + KVCache + KVStore under random ops
# --------------------------------------------------------------------------

class _Churn:
    """Random-op driver over the real host-side subsystem (the engine's
    admission/restore logic mirrored without device storage).  Prompt +
    generation always fit one logical row (<= pages_per_row * span)."""

    def __init__(self, seed, *, n_slots=4, n_pages=14, ppr=4,
                 prefix=False, store=None):
        self.rng = np.random.default_rng(seed)
        self.kv = _host_kv(n_pages=n_pages, ppr=ppr, n_state=n_slots,
                           prefix=prefix)
        self.sched = FifoScheduler(n_slots)
        self.store = KVStore() if store is None else store
        self.flaky = self.store.capacity_bytes is not None
        self.n_slots, self.n_pages = n_slots, n_pages
        self.next_rid = 0
        self.now = 0.0
        self.plans: dict = {}
        self.from_store: set = set()
        self.live: set = set()            # submitted, not finished/cancelled
        self.finished: set = set()
        self.cancelled: set = set()
        self.restarted: set = set()
        self.submit_order: dict = {}      # prio -> [rid]
        self.admit_order: dict = {}       # prio -> [rid first admitted]
        self.admitted_once: set = set()

    # -- engine-mirrored admission gate --------------------------------
    def _fresh_gate(self, req) -> bool:
        kv, pfx = self.kv, self.kv.prefix is not None
        plan = kv.plan(req.prompt, req.max_new_tokens, use_prefix=pfx)
        if not kv.can_admit(plan, reclaim=False):
            if kv.prefix is not None:
                kv.prefix.reclaim(plan.fresh_pages)
                plan = kv.plan(req.prompt, req.max_new_tokens,
                               use_prefix=pfx)
            if not kv.can_admit(plan, reclaim=False):
                return False
        if not kv.reserve(req.rid, plan):
            return False
        self.plans[req.rid] = plan
        return True

    def _gate(self, cand) -> bool:
        if not isinstance(cand, RequestState):
            return self._fresh_gate(cand)
        rid = cand.req.rid
        plan = self.kv.plan_restore(rid, self.store)
        if plan is None:                  # store lost it: re-prefill
            cand.reset_for_refill()
            self.restarted.add(rid)
            return self._fresh_gate(cand.req)
        if not (self.kv.can_admit(plan) and self.kv.reserve(rid, plan)):
            return False
        self.plans[rid] = plan
        self.from_store.add(rid)
        return True

    # -- ops -----------------------------------------------------------
    def op_submit(self):
        rid = self.next_rid
        self.next_rid += 1
        plen = int(self.rng.integers(1, 9))
        gen = int(self.rng.integers(1, 9))
        prio = int(self.rng.integers(0, 3))
        prompt = tuple(int(t) for t in self.rng.integers(1, 50, size=plen))
        self.sched.submit(Request(rid=rid, prompt=prompt,
                                  max_new_tokens=gen, priority=prio,
                                  arrival=self.now))
        self.live.add(rid)
        self.submit_order.setdefault(prio, []).append(rid)
        self.now += 1.0

    def op_admit(self):
        for st in self.sched.admit(self.now, gate=self._gate):
            rid = st.req.rid
            self.kv.bind(rid, st.slot)
            plan = self.plans.pop(rid)
            if rid in self.from_store:
                self.from_store.discard(rid)
                if not self.kv.restore(rid, st.slot, self.store):
                    st.reset_for_refill()
                    self.restarted.add(rid)
            elif plan.covered and st.nprefilled < plan.covered:
                st.nprefilled = plan.covered
            if rid not in self.admitted_once:
                self.admitted_once.add(rid)
                self.admit_order.setdefault(st.req.priority, []).append(rid)

    def op_tick(self):
        for st in self.sched.prefilling():
            st.nprefilled += min(3, len(st.req.prompt) - st.nprefilled)
            if not st.prefilling:
                st.begin_decode()
        for st in self.sched.decoding():
            st.generated.append(7)
            st.pos += 1
            st.next_token = 7
            if st.finished():
                self.kv.free(st.slot, st.req.prompt
                             if self.kv.prefix is not None else None)
                self.sched.evict(st, self.now)
                self.finished.add(st.req.rid)
                self.live.discard(st.req.rid)
        self.now += 1.0

    def op_preempt(self):
        active = list(self.sched.active.values())
        if not active:
            return
        st = active[int(self.rng.integers(len(active)))]
        self.kv.spill(st.req.rid, st.slot, self.store,
                      tokens=st.nprefilled)
        self.sched.preempt(st)

    def op_cancel(self):
        waiting = ([r for q in self.sched.queues.values() for r in q]
                   + [s.req for q in self.sched.resume.values() for s in q])
        if not waiting:
            return
        rid = waiting[int(self.rng.integers(len(waiting)))].rid
        assert self.sched.cancel(rid) is not None
        self.store.drop(rid)
        self.cancelled.add(rid)
        self.live.discard(rid)

    # -- invariants ----------------------------------------------------
    def check(self):
        self.kv.check()                   # refcounts == holders, state rows
        sch = self.sched
        assert sorted(sch.free_slots + list(sch.active)) \
            == list(range(self.n_slots)), "slot leak"
        for slot, st in sch.active.items():
            assert st.slot == slot and slot in self.kv.slot_pages
        assert self.store.bytes_used \
            == sum(e.nbytes for e in self.store._entries.values())
        if not self.flaky:
            for q in sch.resume.values():
                for st in q:
                    assert st.req.rid in self.store, \
                        f"parked rid {st.req.rid} lost its store entry"
        for q in sch.resume.values():
            arrivals = [st.req.arrival for st in q]
            assert arrivals == sorted(arrivals), "unfair resume order"

    def drain(self):
        for _ in range(3000):
            if not self.sched.has_work:
                return
            self.op_admit()
            self.op_tick()
            self.check()
        raise AssertionError("churn did not drain: starvation or leak")

    def run(self, n_ops=320):
        ops = [self.op_submit, self.op_admit, self.op_tick,
               self.op_preempt, self.op_cancel]
        weights = [0.28, 0.22, 0.30, 0.13, 0.07]
        for _ in range(n_ops):
            self.rng.choice(ops, p=weights)()
            self.check()
        self.drain()
        # zero page/slot/state leak after everything completes (prefix
        # entries hold pages by design — drop them before the audit)
        if self.kv.prefix is not None:
            self.kv.prefix.clear()
        assert self.kv.table.free_pages == self.n_pages
        assert sorted(self.kv._state_free) == list(range(self.n_slots))
        assert self.sched.free_slots == list(range(self.n_slots))
        if not self.flaky:
            assert len(self.store) == 0, "orphaned store entries"
        # no starvation: every non-cancelled request finished
        assert self.finished == set(range(self.next_rid)) - self.cancelled
        # FIFO preserved within each priority class (first admissions)
        for prio, order in self.admit_order.items():
            expect = [r for r in self.submit_order[prio] if r in order]
            assert order == expect, f"class {prio} lost FIFO order"


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_churn_300_ops_no_leaks(seed):
    _Churn(seed).run(n_ops=320)


def test_churn_with_prefix_cache():
    _Churn(5, prefix=True).run(n_ops=320)


def test_churn_with_flaky_store_recovers():
    """A capacity-starved store drops/evicts spilled entries; every
    affected request must restart cleanly and still finish."""
    c = _Churn(7, store=KVStore(capacity_bytes=5))
    c.run(n_ops=320)
    assert c.store.drops + c.store.evictions > 0
    assert c.restarted, "flaky store never exercised the restart path"


# --------------------------------------------------------------------------
# engine end-to-end (1x1 mesh, real device storage)
# --------------------------------------------------------------------------

def _engine(params, mesh, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("prefill_len", 8)
    kw.setdefault("max_cache", 24)
    # prefix off: these tests pin EXACT page accounting after drain
    # (prefix entries intentionally outlive their owners); the
    # prefix+preemption interaction is covered by the host-side spill
    # tests above and the equiv runner's forced-preemption cells
    kw.setdefault("prefix_cache", False)
    return ServingEngine(TINY, mesh, params, **kw)


def _submit_mix(eng, n=4, gen=6):
    rng = np.random.default_rng(3)
    for i in range(n):
        plen = int(rng.integers(3, 9))
        prompt = rng.integers(1, TINY.vocab_size, size=plen)
        eng.submit(prompt, max_new_tokens=gen,
                   sampling=SamplingParams(seed=i))


def test_engine_preempt_restore_token_identical():
    """Every request force-preempted mid-decode and restored must
    reproduce the uninterrupted oracle token-for-token (the 2x4 /
    prism version runs in engine_equiv_runner.py)."""
    params = T.init(TINY, jax.random.PRNGKey(0))
    mesh = _mesh()
    oracle = _engine(params, mesh)
    _submit_mix(oracle)
    want = oracle.run()

    eng = _engine(params, mesh, offload=True)
    _submit_mix(eng)
    hit = set()
    for _ in range(400):
        if not eng._sched.has_work and not eng._pending:
            break
        eng.step()
        for st in list(eng._sched.active.values()):
            rid = st.req.rid
            if (rid not in hit and not st.prefilling
                    and len(st.generated) >= 1 and not st.finished()):
                assert eng.preempt(rid)
                hit.add(rid)
        eng.kv_cache.check()
    assert eng.results() == want
    assert len(hit) == 4
    assert eng.stats.preemptions >= 4 and eng.stats.restore_hits >= 4
    assert eng.stats.spilled_pages > 0 and eng.stats.restore_misses == 0
    assert len(eng.kv_store) == 0
    assert eng.kv_cache.table.free_pages == eng.kv_cache.paging.n_pages


def test_engine_priority_pressure_preempts_and_recovers():
    """With the pool page-starved, a higher-priority arrival preempts
    the lowest-priority longest-remaining decode; after the load
    drains, backpressure is gone and the pool is fully free."""
    params = T.init(TINY, jax.random.PRNGKey(0))
    eng = _engine(params, _mesh(), offload=True, n_pages=6)
    rng = np.random.default_rng(0)
    for i in range(3):                     # 2 pages each: pool now full
        eng.submit(rng.integers(1, 61, size=8), max_new_tokens=8,
                   priority=0)
    for _ in range(30):                    # all three decoding
        eng.step()
        if len(eng._sched.decoding()) == 3:
            break
    hi = eng.submit(rng.integers(1, 61, size=6), max_new_tokens=4,
                    priority=1)
    eng.step()                             # blocked -> preempt -> admit
    assert eng.stats.preemptions >= 1
    assert any(st.req.rid == hi for st in eng._sched.active.values())
    out = eng.run()
    assert sorted(out) == [0, 1, 2, hi]
    assert all(len(v) > 0 for v in out.values())
    # backpressure fully cleared: pool free, store empty, and a fresh
    # request admits without a single new out_of_pages event
    kv = eng.kv_cache
    assert kv.table.free_pages == kv.paging.n_pages
    assert len(eng.kv_store) == 0
    blocked = eng.stats.out_of_pages
    eng.submit(rng.integers(1, 61, size=4), max_new_tokens=2)
    eng.run()
    assert eng.stats.out_of_pages == blocked


def test_engine_lost_restore_reprefills_cleanly():
    """A store that drops every spill (total host-memory pressure) must
    surface per-request recovery — re-prefill, same final tokens under
    greedy sampling — and never corrupt a neighbor request."""
    params = T.init(TINY, jax.random.PRNGKey(0))
    mesh = _mesh()
    oracle = _engine(params, mesh)
    _submit_mix(oracle, n=2)
    want = oracle.run()

    eng = _engine(params, mesh, offload=True)
    eng._store = KVStore(capacity_bytes=0)     # fault injection
    _submit_mix(eng, n=2)
    preempted = False
    for _ in range(400):
        if not eng._sched.has_work and not eng._pending:
            break
        eng.step()
        if not preempted:
            for st in list(eng._sched.active.values()):
                if st.req.rid == 0 and len(st.generated) >= 2:
                    assert eng.preempt(0)
                    preempted = True
        eng.kv_cache.check()
    assert preempted
    assert eng.results() == want               # rid 0 reran, rid 1 untouched
    assert eng.stats.restore_misses >= 1 and eng.stats.restore_hits == 0
    assert eng._results[0].restarts >= 1
    assert eng.kv_cache.table.free_pages == eng.kv_cache.paging.n_pages


def test_engine_suspend_resume_idle_session():
    params = T.init(TINY, jax.random.PRNGKey(0))
    mesh = _mesh()
    oracle = _engine(params, mesh)
    _submit_mix(oracle, n=2)
    want = oracle.run()

    eng = _engine(params, mesh, offload=True)
    _submit_mix(eng, n=2)
    for _ in range(200):
        st = eng._find_active(0)
        if st is not None and len(st.generated) >= 2:
            break
        eng.step()
    assert eng.suspend(0)
    assert eng._find_active(0) is None and 0 in eng.kv_store
    out = eng.run()                            # finishes rid 1 only
    assert 0 not in out and out[1] == want[1]
    assert eng.resume(0)
    out = eng.run()
    assert out[0] == want[0]                   # warm restore, same tokens
    assert len(eng.kv_store) == 0


def test_engine_cancel_waiting_request():
    """cancel() reaches every lifecycle state — including ACTIVE
    mid-flight (PR-9: the streaming front-end cancels decoding
    requests through this path).  The queued victim vanishes, the
    active victim releases its slot/pages zero-leak and lands in
    failed(), and the survivor finishes untouched."""
    params = T.init(TINY, jax.random.PRNGKey(0))
    eng = _engine(params, _mesh(), offload=True, n_slots=2)
    _submit_mix(eng, n=2)
    queued = eng.submit((1, 2, 3), max_new_tokens=2)    # no free slot yet
    eng.step()
    assert eng.cancel(queued)
    assert eng.cancel(0)                       # active: cancellable
    assert eng.failed()[0] == "cancelled"
    out = eng.run()
    assert queued not in out and sorted(out) == [1]
    assert eng.stats.cancelled == 2
    eng.kv_cache.check()
    assert (eng.kv_cache.table.free_pages
            == eng.kv_cache.paging.n_pages)


def test_engine_double_cancel_idempotent():
    """cancel() is idempotent: the second call (and a cancel of an
    unknown rid) returns False and leaves the bookkeeping intact."""
    params = T.init(TINY, jax.random.PRNGKey(0))
    eng = _engine(params, _mesh(), offload=True, n_slots=2)
    _submit_mix(eng, n=2)
    queued = eng.submit((1, 2, 3), max_new_tokens=2)
    eng.step()
    assert eng.cancel(queued)
    assert not eng.cancel(queued)              # double-cancel: no-op
    assert not eng.cancel(999)                 # never submitted
    eng.kv_cache.check()
    out = eng.run()
    assert sorted(out) == [0, 1]
    assert eng.kv_cache.table.free_pages == eng.kv_cache.paging.n_pages


def test_engine_cancel_while_spilled_then_recancel():
    """Cancelling a spilled (resume-parked) request reclaims its store
    entry; the second cancel returns False and nothing leaks."""
    params = T.init(TINY, jax.random.PRNGKey(0))
    eng = _engine(params, _mesh(), offload=True)
    _submit_mix(eng, n=2)
    for _ in range(200):
        st = eng._find_active(0)
        if st is not None and len(st.generated) >= 1:
            break
        eng.step()
    assert eng.preempt(0)
    assert 0 in eng.kv_store
    assert eng.cancel(0)                       # parked on the resume queue
    assert 0 not in eng.kv_store               # store bytes reclaimed
    assert not eng.cancel(0)                   # double-cancel: no-op
    out = eng.run()
    assert sorted(out) == [1]
    eng.kv_cache.check()
    assert eng.kv_cache.table.free_pages == eng.kv_cache.paging.n_pages
    assert eng.kv_store.bytes_used == 0


def test_engine_resume_after_cancel_returns_false():
    """resume() of a cancelled (formerly suspended) session returns
    False — the cancel won; no store entry, no ghost requeue."""
    params = T.init(TINY, jax.random.PRNGKey(0))
    eng = _engine(params, _mesh(), offload=True)
    _submit_mix(eng, n=2)
    for _ in range(200):
        st = eng._find_active(0)
        if st is not None and len(st.generated) >= 1:
            break
        eng.step()
    assert eng.suspend(0)
    assert eng.cancel(0)                       # cancels the parked session
    assert not eng.resume(0)                   # resume-after-cancel: no-op
    assert not eng.suspend(0)                  # not active either
    out = eng.run()
    assert sorted(out) == [1]
    assert len(eng.kv_store) == 0 and eng.kv_store.bytes_used == 0
    eng.kv_cache.check()


def test_engine_double_suspend_double_resume():
    params = T.init(TINY, jax.random.PRNGKey(0))
    eng = _engine(params, _mesh(), offload=True)
    _submit_mix(eng, n=2)
    for _ in range(200):
        st = eng._find_active(0)
        if st is not None and len(st.generated) >= 1:
            break
        eng.step()
    assert eng.suspend(0)
    assert not eng.suspend(0)                  # already parked
    assert eng.resume(0)
    assert not eng.resume(0)                   # already requeued
    out = eng.run()
    assert sorted(out) == [0, 1]
    assert len(eng.kv_store) == 0
    eng.kv_cache.check()
    assert eng.kv_cache.table.free_pages == eng.kv_cache.paging.n_pages


def test_engine_config_offload_validation():
    with pytest.raises(ValueError, match="paged"):
        EngineConfig(n_slots=2, prefill_len=8, max_cache=16,
                     offload=True, paged=False)
    with pytest.raises(ValueError, match="gang"):
        EngineConfig(n_slots=2, prefill_len=8, max_cache=16,
                     offload=True, gang=True)
    with pytest.raises(ValueError, match="paged"):
        EngineConfig(n_slots=2, prefill_len=8, max_cache=16,
                     offload=True, prefill_mode="padded")
