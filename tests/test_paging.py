"""Paged KV cache tests: PageTable free-list/refcount invariants under
churn, PrefixCache register/lookup/reclaim, the KVCache lifecycle
(plan/reserve/bind/alloc/free + COW fork), and engine-level prefix
reuse — a shared system prompt must cut prefill work without changing
a single token vs the unshared oracle, and the page accounting must
hold after every engine step.  All on the single real CPU device; the
sharded paged-vs-dense equivalence runs via tests/engine_equiv_runner.py.
"""
import numpy as np
import jax
import pytest

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.runtime.paging import (NO_PAGE, KVCache, PagedLayout, PageTable,
                                  PrefixCache, make_paged_layout)
from repro.runtime.serve import (ServeHParams, make_kv_cache, make_layout,
                                 seq_shards)
from repro.serving import EngineConfig, ServingEngine
from repro.launch.mesh import make_mesh


TINY = ModelConfig(
    name="tiny-paged", arch_type="dense", n_layers=2, d_model=32,
    n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=61,
    mlp_kind="swiglu", norm_kind="rmsnorm", pos="rope",
    tie_embeddings=True)


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


# --------------------------------------------------------------------------
# PageTable
# --------------------------------------------------------------------------

def test_page_table_churn_invariants():
    """Random alloc/share/free churn holds the refcount == holders
    invariant after every operation; allocation is all-or-nothing."""
    rng = np.random.default_rng(0)
    table = PageTable(16)
    holders: list = []                 # list of page lists we hold refs on
    for _ in range(300):
        op = rng.integers(0, 3)
        if op == 0:                    # alloc 1..4 fresh pages
            got = table.alloc(int(rng.integers(1, 5)))
            if got is not None:
                holders.append(list(got))
        elif op == 1 and holders:      # share an existing holding
            pages = holders[int(rng.integers(len(holders)))]
            table.share(pages)
            holders.append(list(pages))
        elif op == 2 and holders:      # drop one holding
            table.free(holders.pop(int(rng.integers(len(holders)))))
        table.check()
        held = np.zeros(16, np.int64)
        for pages in holders:
            for p in pages:
                held[p] += 1
        assert np.array_equal(held, table.refs.astype(np.int64))
    # over-capacity request: nothing granted, nothing leaked
    free_before = table.free_pages
    assert table.alloc(table.free_pages + 1) is None
    assert table.free_pages == free_before
    for pages in holders:
        table.free(pages)
    assert table.free_pages == 16
    with pytest.raises(ValueError):
        table.free([0])                # double free


# --------------------------------------------------------------------------
# PrefixCache
# --------------------------------------------------------------------------

def test_prefix_cache_register_lookup_reclaim():
    span = 4
    table = PageTable(8)
    prompt = list(range(1, 13))        # 12 tokens = 3 full spans
    pages = table.alloc(3)
    cache = PrefixCache(table)
    # one entry per full-page prefix level
    assert cache.register(prompt, pages, span) == 3
    assert len(cache.entries) == 3
    table.free(pages)                  # owner evicted; entries hold refs
    table.check()
    assert table.free_pages == 5

    # longest strict-prefix hit: a longer prompt reuses all 3 pages ...
    ent = cache.lookup(prompt + [99], span)
    assert ent is not None and ent.tokens == 12 and len(ent.pages) == 3
    # ... but the SAME prompt only reuses 2 (the page holding the final
    # token must stay private for the rewind re-feed)
    ent = cache.lookup(prompt, span)
    assert ent is not None and ent.tokens == 8
    assert cache.lookup([7, 7, 7, 7, 7], span) is None
    assert cache.hits == 2 and cache.misses == 1

    # LRU reclaim refills the free list entry by entry
    dropped = cache.reclaim(6)
    assert dropped >= 1 and table.free_pages >= 6
    table.check()
    cache.clear()
    assert table.free_pages == 8


# --------------------------------------------------------------------------
# KVCache lifecycle (host-side bookkeeping; no device storage needed)
# --------------------------------------------------------------------------

def _host_kv(n_pages=12, ppr=3, n_state=4, prefix=False):
    paging = PagedLayout(page_cols=4, n_seq=1, pages_per_row=ppr,
                         n_pages=n_pages, n_state_pages=n_state)
    kv = KVCache(storage=None, layout=None, paging=paging)
    if prefix:
        kv.prefix = PrefixCache(kv.table)
    return kv


def test_kv_cache_alloc_append_free_lifecycle():
    kv = _host_kv()
    span = kv.paging.span              # 4 tokens
    prompt = list(range(1, 7))         # 6 tokens
    plan = kv.plan(prompt, max_new_tokens=3)     # 9 tokens -> 3 pages
    assert (plan.total_pages, plan.fresh_pages, plan.covered) == (3, 3, 0)
    assert kv.can_admit(plan)
    kv.alloc(0, plan)
    assert len(kv.slot_pages[0]) == 3 and kv.table.free_pages == 9
    kv.append(0, len(prompt) + 3)      # already covered: no-op
    assert len(kv.slot_pages[0]) == 3
    kv.check()

    # full-row plan (paged prism) always takes the whole logical row
    full = kv.plan([1, 2], max_new_tokens=1, full_row=True)
    assert full.total_pages == kv.paging.pages_per_row
    kv.alloc(1, full)
    kv.check()

    # reserve/bind is the two-phase admission the engine gate drives;
    # cancel returns everything
    plan2 = kv.plan([1] * span, max_new_tokens=1)
    assert kv.reserve("r7", plan2)
    kv.check()                         # reserved pages are accounted
    kv.cancel("r7")
    kv.check()
    assert kv.reserve("r8", plan2)
    kv.bind("r8", 2)
    assert len(kv.slot_pages[2]) == plan2.total_pages

    for slot in (0, 1, 2):
        kv.free(slot)
    kv.check()
    assert kv.table.free_pages == kv.paging.n_pages
    assert sorted(kv._state_free) == list(range(4))


def test_kv_cache_out_of_pages_is_all_or_nothing():
    kv = _host_kv(n_pages=4, ppr=4, n_state=2)
    big = kv.plan(list(range(12)), max_new_tokens=4)   # 4 pages
    kv.alloc(0, big)
    assert not kv.can_admit(kv.plan([1, 2], 1), reclaim=False)
    assert not kv.reserve("r1", kv.plan([1, 2], 1))    # nothing committed
    kv.check()
    with pytest.raises(RuntimeError):
        kv.alloc(1, kv.plan([1, 2], 1))
    kv.free(0)
    kv.alloc(1, kv.plan([1, 2], 1))
    kv.check()


def test_kv_cache_prefix_share_and_refcounts():
    kv = _host_kv(prefix=True)
    span = kv.paging.span
    prompt = list(range(1, 2 * span + 2))      # 9 tokens: 2 full spans
    kv.alloc(0, kv.plan(prompt, max_new_tokens=2))
    kv.free(0, prompt=prompt)                  # registers 2 prefix levels
    assert len(kv.prefix.entries) == 2
    kv.check()

    plan = kv.plan(prompt, max_new_tokens=2)   # same prompt again
    assert plan.covered == 2 * span and len(plan.shared) == 2
    assert plan.fresh_pages == plan.total_pages - 2
    kv.alloc(1, plan)
    kv.check()
    # holders per page: page 0 is in BOTH prefix levels + the slot,
    # page 1 in the level-2 entry + the slot
    assert kv.table.refs[plan.shared[0]] == 3
    assert kv.table.refs[plan.shared[1]] == 2
    kv.free(1)
    kv.check()
    kv.prefix.clear()
    assert kv.table.free_pages == kv.paging.n_pages


def test_kv_cache_cow_fork_on_device():
    """ensure_writable forks a shared page to a private copy on the
    device pool: refcounts split, the fork is counted, and the page
    accounting invariant still holds."""
    mesh = _mesh()
    hp = ServeHParams(decode_mode="exact", ssm_chunk=8)
    lay = make_layout(TINY, mesh, 2, 16, hp, 8)
    paging = make_paged_layout(lay, page_tokens=4, n_pages=None, n_slots=2)
    kv = make_kv_cache(TINY, mesh, lay, 2, hp, paging=paging,
                       prefix_cache=True)
    prompt = list(range(1, 6))                 # 5 tokens: 1 full span of 4
    kv.alloc(0, kv.plan(prompt, max_new_tokens=2))
    kv.free(0, prompt=prompt)
    plan = kv.plan(prompt, max_new_tokens=2)
    assert plan.covered == 4
    kv.alloc(1, plan)
    shared_page = kv.slot_pages[1][0]
    assert kv.table.refs[shared_page] == 2

    forked = kv.ensure_writable(1, 0, 3)       # write window inside page 0
    assert forked == 1 and kv.cow_copies == 1
    assert kv.slot_pages[1][0] != shared_page
    assert kv.table.refs[shared_page] == 1     # entry's ref survives
    kv.check()
    # a second call is a no-op: the page is already private
    assert kv.ensure_writable(1, 0, 3) == 0


# --------------------------------------------------------------------------
# engine-level prefix reuse + page accounting
# --------------------------------------------------------------------------

def _engine(params, mesh, **over):
    kw = dict(n_slots=2, prefill_len=16, max_cache=24,
              hp=ServeHParams(decode_mode="exact", ssm_chunk=8),
              chunk_len=4, token_budget=8)
    kw.update(over)
    return ServingEngine(TINY, mesh, params, EngineConfig(**kw))


def test_engine_prefix_hit_matches_unshared_oracle():
    """Two requests sharing a long system prompt: the second maps the
    registered prefix pages COW and skips prefilling the covered
    tokens, yet both outputs are token-identical to an engine with
    prefix reuse disabled."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    shared = rng.integers(1, TINY.vocab_size, size=12).tolist()
    p1, p2 = shared + [5], shared + [7, 9]

    eng = _engine(params, mesh)
    span = eng.kv_cache.paging.span
    assert len(shared) >= span                 # at least one full page
    r1 = eng.submit(p1, max_new_tokens=4)
    out1 = eng.run()[r1]
    assert eng.kv_cache.stats()["prefix_entries"] >= 1
    r2 = eng.submit(p2, max_new_tokens=4)
    out2 = eng.run()[r2]
    s = eng.stats.summary()
    assert s["prefix_hits"] == 1
    assert s["prefix_tokens_saved"] == (len(shared) // span) * span
    eng.kv_cache.check()

    ora = _engine(params, mesh, prefix_cache=False)
    for p, got in ((p1, out1), (p2, out2)):
        rid = ora.submit(p, max_new_tokens=4)
        assert ora.run()[rid] == got
    assert ora.stats.summary()["prefix_hits"] == 0


def test_engine_page_accounting_under_churn():
    """Staggered requests (several sharing a prefix) through a 2-slot
    engine: the full refcount/free-list invariant holds after EVERY
    engine step, and after the drain only prefix entries hold pages."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    eng = _engine(params, mesh)
    rng = np.random.default_rng(11)
    shared = rng.integers(1, TINY.vocab_size, size=10).tolist()
    prompts = [shared + rng.integers(1, TINY.vocab_size,
                                     size=int(rng.integers(1, 4))).tolist()
               if i % 2 == 0 else
               rng.integers(1, TINY.vocab_size,
                            size=int(rng.integers(3, 13))).tolist()
               for i in range(6)]
    for p in prompts:
        eng.submit(p, max_new_tokens=3)
    while eng._sched.has_work:
        eng.step()
        eng.kv_cache.check()
    assert eng.stats.completed == 6
    kv = eng.kv_cache
    assert not kv.slot_pages and not kv._reserved
    held = sum(len(e.pages) for e in kv.prefix.entries.values())
    assert kv.table.free_pages == kv.paging.n_pages - held
    kv.prefix.clear()
    assert kv.table.free_pages == kv.paging.n_pages


def test_engine_out_of_pages_backpressure():
    """A pool sized for one row at a time: the second request blocks at
    the admission gate (counted in EngineStats), admits after the first
    eviction, and both finish.  With prefix reuse on, the registered
    pages of the finished request are LRU-reclaimed to make room."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    pa = rng.integers(1, TINY.vocab_size, size=14).tolist()
    pb = rng.integers(1, TINY.vocab_size, size=14).tolist()

    for prefix_on in (False, True):
        eng = _engine(params, mesh, n_pages=3,
                      prefix_cache=prefix_on)   # 3 pages = one max row
        assert eng.kv_cache.pages_needed(14 + 8) == 3
        ra = eng.submit(pa, max_new_tokens=8)
        rb = eng.submit(pb, max_new_tokens=8)
        out = eng.run()
        s = eng.stats.summary()
        assert set(out) == {ra, rb}
        assert len(out[ra]) == 8 and len(out[rb]) == 8
        assert s["out_of_pages"] >= 1, prefix_on
        eng.kv_cache.check()


def test_engine_config_validation_and_defaults():
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    hp_prism = ServeHParams(decode_mode="prism", ssm_chunk=8, means_cr=2.0)

    # padded admission predates paging and forces the dense rowset
    cfg = EngineConfig(n_slots=2, prefill_len=8, max_cache=16,
                       prefill_mode="padded")
    assert cfg.paged is False and cfg.prefix_cache is False

    # prefix reuse needs the paged exact cache
    cfg = EngineConfig(n_slots=2, prefill_len=8, max_cache=16, hp=hp_prism)
    assert cfg.paged is True and cfg.prefix_cache is False
    with pytest.raises(ValueError):
        EngineConfig(n_slots=2, prefill_len=8, max_cache=16, hp=hp_prism,
                     prefix_cache=True)
    with pytest.raises(ValueError):
        EngineConfig(n_slots=2, prefill_len=8, max_cache=16,
                     prefill_mode="bogus")
    with pytest.raises(ValueError):
        EngineConfig(n_slots=4, prefill_len=8, max_cache=16,
                     token_budget=2)

    # config and legacy kwargs are mutually exclusive
    with pytest.raises(TypeError):
        ServingEngine(TINY, mesh, params,
                      EngineConfig(n_slots=2, prefill_len=8, max_cache=16),
                      n_slots=2)

    # legacy kwargs still construct (the shim builds the EngineConfig)
    eng = ServingEngine(TINY, mesh, params, n_slots=2, prefill_len=8,
                        max_cache=16)
    assert eng.config.paged is True
    assert eng.kv_cache.paged
    # page geometry: spans cover the row exactly
    pg = eng.kv_cache.paging
    assert pg.span * pg.pages_per_row == eng.layout.cap
    n_seq = seq_shards(mesh, 2)
    assert pg.n_seq == n_seq
