"""Standalone runner: the continuous-batching engine on a (2,4) mesh —
6 staggered requests through 4 slots must terminate with exactly the
tokens one-at-a-time serving produces, in BOTH decode modes (exact
flash-decoding and the paper-faithful prism Segment-Means cache), with
the prompt split across MULTIPLE prefill chunks (chunk_len < prompt
length) AND in token-packed mode (one ragged mixed prefill+decode
program per tick, token_budget not a multiple of the live token
count), so prefill tokens of different requests pack into the same
tick as in-flight decodes.

All paths run the identical per-token computation (packed/chunk rows
are request-isolated, decode rows are owner-masked, and the cache is
addressed purely by (slot, position)), so greedy token ids match
bit-for-bit regardless of which slot a request lands in, which other
requests share the tick, or how its prompt was split.  Exact mode is
additionally pinned against a teacher-forced ``T.forward`` oracle
that shares none of the serving code.

The (2,4) mesh matters doubly for packed mode: the cache batch dim is
sharded over 'data', so packed tokens must route their writes/reads to
the one (batch, sequence) shard pair owning their cache address — the
replicated-token, psum-over-all-axes path this runner pins.

The packed cells additionally pin the async streaming loop
(``serving/streaming.py``): the double-buffered engine — device-side
argmax, speculative next-tick dispatch, single ``ResultTokens`` copy
home per tick — must stream exactly the synchronous engine's tokens on
the same sharded mesh, in exact AND prism decode modes.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import transformer as T
from repro.runtime.serve import ServeHParams
from repro.serving import ServingEngine
from repro.launch.mesh import make_mesh


CFG = ModelConfig(
    name="tiny-dense", arch_type="dense", n_layers=2, d_model=64,
    n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=64,
    mlp_kind="swiglu", norm_kind="rmsnorm", pos="rope",
    tie_embeddings=True)


def check(mode: str, chunk_len: int, *, ground_truth: bool = False,
          prefill_mode: str = "chunked", token_budget: int = 11,
          paged: bool = True) -> bool:
    mesh = make_mesh((2, 4), ("data", "model"))
    params = T.init(CFG, jax.random.PRNGKey(0))
    hp = ServeHParams(decode_mode=mode, ssm_chunk=8, means_cr=4.0)
    kw = dict(n_slots=4, prefill_len=32, max_cache=48, hp=hp,
              chunk_len=chunk_len, prefill_mode=prefill_mode,
              token_budget=token_budget)
    tag = f"{mode}/{prefill_mode}/c{chunk_len}"

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, CFG.vocab_size,
                            size=int(rng.integers(8, 33))).tolist()
               for _ in range(6)]

    eng = ServingEngine(CFG, mesh, params, paged=paged, **kw)
    for p in prompts[:4]:
        eng.submit(p, max_new_tokens=8)
    for _ in range(4):                       # decode before late arrivals
        eng.step()
    for p in prompts[4:]:
        eng.submit(p, max_new_tokens=8)
    concurrent = eng.run()

    # the sequential oracle runs on the DENSE rowset, so this check
    # doubles as the paged ≡ unpaged equivalence pin (exact mode is
    # further pinned below against T.forward, which shares no serving
    # code at all)
    seq = ServingEngine(CFG, mesh, params, paged=False, **kw)
    ok = True
    for i, p in enumerate(prompts):
        rid = seq.submit(p, max_new_tokens=8)
        out = seq.run()[rid]
        match = concurrent[i] == out
        ok &= match
        print(f"[{tag}] request {i}: {'OK' if match else 'MISMATCH'} "
              f"{concurrent[i]} vs {out}")
    s = eng.stats.summary()
    ok &= eng.stats.completed == 6 and s["occupancy"] > 0
    if prefill_mode == "packed":
        # prompts of 8..32 tokens against a ragged budget of 11 mixed
        # tokens must spread over several packed ticks
        ok &= s["packed_ticks"] > 6
        ok &= s["packed_prefill_tokens"] == s["prefill_tokens"]
    elif chunk_len < 32:
        # prompts of 8..32 tokens at chunk_len < 8 must take > 1 chunk
        ok &= s["prefill_chunks"] > 6
    print(f"[{tag}] occupancy={s['occupancy']:.2f} "
          f"prefills={s['prefills']} chunks={s['prefill_chunks']} "
          f"packed_ticks={s['packed_ticks']} "
          f"prefill_tokens={s['prefill_tokens']} "
          f"decode_steps={s['decode_steps']}")

    if prefill_mode == "packed" and paged:
        # streamed ≡ sync on the sharded mesh: the double-buffered
        # overlapped streaming loop (serving/streaming.py) replays the
        # same staggered trace — the device-side argmax carried home in
        # each tick's ResultTokens array must reproduce the synchronous
        # engine's host-sampled tokens bit-for-bit, per stream, in BOTH
        # decode modes (the merge/pack programs run under the same
        # GSPMD partitioning as the packed tick itself)
        from repro.serving import StreamingEngine
        eng_s = ServingEngine(CFG, mesh, params, paged=True, **kw)
        seng = StreamingEngine(eng_s, overlap=True)
        streams = []
        for p in prompts[:4]:
            streams.append(seng.submit_stream(p, max_new_tokens=8)[1])
        for _ in range(4):                   # stagger, as in the oracle
            seng.step()
        for p in prompts[4:]:
            streams.append(seng.submit_stream(p, max_new_tokens=8)[1])
        streamed = seng.run_sync()
        match = streamed == concurrent
        ok &= match
        ok &= all(streams[i].drain() == concurrent[i] for i in range(6))
        ok &= (eng_s.stats.tokens_streamed
               == sum(len(v) for v in concurrent.values()))
        print(f"[{tag}] streamed-vs-sync: "
              f"{'OK' if match else 'MISMATCH'} "
              f"(tokens_streamed={eng_s.stats.tokens_streamed}, "
              f"ticks_idle={eng_s.stats.ticks_idle})")

    if ground_truth:
        # exact mode only: pin against teacher-forced full forward
        for i in (0, 1):
            toks = list(prompts[i])
            for _ in range(8):
                logits, _ = T.forward(CFG, params, jnp.asarray([toks]),
                                      chunk=8)
                toks.append(int(np.argmax(np.asarray(logits[0, -1]))))
            want = toks[len(prompts[i]):]
            match = concurrent[i] == want
            ok &= match
            print(f"[{tag}] request {i} vs T.forward: "
                  f"{'OK' if match else 'MISMATCH'}")

    if paged:
        # forced-preemption variant: the same trace with the host
        # offload tier on, every request spilled to the KVStore once
        # mid-decode (pages + prism kz/vz/gz/zsum state in one
        # device->host gather) and restored through the page-aware
        # admission path — final tokens must still equal the
        # uninterrupted oracle's, pinning spill/restore bit-equality
        # on the sharded mesh in BOTH decode modes
        pre = ServingEngine(CFG, mesh, params, paged=True, offload=True,
                            **kw)
        for p in prompts[:4]:
            pre.submit(p, max_new_tokens=8)
        for _ in range(4):
            pre.step()
        for p in prompts[4:]:
            pre.submit(p, max_new_tokens=8)
        hit = set()
        for _ in range(2000):
            if not pre._sched.has_work and not pre._pending:
                break
            pre.step()
            for st in list(pre._sched.active.values()):
                rid = st.req.rid
                if (rid not in hit and not st.prefilling
                        and len(st.generated) >= 1 and not st.finished()):
                    assert pre.preempt(rid)
                    hit.add(rid)
        forced = pre.results()
        match = forced == concurrent and len(hit) == 6
        ok &= match
        st6 = pre.stats
        ok &= st6.preemptions >= 6 and st6.restore_hits >= 6
        ok &= st6.restore_misses == 0 and len(pre.kv_store) == 0
        pre.kv_cache.check()
        print(f"[{tag}] forced-preempt: {'OK' if match else 'MISMATCH'} "
              f"(preemptions={st6.preemptions} "
              f"spilled_pages={st6.spilled_pages} "
              f"restore_hits={st6.restore_hits})")

    if paged and prefill_mode == "packed":
        # kill-and-restore: run the engine to a mixed mid-flight moment
        # (some slots decoding, some prefilling, >= 1 request spilled
        # to the host store), journal it with snapshot(), TEAR THE
        # ENGINE DOWN, and restore the journal into a fresh engine —
        # which must finish the trace with tokens identical to the
        # uninterrupted oracle's, in BOTH decode modes on the sharded
        # (2,4) mesh (the prism kz/vz/gz/zsum state rows ride the same
        # journalled gather the offload tier uses)
        eng1 = ServingEngine(CFG, mesh, params, paged=True, offload=True,
                             **kw)
        for p in prompts[:4]:
            eng1.submit(p, max_new_tokens=8)
        for _ in range(200):
            eng1.step()
            act = list(eng1._sched.active.values())
            dec = [st for st in act
                   if not st.prefilling and st.generated
                   and not st.finished()]
            pref = [st for st in act if st.prefilling]
            if len(dec) >= 2 and pref:
                break
        else:
            raise AssertionError("no mixed prefill+decode moment")
        for p in prompts[4:]:
            eng1.submit(p, max_new_tokens=8)
        assert eng1.preempt(dec[0].req.rid)       # >= 1 spilled
        assert len(eng1.kv_store) == 1
        snap = eng1.snapshot()
        n_active = len(snap.active)
        del eng1                                  # the crash

        eng2 = ServingEngine(CFG, mesh, params, paged=True, offload=True,
                             **kw)
        eng2.restore(snap)
        assert len(eng2._sched.active) == n_active
        assert len(eng2.kv_store) == 1
        restored = eng2.run()
        match = restored == concurrent
        ok &= match
        ok &= eng2.stats.restore_misses == 0
        ok &= eng2.stats.completed == 6 and len(eng2.kv_store) == 0
        eng2.kv_cache.check()
        print(f"[{tag}] kill-and-restore: "
              f"{'OK' if match else 'MISMATCH'} "
              f"(journalled {n_active} live slots + 1 spilled; "
              f"restore_hits={eng2.stats.restore_hits})")
    return ok


def check_degraded(mode: str) -> bool:
    """Degraded-mesh cell: kill one sequence shard mid-decode on the
    (2,4) mesh and pin the shard-loss contract in BOTH decode modes —

    * every stream terminates, finite, with exactly ``max_new`` tokens
      (the first few from the Segment-Means standby-replica substitute
      path, the rest exact after recovery);
    * recovered / re-prefilled requests finish token-identical to the
      uninterrupted oracle (``results()`` compares ALL requests,
      including ones admitted after recovery);
    * the degraded window is observable (``shard_lost >= 1``,
      ``degraded_ticks >= 1``) and the drained engine is leak-free.

    The StreamingEngine wrapper runs synchronously here by
    construction (any FaultPlan disables overlap — chaos semantics are
    per synchronous tick), which is exactly the drain the degraded
    window requires."""
    from repro.runtime.faults import FaultPlan, FaultSpec
    from repro.serving import StreamingEngine

    mesh = make_mesh((2, 4), ("data", "model"))
    params = T.init(CFG, jax.random.PRNGKey(0))
    hp = ServeHParams(decode_mode=mode, ssm_chunk=8, means_cr=4.0)
    kw = dict(n_slots=4, prefill_len=32, max_cache=48, hp=hp,
              chunk_len=8, prefill_mode="packed", token_budget=11,
              paged=True)
    tag = f"{mode}/degraded"
    gen = 8

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, CFG.vocab_size,
                            size=int(rng.integers(8, 33))).tolist()
               for _ in range(6)]

    oracle_eng = ServingEngine(CFG, mesh, params, **kw)
    for p in prompts:
        oracle_eng.submit(p, max_new_tokens=gen)
    oracle = oracle_eng.run()

    ok = True
    for shard in (0, 1):
        plan = FaultPlan(shard_loss=FaultSpec(at=(6,), shard=shard))
        eng = ServingEngine(CFG, mesh, params, faults=plan, **kw)
        seng = StreamingEngine(eng)
        assert not seng.overlap        # injector forces sync ticks
        streams = []
        for p in prompts[:4]:          # in flight when the shard dies
            streams.append(seng.submit_stream(p, max_new_tokens=gen)[1])
        kinds = []
        for _ in range(2000):
            kinds.append(seng.step())
            if len(prompts) > len(streams) and "recovered" in kinds:
                # admitted strictly after recovery: must be exact
                for p in prompts[4:]:
                    streams.append(
                        seng.submit_stream(p, max_new_tokens=gen)[1])
            if not seng.has_work:
                break
        got = eng.results()
        match = got == oracle
        ok &= match
        delivered = [s.drain() for s in streams]
        finite = all(len(d) == gen and all(isinstance(t, int) for t in d)
                     for d in delivered)
        ok &= finite
        ok &= all(s.finished in ("length", "eos") for s in streams)
        # post-recovery suffix of every stream is exact: it can only
        # contain tokens re-derived by the deterministic re-prefill
        ok &= all(d[-1] == oracle[i][-1]
                  for i, d in enumerate(delivered))
        s = eng.stats.summary()
        ok &= s["shard_lost"] >= 1 and s["degraded_ticks"] >= 1
        ok &= "degraded" in kinds and "recovered" in kinds
        # zero-leak audit (same checks as the chaos drill)
        kv = eng.kv_cache
        kv.check()
        leak_free = (not kv.slot_pages and not kv.slot_state
                     and sorted(eng._sched.free_slots) == list(range(4)))
        ok &= leak_free
        print(f"[{tag}] shard {shard} dies at tick 6: "
              f"{'OK' if match else 'MISMATCH'} streams_finite="
              f"{finite} leak_free={leak_free} "
              f"shard_lost={s['shard_lost']} "
              f"degraded_ticks={s['degraded_ticks']} "
              f"restarts={s['restarts']}")
    return ok


def main():
    ok = check("exact", 64)                # clamps to prefill_len: 1 flush
    ok &= check("exact", 8, ground_truth=True)   # 1-4 chunks per prompt
    ok &= check("prism", 8)
    # token-packed ticks: ragged 11-token budget of mixed prefill +
    # decode work, batch dim sharded over 'data' — both decode modes,
    # exact additionally vs the teacher-forced oracle
    ok &= check("exact", 8, ground_truth=True, prefill_mode="packed")
    ok &= check("prism", 8, prefill_mode="packed")
    # degraded-mesh serving: a sequence shard dies mid-decode; streams
    # stay finite through the Segment-Means standby replicas and
    # recovery returns to token-exact serving
    ok &= check_degraded("exact")
    ok &= check_degraded("prism")
    print("ALL OK" if ok else "ENGINE FAILURES")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
