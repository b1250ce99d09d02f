"""Serving launcher: static batch (prefill + greedy decode) or the
continuous-batching engine on a synthetic Poisson arrival trace.

    # static batch
    PYTHONPATH=src python -m repro.launch.serve --arch gpt2-small \
        --reduced --batch 8 --prompt-len 64 --gen 16 --mesh 2x4 \
        --decode-mode exact

    # request-level engine, Poisson arrivals
    PYTHONPATH=src python -m repro.launch.serve --arch gpt2-small \
        --reduced --engine --requests 32 --rate 4 --batch 8 \
        --prompt-len 64 --gen 16 --mesh 2x4
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-small")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8,
                    help="static batch size / engine slot count")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", default=None,
                    help="DATAxMODEL (default: 1x<device count>)")
    ap.add_argument("--decode-mode", default="exact",
                    choices=("exact", "prism"))
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "pallas", "jnp"),
                    help="kernel dispatch: auto = Pallas compiled on "
                         "TPU, jnp elsewhere; pallas forces the kernels "
                         "(interpret mode off-TPU); jnp forces the "
                         "oracle path")
    ap.add_argument("--cr", type=float, default=4.0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine over a Poisson trace")
    ap.add_argument("--requests", type=int, default=32,
                    help="[engine] number of requests in the trace")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="[engine] Poisson arrival rate, requests/s")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--gang", action="store_true",
                    help="[engine] static-batching admission (baseline)")
    ap.add_argument("--chunk-len", type=int, default=64,
                    help="[engine] prefill chunk size (clamped to the "
                         "prefill length)")
    ap.add_argument("--prefill-mode", default="packed",
                    choices=("packed", "chunked", "padded"),
                    help="[engine] token-packed unified ticks "
                         "(default), chunked prefill, or the legacy "
                         "pad-to-length admission flush")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="[engine, packed] tokens per packed tick "
                         "(default: slots + chunk-len); must be >= the "
                         "slot count")
    ap.add_argument("--no-paged", action="store_true",
                    help="[engine] dense slot-row cache instead of the "
                         "paged page-table pool")
    ap.add_argument("--page-tokens", type=int, default=None,
                    help="[engine, paged] page size in token positions "
                         "(default: derived, ~16-token spans)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="[engine, paged] physical pool pages (default: "
                         "memory parity with the dense rows)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="[engine, paged] disable shared-prefix COW "
                         "reuse (on by default in exact decode mode)")
    ap.add_argument("--offload", action="store_true",
                    help="[engine, paged] host KV offload tier: blocked "
                         "higher-priority arrivals preempt lower-priority "
                         "work (spill to host memory, restore on resume)")
    ap.add_argument("--priority", type=int, default=1, metavar="CLASSES",
                    help="[engine] priority classes in the synthetic "
                         "trace — each request draws uniform [0, CLASSES)"
                         " (higher = more urgent; 1 = plain FIFO)")
    ap.add_argument("--stream", action="store_true",
                    help="[engine] asyncio streaming front-end: tokens "
                         "stream per request while the double-buffered "
                         "loop overlaps host and device work "
                         "(docs/streaming.md)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="[engine, stream] disable double-buffered "
                         "dispatch (synchronous ticks; tokens still "
                         "stream) — the A/B baseline for the overlap win")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="[engine] seeded fault injection: run the "
                         "trace under FaultPlan.chaos(SEED) — store "
                         "put/get loss, page poisoning, admission "
                         "stalls, tick delays, shard loss — and audit "
                         "zero leaks after the drain")
    ap.add_argument("--shard-loss", type=int, default=None,
                    metavar="SHARD",
                    help="[engine] degraded-mesh drill: kill sequence "
                         "shard SHARD's KV mid-trace (scheduled "
                         "shard_loss fault) — in-flight requests serve "
                         "through the Segment-Means standby replicas, "
                         "then recover by deterministic re-prefill; "
                         "zero-leak audited like --chaos")
    ap.add_argument("--shard-loss-at", type=int, default=6, metavar="N",
                    help="[engine] shard_loss fires at the Nth "
                         "opportunity (engine tick with work; "
                         "default 6)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.protocol import PrismConfig
    from repro.models import transformer as T
    from repro.runtime.serve import (ServeHParams, make_prefill_step,
                                     make_serve_step)
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_mesh

    use_compile_cache()
    mesh_arg = args.mesh or f"1x{len(jax.devices())}"
    data, model = (int(x) for x in mesh_arg.split("x"))
    mesh = make_mesh((data, model), ("data", "model"))
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(0)
    params = T.init(cfg, key)
    if args.checkpoint:
        from repro.checkpoint.io import restore_checkpoint, latest_step
        step_n = latest_step(args.checkpoint)
        params = restore_checkpoint(args.checkpoint, step_n, params)
        print(f"[serve] restored step {step_n}")

    from repro.runtime.serve import seq_shards
    n_seq = seq_shards(mesh, args.batch)
    n = args.prompt_len - args.prompt_len % n_seq
    cap = n + args.gen + (-(n + args.gen)) % n_seq
    hp = ServeHParams(decode_mode=args.decode_mode, means_cr=args.cr,
                      backend=args.backend)
    prism = PrismConfig(
        P=model, cr=args.cr,
        mode="prism" if args.decode_mode == "prism" else "voltage")

    if args.engine:
        from repro.serving import (EngineConfig, FaultPlan, SamplingParams,
                                   ServingEngine)
        from repro.runtime.faults import FaultSpec
        faults = (FaultPlan.chaos(args.chaos)
                  if args.chaos is not None else None)
        if args.shard_loss is not None:
            spec = FaultSpec(at=(args.shard_loss_at,),
                             shard=args.shard_loss)
            faults = (FaultPlan(shard_loss=spec) if faults is None
                      else FaultPlan.chaos(args.chaos, shard_loss=spec))
        ecfg = EngineConfig(
            n_slots=args.batch, prefill_len=n, max_cache=cap, hp=hp,
            prism=prism, gang=args.gang, chunk_len=args.chunk_len,
            prefill_mode=args.prefill_mode,
            token_budget=args.token_budget,
            paged=not args.no_paged, page_tokens=args.page_tokens,
            n_pages=args.n_pages,
            prefix_cache=False if args.no_prefix_cache else None,
            offload=args.offload, faults=faults,
            max_restarts=8 if faults is not None else 3)
        eng = ServingEngine(cfg, mesh, params, ecfg)
        seng = None
        if args.stream:
            from repro.serving import StreamingEngine
            seng = StreamingEngine(eng, overlap=not args.no_overlap)
        rng = np.random.default_rng(0)
        arrivals = np.cumsum(rng.exponential(1.0 / args.rate,
                                             size=args.requests))
        streams = {}
        for i in range(args.requests):
            plen = int(rng.integers(max(1, n // 2), n + 1))
            prompt = rng.integers(1, cfg.vocab_size, size=plen)
            kw = dict(max_new_tokens=args.gen,
                      sampling=SamplingParams(temperature=args.temperature,
                                              top_k=args.top_k, seed=i),
                      arrival=float(arrivals[i]),
                      priority=int(rng.integers(0, max(1, args.priority))))
            if seng is not None:
                rid, stream = seng.submit_stream(prompt, **kw)
                streams[rid] = stream
            else:
                eng.submit(prompt, **kw)
        mode = "gang (static)" if args.gang else "continuous"
        extras = (f", {args.priority} priority classes"
                  if args.priority > 1 else "")
        extras += ", host offload" if args.offload else ""
        extras += (f", chaos seed {args.chaos}"
                   if args.chaos is not None else "")
        extras += (f", shard {args.shard_loss} dies at tick "
                   f"{args.shard_loss_at}"
                   if args.shard_loss is not None else "")
        extras += (", streaming" + (" (overlap off)" if args.no_overlap
                                    else " (overlap)")
                   if args.stream else "")
        print(f"[engine] {args.requests} requests, Poisson rate "
              f"{args.rate}/s, {args.batch} slots, {mode} admission"
              f"{extras}")
        if seng is not None:
            import asyncio

            async def _drive():
                loop = asyncio.get_running_loop()
                got = {}

                async def consume(rid, stream):
                    toks = []
                    async for t in stream:
                        toks.append(t)
                    got[rid] = (toks, stream.finished)

                tasks = [asyncio.ensure_future(consume(rid, s))
                         for rid, s in streams.items()]
                while seng.has_work:
                    kind = await loop.run_in_executor(None, seng.step)
                    if kind == "idle":
                        await asyncio.sleep(0.002)
                seng.drain()
                seng._flush_streams()
                await asyncio.gather(*tasks)
                return got

            got = asyncio.run(_drive())
            fins = {}
            for toks, fin in got.values():
                fins[fin] = fins.get(fin, 0) + 1
            print(f"[stream] {len(got)} streams closed: "
                  + ", ".join(f"{k}={v}" for k, v in sorted(fins.items())))
            itl = seng.itl_samples()
            if itl:
                print(f"[stream] itl_p50_s {float(np.percentile(itl, 50)):.4f}"
                      f"  itl_p99_s {float(np.percentile(itl, 99)):.4f}")
        else:
            eng.run()
        for k, v in eng.stats.summary().items():
            print(f"[engine] {k:22s} {v:.3f}"
                  if isinstance(v, float) else f"[engine] {k:22s} {v}")
        if faults is not None:
            inj = eng._injector
            print(f"[chaos] injected {inj.stats()['injected']} over "
                  f"{inj.stats()['ops']} opportunities")
            done = len(eng.results())
            failed = eng.failed()
            print(f"[chaos] completed {done}/{args.requests}, "
                  f"failed {len(failed)} {sorted(failed.values())}")
            assert done + len(failed) == args.requests, (
                done, failed, args.requests)
            # zero-leak audits: page refcounts consistent, no slot
            # holds pages, store drained, every slot back in the pool
            kv = eng.kv_cache
            kv.check()
            assert not kv.slot_pages and not kv.slot_state
            if eng.kv_store is not None:
                assert len(eng.kv_store) == 0, eng.kv_store.stats()
            assert sorted(eng._sched.free_slots) == list(
                range(args.batch))
            print("[chaos] zero-leak audits OK")
            if args.shard_loss is not None:
                s = eng.stats
                rep = (eng._replica.stats()
                       if eng._replica is not None else {})
                print(f"[degraded] shard_lost {s.shard_lost} "
                      f"degraded_ticks {s.degraded_ticks} "
                      f"restarts {s.restarts} "
                      f"replica_captures {rep.get('captures', 0)}")
                assert s.shard_lost >= 1, "shard_loss never fired"
        return

    prompts = np.random.default_rng(0).integers(
        1, cfg.vocab_size, size=(args.batch, n)).astype(np.int32)

    # the prefill program captures its cache rows straight at decode
    # capacity (cap=...), so the old grow-to-capacity pad is gone
    prefill, lay_p, _, _ = make_prefill_step(
        cfg, mesh, params, prism, batch=args.batch, n=n, hp=hp, cap=cap)
    t0 = time.time()
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompts)})
    logits.block_until_ready()
    print(f"[serve] prefill {args.batch}x{n}: {time.time() - t0:.2f}s "
          f"({args.decode_mode} cache)")

    step, lay_d, _, _ = make_serve_step(
        cfg, mesh, params, batch=args.batch, cap=cap, prefill_len=n, hp=hp)
    assert lay_p == lay_d, (lay_p, lay_d)

    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [np.asarray(tok)]
    t0 = time.time()
    for g in range(args.gen - 1):
        pos = jnp.full((args.batch,), n + g, jnp.int32)
        logits, cache = step(params, cache, tok, pos)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(np.asarray(tok))
    jax.block_until_ready(tok)
    dt = time.time() - t0
    print(f"[serve] decoded {args.gen - 1} steps in {dt:.2f}s "
          f"({1e3 * dt / max(1, args.gen - 1):.1f} ms/token)")
    gen = np.stack(out, axis=1)
    print("[serve] generated token ids (first 2 rows):")
    print(gen[:2])


if __name__ == "__main__":
    main()
