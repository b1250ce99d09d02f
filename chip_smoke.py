"""Chip smoke test: the PRISM serving engine on a TPU, end to end.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sequence-sharded path, 1x4 mesh

One chip: gpt2-small at its published width (12 layers, d_model 768,
vocab 50257, random weights from a seed, float32) is served through
``StreamingEngine(ServingEngine(...))`` with packed ticks, the paged KV
cache and exact decode — the path ``python -m repro.launch.serve
--engine --stream`` takes.  Sixteen greedy requests with 64-512-token
prompts over eight slots are checked against a plain ``T.forward`` pass
on the same chip: the tokens, and the logits of every request's
prefill and first decode steps.

Four chips (``--chips 4``): the same model on a 1x4 mesh, where PRISM's
position-wise partitioning of the sequence and the cross-chip
flash-decode combine exist.  Exact decode is compared with
single-device reference logits; prism decode at CR 4 must give finite
outputs and the same tokens served concurrently as one at a time.  The
script checks that every chip holds its share of the work and that the
compiled programs carry collectives.  Only this phase runs.

Both phases fail, and print no result, when JAX finds no TPU, when the
kernel backend does not resolve to the compiled Pallas kernels, or when
the compiled step programs do not contain them.  Everything runs in
this one process.  The last line of standard output is the JSON result.
The compile cache sits at ``$JAX_COMPILATION_CACHE_DIR`` when set,
else at ``.jax_cache`` in this checkout.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ARCH = "gpt2-small"
SEED = 0
N_SLOTS = 8
N_REQUESTS = 16
PROMPT_LENS = (64, 512)       # inclusive range of prompt lengths
MAX_NEW = 32
MAX_CACHE = 576               # longest prompt + MAX_NEW, page-aligned
PROBE_STEPS = 4               # prefill logits + 3 decode steps each
# Logits tolerance, relative to each row's largest reference logit.
# Both sides run float32 under matmul precision "highest"; they differ
# only in reduction order (online-softmax flash-decode over the paged
# cache against one dense softmax), worth ~1e-5 relative after 12
# layers.  A masking, position or cache-placement fault moves logits by
# the order of the row's scale, far beyond this bound.
LOGIT_RTOL = 1e-3
# Greedy tokens are an argmax: a near-tie between the top two logits
# (gap below the logits error) may flip one, and the reference is
# teacher-forced on the served tokens, so a flip stays one token.  At
# most 2 flips in 512 tokens: float32 at "highest" read 1.0000 on one
# chip (512 tokens) and on four (256); the same engine at the default
# matmul precision read 0.9844 (8 flips), which this bound refuses.
MIN_TOKEN_AGREEMENT = 0.995
KERNEL_MARK = "tpu_custom_call"
# JAX monitoring events: every compile or compile-cache load, and the
# persistent-cache hits among them.
COMPILE_EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
                  "/jax/compilation_cache/compile_time_saved_sec": "hits"}
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


compile_log = {name: [0, 0.0] for name in COMPILE_EVENTS.values()}


def watch_compiles() -> None:
    """Count compiles and persistent-cache hits into ``compile_log``
    as ``[count, seconds]``; hits count the compile seconds saved."""
    import jax

    def on_duration(event, secs, **_):
        name = COMPILE_EVENTS.get(event)
        if name:
            compile_log[name][0] += 1
            compile_log[name][1] += secs
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def make_requests(cfg, n: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_LENS
    return [rng.integers(1, cfg.vocab_size, size=int(rng.integers(lo, hi + 1)))
            .astype(np.int32) for _ in range(n)]


def build_engine(cfg, mesh, params, decode_mode: str):
    from repro.core.protocol import PrismConfig
    from repro.runtime.serve import ServeHParams
    from repro.serving import EngineConfig, ServingEngine

    class RecordingEngine(ServingEngine):
        """Records the logits row behind every token sampled on the
        host (synchronous ticks); streamed ticks argmax on device."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.logit_rows: dict = {}

        def _advance_decode(self, st, logits_row, now):
            self.logit_rows.setdefault(st.req.rid, []).append(
                np.array(logits_row, np.float32))
            super()._advance_decode(st, logits_row, now)

    p = mesh.shape["model"]
    return RecordingEngine(cfg, mesh, params, EngineConfig(
        n_slots=N_SLOTS, prefill_len=PROMPT_LENS[1], max_cache=MAX_CACHE,
        hp=ServeHParams(decode_mode=decode_mode, means_cr=4.0),
        prism=PrismConfig(P=p, cr=4.0, mode="prism"
                          if decode_mode == "prism" else "voltage")))


def compile_programs(eng) -> dict:
    """Compile the engine's packed and decode programs ahead of time,
    with arguments of the shapes a tick passes (the programs fix their
    input shardings), and return each one's compile seconds and
    compiled text.  The engine's first calls lower the same programs
    and can load them from the persistent compile cache;
    ``serve_streaming`` logs the cache hits it sees."""
    import jax.numpy as jnp
    tb, b = eng.token_budget, eng.n_slots
    vec = lambda n: jnp.zeros((n,), jnp.int32)           # noqa: E731
    storage, maps = eng.kv_cache.storage, eng._maps()
    out = {}
    for kind, prog, args in (
            ("packed", eng._packed,
             (eng.params, storage, *[vec(tb)] * 5, *maps)),
            ("decode", eng._step, (eng.params, storage, vec(b), vec(b),
                                   *maps))):
        t0 = time.perf_counter()
        text = prog.lower(*args).compile().as_text()
        out[kind] = (time.perf_counter() - t0, text)
    return out


def check_kernels_present(programs: dict) -> None:
    for kind, (secs, text) in programs.items():
        n = text.count(KERNEL_MARK)
        log(f"program {kind}: compiled in {secs:.1f} s, "
            f"{n} Pallas kernel calls ({KERNEL_MARK})")
        if n == 0:
            raise RuntimeError(f"the {kind} program holds no Pallas kernel")


def bytes_in_use(devices) -> list:
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def tree_bytes(tree, devices) -> list:
    """Bytes of ``tree``'s arrays resident on each of ``devices``."""
    import jax
    per = dict.fromkeys(devices, 0)
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            per[shard.device] = per.get(shard.device, 0) + shard.data.nbytes
    return [per[d] for d in devices]


def reference(cfg, params, prompts, generated, device):
    """Teacher-forced ``T.forward`` over each prompt + its generated
    tokens on one device.  Returns, per request, the greedy argmax
    after each prompt position from the last one on (len(generated)),
    and the logits rows of the first PROBE_STEPS of those."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as T

    seqs = [np.concatenate([p, np.asarray(g, np.int32)])
            for p, g in zip(prompts, generated)]
    width = -(-max(len(s) for s in seqs) // 128) * 128
    toks = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s          # causal: the tail pad is never seen
    first = np.asarray([len(p) - 1 for p in prompts], np.int32)
    n_gen = max(len(g) for g in generated)

    @jax.jit
    def run(params, toks, first):
        logits, _ = T.forward(cfg, params, toks)
        idx = first[:, None] + jnp.arange(n_gen)[None]
        rows = jnp.take_along_axis(logits, idx[..., None], axis=1)
        return jnp.argmax(rows, -1), rows[:, :PROBE_STEPS]

    params = jax.device_put(params, device)
    argmax, rows = [], []
    for i in range(0, len(seqs), 4):          # bounds the logits' memory
        a, r = run(params, jax.device_put(toks[i:i + 4], device),
                   jax.device_put(first[i:i + 4], device))
        argmax.append(np.asarray(a))
        rows.append(np.asarray(r))
    argmax, rows = np.concatenate(argmax), np.concatenate(rows)
    return ([argmax[i, :len(g)] for i, g in enumerate(generated)],
            [rows[i] for i in range(len(generated))])


def token_agreement(ref_argmax, generated) -> float:
    hits = sum(int((a == np.asarray(g)).sum())
               for a, g in zip(ref_argmax, generated))
    return hits / sum(len(g) for g in generated)


def serve_streaming(eng, prompts) -> tuple:
    """The main path: every request through the asyncio streaming
    front-end at once.  Returns (tokens per request, wall seconds)."""
    import asyncio
    from repro.serving import StreamingEngine, serve_stream
    seng = StreamingEngine(eng)
    before = {k: list(v) for k, v in compile_log.items()}
    t0 = time.perf_counter()
    out = asyncio.run(serve_stream(seng, [
        dict(prompt=p, max_new_tokens=MAX_NEW) for p in prompts]))
    wall = time.perf_counter() - t0
    (n, secs), (hits, saved) = (
        [a - b for a, b in zip(compile_log[k], before[k])]
        for k in ("compiles", "hits"))
    log(f"while serving: {n} programs compiled or loaded from the cache "
        f"({secs:.1f} s), {hits} of them persistent-cache hits that "
        f"saved {saved:.1f} s of compile")
    rids = sorted(out)
    bad = [r for r in rids if out[r]["finished"] != "length"]
    if bad:
        raise RuntimeError(f"streams {bad} closed early: "
                           f"{[out[r]['finished'] for r in bad]}")
    return [out[r]["tokens"] for r in rids], wall


def probe_logits(eng, prompts, streamed) -> list:
    """Serve every prompt again for PROBE_STEPS tokens on synchronous
    ticks (the same compiled programs; the prompts' full pages hit the
    prefix cache), recording the logits row behind each token; their
    tokens must equal the streamed ones."""
    rids = [eng.submit(p, max_new_tokens=PROBE_STEPS) for p in prompts]
    res = eng.run()
    want = [g[:PROBE_STEPS] for g in streamed]
    if [res[r] for r in rids] != want:
        raise RuntimeError("synchronous ticks disagree with the streamed "
                           "tokens of the same prompts")
    return [np.stack(eng.logit_rows[r]) for r in rids]


def check_logits(tag, got_rows, ref_rows) -> None:
    worst = 0.0
    for i, (got, want) in enumerate(zip(got_rows, ref_rows)):
        if not np.isfinite(got).all():
            raise RuntimeError(f"{tag}: request {i} has non-finite logits")
        scale = np.abs(want).max(axis=-1, keepdims=True)
        err = float((np.abs(got - want) / scale).max())
        worst = max(worst, err)
        if err > LOGIT_RTOL:
            raise RuntimeError(
                f"{tag}: request {i} logits differ from the reference "
                f"by {err:.2e} of the row scale (bound {LOGIT_RTOL})")
    log(f"{tag}: prefill + {PROBE_STEPS - 1} decode logits of "
        f"{len(got_rows)} requests match T.forward, worst relative "
        f"error {worst:.2e} (bound {LOGIT_RTOL})")


def check_tokens(tag, ref_argmax, generated) -> None:
    agree = token_agreement(ref_argmax, generated)
    n = sum(len(g) for g in generated)
    log(f"{tag}: greedy token agreement with T.forward {agree:.4f} "
        f"over {n} tokens")
    if agree < MIN_TOKEN_AGREEMENT:
        raise RuntimeError(f"{tag}: token agreement {agree:.4f} < "
                           f"{MIN_TOKEN_AGREEMENT}")


def one_chip_phase(cfg, params) -> None:
    import jax
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    prompts = make_requests(cfg, N_REQUESTS, SEED)
    eng = build_engine(cfg, mesh, params, "exact")
    check_kernels_present(compile_programs(eng))

    generated, wall = serve_streaming(eng, prompts)
    s = eng.stats
    log(f"smoke timing (not a benchmark result): {N_REQUESTS} requests, "
        f"{s.packed_ticks} packed + {s.decode_steps} decode ticks, "
        f"{s.generated_tokens} tokens, {wall:.2f} s wall "
        f"(first ticks include loading the compiled programs)")

    rows = probe_logits(eng, prompts, generated)
    ref_argmax, ref_rows = reference(cfg, params, prompts, generated,
                                     jax.devices()[0])
    check_tokens("exact 1x1", ref_argmax, generated)
    check_logits("exact 1x1", rows, ref_rows)


def four_chip_phase(cfg, params) -> None:
    import jax
    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    mesh = make_mesh((1, 4), ("data", "model"))
    prompts = make_requests(cfg, N_SLOTS, SEED)

    # exact decode: the cross-chip flash-decode combine
    before = bytes_in_use(devs[:4])
    eng = build_engine(cfg, mesh, params, "exact")
    programs = compile_programs(eng)
    check_kernels_present(programs)
    for kind, (_, text) in programs.items():
        found = {c: text.count(c) for c in COLLECTIVES if c in text}
        log(f"program {kind} collectives: {found}")
        if not found:
            raise RuntimeError(f"the 1x4 {kind} program has no collective")
    generated, wall = serve_streaming(eng, prompts)
    log(f"smoke timing (not a benchmark result): exact 1x4, "
        f"{len(prompts)} requests, {eng.stats.generated_tokens} tokens, "
        f"{wall:.2f} s wall")
    rows = probe_logits(eng, prompts, generated)
    used = [u - b for u, b in zip(bytes_in_use(devs[:4]), before)]
    mib = lambda xs: ", ".join(f"{d.id}: {x / 2**20:.1f} MiB"  # noqa: E731
                               for d, x in zip(devs, xs))
    log(f"bytes the engine holds per device: {mib(used)}")
    kv = tree_bytes(eng.kv_cache.storage, devs[:4])
    log(f"of which the KV storage: {mib(kv)}")
    leaves = jax.tree.leaves(eng.params)
    log(f"weights: {sum(x.nbytes for x in leaves) / 2**20:.1f} MiB on "
        f"devices {sorted({d.id for x in leaves for d in x.devices()})}, "
        f"committed: {sorted({x.committed for x in leaves})}")
    if min(used) <= 0 or min(used) < 0.25 * max(used):
        raise RuntimeError(f"work is not spread over the 4 chips: {used}")
    ref_argmax, ref_rows = reference(cfg, params, prompts, generated,
                                     devs[0])
    check_tokens("exact 1x4", ref_argmax, generated)
    check_logits("exact 1x4 vs single device", rows, ref_rows)
    del eng

    # prism decode at CR 4: Segment-Means columns stand in for the
    # other chips' exact KV, so only finiteness and batch independence
    # are checked, not agreement with the exact reference
    eng = build_engine(cfg, mesh, params, "prism")
    check_kernels_present(compile_programs(eng))
    concurrent, wall = serve_streaming(eng, prompts)
    log(f"smoke timing (not a benchmark result): prism 1x4, "
        f"{len(prompts)} requests, {wall:.2f} s wall")
    one_at_a_time = []
    for p in prompts:
        rid = eng.submit(p, max_new_tokens=MAX_NEW)
        one_at_a_time.append(eng.run()[rid])
    same = sum(a == b for a, b in zip(concurrent, one_at_a_time))
    log(f"prism 1x4: {same}/{len(prompts)} requests give identical tokens "
        f"concurrently and one at a time")
    if same != len(prompts):
        raise RuntimeError("prism decode depends on batch composition")
    bad = eng.stats.quarantined + eng.stats.restarts
    if bad or any(not np.isfinite(r).all() for r in eng.logit_rows.values()):
        raise RuntimeError("prism decode produced non-finite logits")
    log(f"prism 1x4: all logits finite over {len(eng.logit_rows)} "
        f"synchronous requests, no quarantined rows")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the single-chip serving path (default); "
                         "4: only the 1x4 sequence-sharded path")
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax
    from repro.launch.compile_cache import use_compile_cache
    from repro.kernels.dispatch import resolve_backend

    cache = use_compile_cache()
    watch_compiles()
    devs = jax.devices()
    d0 = devs[0]
    log(f"device {d0.platform} {d0.device_kind} x{len(devs)}, "
        f"compile cache {cache}")
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {d0.platform}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} "
              "devices", file=sys.stderr)
        return 1
    if resolve_backend("auto") != "pallas":
        print("chip_smoke: the kernel backend does not resolve to the "
              "compiled Pallas kernels", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = get_config(ARCH)
    log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads, vocab {cfg.vocab_size}, float32, "
        f"seed {SEED}")
    # A TPU's default float32 matmul is not float32: both the engine
    # and the reference run at "highest".  Set process-wide, not with
    # the thread-local context manager: the streaming front-end runs
    # its ticks on an executor thread.
    jax.config.update("jax_default_matmul_precision", "highest")
    t0 = time.perf_counter()
    params = T.init(cfg, jax.random.PRNGKey(SEED))
    if args.chips == 1:
        one_chip_phase(cfg, params)
    else:
        four_chip_phase(cfg, params)
    log(f"all checks passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
