"""Chunked-prefill tests on the single real CPU device (mesh 1x1; the
sharded versions run via tests/engine_equiv_runner.py):

* the chunk program writes the SAME cache the monolithic prefill
  writes (exact mode, mixed per-row offsets, ragged final chunks);
* prism Segment-Means state is captured over REAL columns only — the
  regression test for the old padded-prefill wart where a short
  prompt's kz/vz averaged pad columns;
* engine-level: prompt lengths exactly at / off chunk boundaries match
  a teacher-forced ``T.forward`` oracle, and the legacy padded mode
  still serves correctly.
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core.protocol import PrismConfig
from repro.core.segment_means import segment_fill_counts
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.runtime.serve import (ServeHParams, grow_cache, init_cache,
                                 make_chunk_prefill_step,
                                 make_prefill_step, make_serve_step)
from repro.serving import ServingEngine
from repro.launch.mesh import make_mesh


TINY = ModelConfig(
    name="tiny-serve", arch_type="dense", n_layers=2, d_model=32,
    n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=61,
    mlp_kind="swiglu", norm_kind="rmsnorm", pos="rope",
    tie_embeddings=True)


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def _chunk_fill(chunk, params, cache, prompts, chunk_len, n_slots):
    """Drive the chunk program like the engine does: every mid-prefill
    row advances each call, rows at different offsets."""
    prog = {s: 0 for s in prompts}               # slot -> offset
    while any(prog[s] < len(p) for s, p in prompts.items()):
        toks = np.zeros((n_slots, chunk_len), np.int32)
        off = np.full(n_slots, -1, np.int32)
        nreal = np.zeros(n_slots, np.int32)
        for s, p in prompts.items():
            if prog[s] >= len(p):
                continue
            take = min(chunk_len, len(p) - prog[s])
            toks[s, :take] = p[prog[s]:prog[s] + take]
            off[s] = prog[s]
            nreal[s] = take
            prog[s] += take
        cache = chunk(params, cache, jnp.asarray(toks), jnp.asarray(off),
                      jnp.asarray(nreal))
    return cache


def test_chunked_cache_matches_monolithic_exact():
    """Chunked prefill (ragged chunks, rows at different offsets) lays
    down bit-comparable K/V to the monolithic prefill, and the decode
    logits from both caches agree."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    n0, cap, C, B = 8, 16, 3, 4                   # 3 does not divide 8
    hp = ServeHParams(decode_mode="exact", ssm_chunk=8)
    prism = PrismConfig(P=1, mode="voltage")
    rng = np.random.default_rng(0)
    p1 = rng.integers(1, TINY.vocab_size, size=n0)
    p3 = rng.integers(1, TINY.vocab_size, size=5)

    pre, lp, _, _ = make_prefill_step(TINY, mesh, params, prism,
                                      batch=B, n=n0, hp=hp)
    batch = np.zeros((B, n0), np.int32)
    batch[1], batch[3, :5] = p1, p3
    _, ref = pre(params, {"tokens": jnp.asarray(batch)})
    step, ld, _, _ = make_serve_step(TINY, mesh, params, batch=B,
                                     cap=cap, prefill_len=n0, hp=hp)
    ref = grow_cache(ref, lp, ld)

    chunk, lc, _ = make_chunk_prefill_step(
        TINY, mesh, params, batch=B, cap=cap, prefill_len=n0,
        chunk_len=C, hp=hp)
    assert lc == ld
    got = _chunk_fill(chunk, params, init_cache(TINY, ld, B, hp),
                      {1: p1, 3: p3}, C, B)

    for u in range(2):
        for key in ("k", "v"):
            a = np.asarray(ref["scan"][0][key][u])   # (B, cap, H, hd)
            b = np.asarray(got["scan"][0][key][u])
            # row 1: all n0 positions real; row 3: first 5 real
            assert np.abs(a[1, :n0] - b[1, :n0]).max() < 1e-5, (u, key)
            assert np.abs(a[3, :5] - b[3, :5]).max() < 1e-5, (u, key)

    # decode from both caches: teacher-forced logits agree
    tok = np.array([0, p1[-1], 0, 0], np.int32)
    pos = np.array([-1, n0 - 1, -1, -1], np.int32)
    la, ref = step(params, ref, jnp.asarray(tok), jnp.asarray(pos))
    lb, got = step(params, got, jnp.asarray(tok), jnp.asarray(pos))
    a, b = np.asarray(la[1]), np.asarray(lb[1])
    assert np.abs(a - b).max() / np.abs(a).max() < 1e-5


def test_prism_means_capture_real_columns_only():
    """THE regression test for the padded-prefill wart: a short
    prompt's Segment-Means state must match the UNPADDED reference —
    counts are real-token counts, sums/values average no pad column.
    (The monolithic voltage prefill at n = len(prompt) computes the
    same quantities over a prompt that needs no padding; vz and zsum
    carry no positional encoding, so they must agree exactly.)"""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    n0, cap, plen = 8, 16, 6
    hp = ServeHParams(decode_mode="prism", ssm_chunk=8, means_cr=8.0)
    prompt = np.asarray([7, 19, 3, 42, 11, 23], np.int32)
    assert plen == len(prompt)

    # engine-style chunked prefill into an n0 = 8 slot (L = 1 segment)
    chunk, lay, _ = make_chunk_prefill_step(
        TINY, mesh, params, batch=2, cap=cap, prefill_len=n0,
        chunk_len=4, hp=hp)
    assert lay.L == 1
    cache = _chunk_fill(chunk, params, init_cache(TINY, lay, 2, hp),
                        {0: prompt}, 4, 2)

    # unpadded reference: monolithic voltage prefill over exactly plen
    # tokens — same single segment over only-real columns
    prism = PrismConfig(P=1, cr=8.0, mode="voltage")
    pre, lpp, _, _ = make_prefill_step(TINY, mesh, params, prism,
                                       batch=1, n=plen, hp=hp)
    _, ref = pre(params, {"tokens": jnp.asarray(prompt[None])})

    for u in range(2):
        gz = np.asarray(cache["scan"][0]["gz"][u, 0])
        assert gz.tolist() == [float(plen)], gz   # real count, NOT n0
        for key in ("vz", "zsum"):
            a = np.asarray(ref["scan"][0][key][u, 0])
            b = np.asarray(cache["scan"][0][key][u, 0])
            scale = max(np.abs(a).max(), 1e-6)
            assert np.abs(a - b).max() / scale < 1e-5, (u, key)

    # the counts the engine wrote == the analytic fill counts
    from repro.runtime.serve import _means_meta
    lo, hi, _, _, _ = _means_meta(lay)
    want = segment_fill_counts(lo, hi, plen)
    assert np.allclose(np.asarray(cache["scan"][0]["gz"][0, 0]),
                       np.asarray(want))


def test_padded_mode_prism_gz_shows_the_wart():
    """The legacy padded flush captures means over the whole padded
    region: gz reports the full segment size even though the prompt is
    shorter — exactly what the chunked path fixes."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    hp = ServeHParams(decode_mode="prism", ssm_chunk=8, means_cr=8.0)
    eng = ServingEngine(TINY, mesh, params, n_slots=2, prefill_len=8,
                        max_cache=16, hp=hp, prefill_mode="padded")
    eng.submit([7, 19, 3, 42, 11, 23], max_new_tokens=1)
    eng.run()
    gz = np.asarray(eng.kv_cache.storage["scan"][0]["gz"][0, 0])
    assert gz.tolist() == [8.0], gz               # pads counted: the wart

    # paged=False: the gz-by-slot-row read below is dense-layout
    # addressing (the paged prism engine pools this state per request)
    eng2 = ServingEngine(TINY, mesh, params, n_slots=2, prefill_len=8,
                         max_cache=16, hp=hp, chunk_len=4, paged=False)
    eng2.submit([7, 19, 3, 42, 11, 23], max_new_tokens=1)
    eng2.run()
    gz2 = np.asarray(eng2.kv_cache.storage["scan"][0]["gz"][0, 0])
    assert gz2.tolist() == [6.0], gz2             # real columns only


def test_engine_chunk_boundary_prompt_lengths():
    """Prompt lengths exactly at, one below, and one above a chunk
    boundary all match the teacher-forced oracle (chunk_len = 4)."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    for plen in (4, 8, 3, 5):
        prompt = rng.integers(1, TINY.vocab_size, size=plen).tolist()
        eng = ServingEngine(TINY, mesh, params, n_slots=2, prefill_len=8,
                            max_cache=24, chunk_len=4,
                            prefill_mode="chunked")
        rid = eng.submit(prompt, max_new_tokens=4)
        got = eng.run()[rid]
        seq = list(prompt)
        for _ in range(4):
            logits, _ = T.forward(TINY, params, jnp.asarray([seq]),
                                  chunk=8)
            seq.append(int(np.argmax(np.asarray(logits[0, -1]))))
        assert got == seq[plen:], (plen, got, seq[plen:])
        want_chunks = -(-plen // 4)
        assert eng.stats.prefill_chunks == want_chunks
        assert eng.stats.prefill_tokens == plen


def test_engine_padded_mode_still_serves():
    """Legacy admission path: the padded flush + rewind still matches
    the teacher-forced oracle."""
    mesh = _mesh()
    params = T.init(TINY, jax.random.PRNGKey(0))
    prompt = [7, 19, 3, 42, 11]
    eng = ServingEngine(TINY, mesh, params, n_slots=2, prefill_len=8,
                        max_cache=16, prefill_mode="padded")
    rid = eng.submit(prompt, max_new_tokens=3)
    got = eng.run()[rid]
    seq = list(prompt)
    for _ in range(3):
        logits, _ = T.forward(TINY, params, jnp.asarray([seq]), chunk=8)
        seq.append(int(np.argmax(np.asarray(logits[0, -1]))))
    assert got == seq[len(prompt):]
    assert eng.stats.prefill_chunks == 0 and eng.stats.prefills == 1
