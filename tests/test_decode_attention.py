"""Flash-decode kernel validation: interpret-mode execution vs the jnp
oracles (`flash_decode_combine` / `prism_decode_attention`), sweeping
GQA ratios, ragged per-slot positions (idle pos = -1 rows), prism means
columns, and non-block-multiple cache lengths — plus the backend
dispatch rules and a serve-step integration check."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st

from repro.kernels.decode_attention import (decode_stats_reference,
                                            flash_decode_stats,
                                            merge_stats,
                                            partial_softmax_stats)
from repro.kernels.dispatch import (default_interpret, pallas_interpret,
                                    resolve_backend, use_pallas)
from repro.runtime.serve import (decode_attention, flash_decode_combine,
                                 prism_decode_attention)


def make_case(b, m_loc, hq, hkv, hd, *, mz=0, seed=0, pos=None):
    """Continuous-batching-shaped decode case: per-row positions (idle
    rows -1), prefix-valid columns, optional means columns with a
    per-row g (0 = dead: own shard / not-yet-covered segment)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, 1, hq, hd)) * 0.5
    k = jax.random.normal(ks[1], (b, m_loc, hkv, hd)) * 0.5
    v = jax.random.normal(ks[2], (b, m_loc, hkv, hd)) * 0.5
    if pos is None:
        rng = np.random.default_rng(seed)
        pos = rng.integers(-1, m_loc, size=b)
        pos[0] = m_loc - 1                       # one fully-deep slot
        if b > 1:
            pos[1] = -1                          # one idle slot
    pos = np.asarray(pos)
    valid = jnp.asarray(np.arange(m_loc)[None, :] <= pos[:, None])
    scale = hd ** -0.5
    if not mz:
        return q, k, v, valid, pos, scale
    kz = jax.random.normal(ks[3], (b, mz, hkv, hd)) * 0.5
    vz = jax.random.normal(ks[4], (b, mz, hkv, hd)) * 0.5
    gz = np.where(np.arange(mz)[None, :] % 3 == 0, 0.0, 4.0)
    gz = jnp.asarray(gz * (pos >= 0)[:, None].astype(np.float64),
                     jnp.float32)                # idle rows: all dead
    return q, k, v, valid, pos, scale, kz, vz, gz


GQA_GRID = [
    # b, m_loc, hq, hkv, hd      — m_loc deliberately off block multiples
    (4, 16, 2, 2, 16),           # MHA
    (3, 33, 8, 2, 32),           # GQA 4:1, ragged M
    (2, 100, 6, 3, 64),          # GQA 2:1, ragged M
    (2, 128, 8, 1, 64),          # MQA, block-aligned
    (1, 7, 4, 4, 16),            # shorter than one block
]


@pytest.mark.parametrize("b,m_loc,hq,hkv,hd", GQA_GRID)
def test_kernel_vs_combine_oracle(b, m_loc, hq, hkv, hd):
    """Kernel stats, locally combined, equal the dense flash-decode
    oracle on every live row (idle rows are garbage-but-finite in the
    oracle, exact zero in the stats path — both unobserved)."""
    q, k, v, valid, pos, scale = make_case(b, m_loc, hq, hkv, hd)
    want = flash_decode_combine(q, k, v, valid, (), scale)
    got = decode_attention(q, k, v, valid, (), scale, backend="pallas")
    live = pos >= 0
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live],
                               atol=1e-5, rtol=1e-5)
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("b,m_loc,hq,hkv,hd", GQA_GRID)
@pytest.mark.parametrize("mz", [6, 8])
def test_kernel_vs_prism_oracle(b, m_loc, hq, hkv, hd, mz):
    """Means columns folded in-kernel (+log g bias) equal the
    concatenate-then-softmax prism oracle, for both backends."""
    q, k, v, valid, pos, scale, kz, vz, gz = make_case(
        b, m_loc, hq, hkv, hd, mz=mz)
    owner = jnp.asarray(pos >= 0)
    want = prism_decode_attention(q, k, v, kz, vz, valid, gz, owner,
                                  (), scale)
    live = pos >= 0
    for backend in ("jnp", "pallas"):
        got = decode_attention(q, k, v, valid, (), scale, gz=gz, kz=kz,
                               vz=vz, owner=owner, mode="prism",
                               backend=backend)
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(want)[live],
                                   atol=1e-5, rtol=1e-5, err_msg=backend)


def test_kernel_stats_match_reference_stats():
    """The raw (m, l, acc) triples agree between kernel and jnp
    reference — the shard-combine contract, not just the combined
    output.  (m is only meaningful where l > 0.)"""
    q, k, v, valid, pos, scale, kz, vz, gz = make_case(
        3, 40, 4, 2, 32, mz=6, seed=3)
    log_gz = jnp.where(gz > 0, jnp.log(jnp.maximum(gz, 1e-30)), -1e30)
    m_k, l_k, a_k = flash_decode_stats(q, k, v, valid, log_gz, kz, vz,
                                       scale=scale, interpret=True)
    m_r, l_r, a_r = decode_stats_reference(q, k, v, valid, log_gz, kz,
                                           vz, scale=scale)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r),
                               atol=1e-5, rtol=1e-5)
    alive = np.asarray(l_r) > 0
    np.testing.assert_allclose(np.asarray(m_k)[alive],
                               np.asarray(m_r)[alive],
                               atol=1e-6, rtol=1e-6)
    # idle rows carry exactly-empty stats, not garbage
    idle = ~(pos >= 0)
    assert not np.asarray(l_k)[idle].any()
    assert not np.asarray(a_k)[idle].any()


def test_merge_stats_is_concat():
    """Splitting the columns anywhere and merging the partial stats
    equals single-pass stats over all columns — the identity both the
    kernel grid and the cross-shard combine rest on."""
    q, k, v, valid, pos, scale = make_case(3, 24, 4, 2, 16, seed=5)
    bias = jnp.where(valid, 0.0, -1e30)
    whole = partial_softmax_stats(q, k, v, bias, scale)
    for cut in (1, 8, 23):
        a = partial_softmax_stats(q, k[:, :cut], v[:, :cut],
                                  bias[:, :cut], scale)
        b = partial_softmax_stats(q, k[:, cut:], v[:, cut:],
                                  bias[:, cut:], scale)
        m, l, acc = merge_stats(a, b)
        np.testing.assert_allclose(np.asarray(l), np.asarray(whole[1]),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(acc), np.asarray(whole[2]),
                                   atol=1e-5, rtol=1e-5)


@settings(deadline=None, max_examples=10)
@given(b=st.integers(1, 4), m_loc=st.integers(2, 80),
       grp=st.sampled_from([1, 2, 4]), hkv=st.sampled_from([1, 2, 3]),
       prism=st.booleans(), seed=st.integers(0, 10_000))
def test_decode_kernel_property(b, m_loc, grp, hkv, prism, seed):
    """Property sweep: any (batch, cache length, GQA ratio, means?)
    draw — kernel ≡ oracle on live rows."""
    hq, hd = grp * hkv, 16
    mz = 6 if prism else 0
    case = make_case(b, m_loc, hq, hkv, hd, mz=mz, seed=seed)
    if prism:
        q, k, v, valid, pos, scale, kz, vz, gz = case
        owner = jnp.asarray(pos >= 0)
        want = prism_decode_attention(q, k, v, kz, vz, valid, gz,
                                      owner, (), scale)
        got = decode_attention(q, k, v, valid, (), scale, gz=gz, kz=kz,
                               vz=vz, owner=owner, mode="prism",
                               backend="pallas")
    else:
        q, k, v, valid, pos, scale = case
        want = flash_decode_combine(q, k, v, valid, (), scale)
        got = decode_attention(q, k, v, valid, (), scale,
                               backend="pallas")
    live = pos >= 0
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live],
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------
# backend dispatch
# ---------------------------------------------------------------------

def test_dispatch_rules():
    assert resolve_backend("pallas") == "pallas"
    assert resolve_backend("jnp") == "jnp"
    # 'auto' resolves by platform; on the CPU CI image that is jnp
    auto = resolve_backend("auto")
    assert auto == ("pallas" if jax.default_backend() == "tpu" else "jnp")
    assert resolve_backend(None) in ("pallas", "jnp")
    assert use_pallas("pallas") and not use_pallas("jnp")
    with pytest.raises(ValueError):
        resolve_backend("cuda")
    # the environment never moves 'auto' off the platform's choice (an
    # inherited variable must not put a chip run on the jnp oracle)
    import os
    other = "jnp" if auto == "pallas" else "pallas"
    os.environ["PRISM_KERNEL_BACKEND"] = other
    try:
        assert resolve_backend("auto") == auto
        assert resolve_backend(None) == auto
    finally:
        del os.environ["PRISM_KERNEL_BACKEND"]
    # interpret auto-detection: emulate everywhere but real TPU
    assert pallas_interpret() == (jax.default_backend() != "tpu")
    assert default_interpret(None) == pallas_interpret()
    assert default_interpret(True) is True
    assert default_interpret(False) is False


def test_ops_interpret_defaults_auto_detect():
    """The kernel wrappers no longer default to interpret=True: leaving
    ``interpret`` unset must resolve by platform (compiled on TPU) and
    still match the explicit-interpret result off-TPU."""
    from repro.kernels.ops import prism_attention_op
    from repro.kernels.segment_means import segment_means_op
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 24))
    got = segment_means_op(x, L=4)                  # interpret unset
    want = segment_means_op(x, L=4, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6)
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 2, 16))
    g = jnp.ones((8,), jnp.float32)
    pos = jnp.arange(8, dtype=jnp.int32)
    got = prism_attention_op(q, k, k, g, pos, pos, pos, causal=True)
    want = prism_attention_op(q, k, k, g, pos, pos, pos, causal=True,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6)


# ---------------------------------------------------------------------
# serve-step integration: backend routed through ServeHParams
# ---------------------------------------------------------------------

def test_serve_step_backend_equivalence():
    """Prefill + decode through make_serve_step with backend='pallas'
    (interpret on CPU) matches backend='jnp' — the whole hot path runs
    through the kernels, inside shard_map, and agrees with the oracle
    routing."""
    from repro.core.protocol import PrismConfig
    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.runtime.serve import (ServeHParams, grow_cache,
                                     make_prefill_step, make_serve_step)
    from repro.launch.mesh import make_mesh
    tiny = ModelConfig(
        name="tiny-kb", arch_type="dense", n_layers=2, d_model=32,
        n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64, vocab_size=61,
        mlp_kind="swiglu", norm_kind="rmsnorm", pos="rope",
        tie_embeddings=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    params = T.init(tiny, jax.random.PRNGKey(0))
    n0, cap = 8, 12
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, n0), 1,
                                tiny.vocab_size)
    outs = {}
    for backend in ("jnp", "pallas"):
        hp = ServeHParams(decode_mode="prism", ssm_chunk=8,
                          backend=backend)
        prism = PrismConfig(P=1, mode="prism")
        pre, lp, _, _ = make_prefill_step(tiny, mesh, params, prism,
                                          batch=2, n=n0, hp=hp)
        logits, cache = pre(params, {"tokens": prompt})
        step, ld, _, _ = make_serve_step(tiny, mesh, params, batch=2,
                                         cap=cap, prefill_len=n0, hp=hp)
        cache = grow_cache(cache, lp, ld)
        trace = [np.asarray(logits)]
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for g in range(2):
            pos = jnp.full((2,), n0 + g, jnp.int32)
            logits, cache = step(params, cache, tok, pos)
            trace.append(np.asarray(logits))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        outs[backend] = trace
    for a, b in zip(outs["jnp"], outs["pallas"]):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)
