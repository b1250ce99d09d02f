"""The plain references agree with the engine's logits at reduced
widths (both at "highest"), before any chip time is spent on them."""
import jax
import numpy as np
import pytest

from bench import spec
from bench.references import common
from bench.tests import tiny
from bench.weights import make_weights


def _engine_logits(conf, prompts, n_new):
    """Serve ``prompts`` through the synchronous engine, recording the
    logits row behind every sampled token."""
    from repro.launch.mesh import make_mesh
    from repro.serving import EngineConfig, ServingEngine

    class Recording(ServingEngine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.rows = {}

        def _advance_decode(self, st, logits_row, now):
            self.rows.setdefault(st.req.rid, []).append(
                np.array(logits_row, np.float32))
            super()._advance_decode(st, logits_row, now)

    cfg = spec.model_config(conf)
    mesh = make_mesh((1, 1), ("data", "model"))
    w = make_weights(cfg, mesh, 2**31 + 99)
    eng = Recording(cfg, mesh, w, EngineConfig(n_slots=2, prefill_len=64,
                                               max_cache=128))
    rids = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
    out = eng.run()
    return w, [(out[r], np.stack(eng.rows[r])) for r in rids]


@pytest.mark.parametrize("conf", [tiny.GPT2, tiny.LLAMA],
                         ids=["gpt2", "llama"])
def test_reference_matches_engine(conf):
    jax.config.update("jax_default_matmul_precision", "highest")
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, conf["vocab_size"], size=n).astype(np.int32)
               for n in (9, 40)]
    w, served = _engine_logits(conf, prompts, 6)
    ref = spec.reference(conf)
    for p, (toks, rows) in zip(prompts, served):
        seq = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
        want = ref.logits_at(conf, w, seq,
                             np.arange(len(p) - 1, len(p) - 1 + len(toks)))
        scale = np.abs(want).max(-1, keepdims=True)
        assert np.abs(rows - want).max() / scale.min() < 1e-4
        assert list(want.argmax(-1)) == list(toks)


def test_lower_precision_modes_differ():
    a = np.random.default_rng(0).standard_normal((64, 256)).astype(np.float32)
    b = np.random.default_rng(1).standard_normal((256, 64)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    err = {m: float(np.abs(np.asarray(common.ein("ij,jk->ik", a, b, m))
                           - exact).max()) for m in common.MODES}
    assert err["highest"] < 1e-3
    assert err["high"] < 1e-3            # float32 on a CPU
    assert err["bf16"] > 1e-2


@pytest.mark.parametrize("conf,key", [(tiny.GPT2, "layer_norm_epsilon"),
                                      (tiny.LLAMA, "rms_norm_eps")],
                         ids=["gpt2", "llama"])
def test_reference_reads_the_configuration_it_is_given(conf, key):
    """A value replaced in the configuration reaches the reference's
    pass, also after the same model has been read once."""
    from repro.launch.mesh import make_mesh
    ref = spec.reference(conf)
    w = make_weights(spec.model_config(conf), make_mesh((1, 1),
                                                        ("data", "model")), 3)
    seq = np.arange(1, 33, dtype=np.int32)
    rows = np.arange(32)
    a = ref.logits_at(conf, w, seq, rows)
    b = ref.logits_at(dict(conf, **{key: 0.5}), w, seq, rows)
    assert np.abs(a - b).max() > 1e-3
    assert np.array_equal(ref.logits_at(conf, w, seq, rows), a)
