"""Per-assigned-architecture smoke tests (assignment requirement):
instantiate a REDUCED variant of the same family (≤2 layers, d_model≤512,
≤4 experts) and run one forward + one train step on CPU, asserting output
shapes and the absence of NaNs.  The FULL configs are exercised only via
the dry-run (ShapeDtypeStruct, no allocation)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, ASSIGNED_ARCHS
from repro.core.protocol import PrismConfig
from repro.models import transformer as T
from repro.optim import adamw_init
from repro.runtime.train import make_train_step, TrainHParams
from repro.launch.mesh import make_mesh

B, N = 2, 32


def smoke_batch(cfg, key):
    ks = jax.random.split(key, 3)
    batch = {"labels": jax.random.randint(ks[1], (B, N), 0,
                                          cfg.vocab_size)}
    if cfg.frontend == "encodec_stub":
        batch["embeds"] = jax.random.normal(ks[0], (B, N, cfg.d_model))
    else:
        batch["tokens"] = jax.random.randint(ks[0], (B, N), 0,
                                             cfg.vocab_size)
        if cfg.arch_type == "vlm":
            batch["embeds"] = jax.random.normal(
                ks[2], (B, cfg.prefix_len, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_reduced_forward(arch):
    cfg = get_config(arch).reduced()
    assert cfg.n_layers <= 2 and cfg.d_model <= 512
    assert (cfg.n_experts or 0) <= 4
    params = T.init(cfg, jax.random.PRNGKey(0))
    batch = smoke_batch(cfg, jax.random.PRNGKey(1))
    logits, aux = T.forward(cfg, params, batch.get("tokens"),
                            embeds=batch.get("embeds"), chunk=8)
    n_out = N if cfg.frontend != "encodec_stub" else N
    assert logits.shape == (B, n_out, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_reduced_train_step(arch):
    cfg = get_config(arch).reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    params = T.init(cfg, jax.random.PRNGKey(0))
    prism = PrismConfig(P=1, cr=4.0, mode="prism")
    hp = TrainHParams(lr=1e-3, warmup=1, loss_chunks=4, ssm_chunk=8)
    step, rules, psh, osh, bsh = make_train_step(cfg, mesh, params,
                                                 prism, hp)
    opt = jax.device_put(adamw_init(params), osh)
    params = jax.device_put(params, psh)
    batch = jax.device_put(smoke_batch(cfg, jax.random.PRNGKey(1)), bsh)
    new_params, new_opt, metrics = step(params, opt, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    assert int(new_opt["step"]) == 1
    # second step: warmed-up lr > 0 — parameters must move
    new_params, new_opt, metrics = step(new_params, new_opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    # params actually moved
    moved = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(jax.device_get(new_params)),
                        jax.tree.leaves(jax.device_get(
                            T.init(cfg, jax.random.PRNGKey(0))))))
    assert moved


@pytest.mark.parametrize("arch", ["yi-6b", "xlstm-1.3b", "zamba2-2.7b",
                                  "olmoe-1b-7b", "gemma3-1b"])
def test_reduced_decode_step(arch):
    """Representative decode smoke (one arch per family): prefill 16,
    decode 2 tokens, finite logits of the right shape."""
    from repro.runtime.serve import (ServeHParams, grow_cache,
                                     make_prefill_step, make_serve_step)
    cfg = get_config(arch).reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    params = T.init(cfg, jax.random.PRNGKey(0))
    n, gen = 16, 2
    hp = ServeHParams(decode_mode="exact", ssm_chunk=8)
    prism = PrismConfig(P=1, mode="voltage")
    prefill, lay_p, _, _ = make_prefill_step(cfg, mesh, params, prism,
                                             batch=B, n=n, hp=hp)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, n + gen), 0,
                                cfg.vocab_size)
    logits, cache = prefill(params, {"tokens": tokens[:, :n]})
    assert logits.shape == (B, cfg.vocab_size)
    step, lay_d, _, _ = make_serve_step(cfg, mesh, params, batch=B,
                                        cap=n + gen, prefill_len=n, hp=hp)
    cache = grow_cache(cache, lay_p, lay_d)
    for g in range(gen):
        logits, cache = step(params, cache, tokens[:, n + g],
                             jnp.full((B,), n + g, jnp.int32))
        assert logits.shape == (B, cfg.vocab_size)
        assert np.isfinite(np.asarray(logits)).all()
