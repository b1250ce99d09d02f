"""Standalone runner: prefill + incremental decode must match the
single-device full forward's last-token logits (exact mode), and be
plausible in prism mode (approximate by design).
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.protocol import PrismConfig
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.runtime.serve import (ServeHParams, make_prefill_step,
                                 make_serve_step, make_layout, grow_cache)
from repro.launch.mesh import make_mesh


def check(name, cfg, mode, *, atol, batch=8, n=32, gen=4, backend="auto"):
    mesh = make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(0)
    params = T.init(cfg, key)
    total = n + gen
    tokens = jax.random.randint(key, (batch, total), 0, cfg.vocab_size)

    name = f"{name}+{backend}" if backend != "auto" else name
    hp = ServeHParams(decode_mode="exact" if mode == "tp" else mode,
                      decode_tp=(mode == "tp"), ssm_chunk=8, means_cr=4.0,
                      backend=backend)
    prism = PrismConfig(P=4, mode="prism" if mode == "prism" else "voltage")
    prefill, lay_p, _, _ = make_prefill_step(
        cfg, mesh, params, prism, batch=batch, n=n, hp=hp)
    logits_pre, cache = prefill(params, {"tokens": tokens[:, :n]})

    # prefill last-token logits vs full forward over the first n tokens
    ref_n, _ = T.forward(cfg, params, tokens[:, :n], chunk=8)
    if mode in ("exact", "tp"):
        got = np.asarray(jax.device_get(logits_pre))
        ref = np.asarray(ref_n[:, -1])
        err = np.abs(got - ref).max() / max(1e-6, np.abs(ref).max())
        print(f"[{name}/{mode}] prefill rel-err={err:.2e} "
              f"{'OK' if err < atol else 'FAIL'}")
        if err >= atol:
            return False

    cap = n + ((gen + 3) // 4) * 4
    step, lay_d, _, _ = make_serve_step(cfg, mesh, params, batch=batch,
                                        cap=cap, prefill_len=n, hp=hp)
    cache = grow_cache(jax.device_get(cache) and cache, lay_p, lay_d)

    ok = True
    for g in range(gen):
        pos = jnp.full((batch,), n + g, jnp.int32)
        logits_dec, cache = step(params, cache, tokens[:, n + g], pos)
        if mode in ("exact", "tp"):
            ref_g, _ = T.forward(cfg, params, tokens[:, :n + g + 1], chunk=1)
            ref = np.asarray(ref_g[:, -1])
            got = np.asarray(jax.device_get(logits_dec))
            err = np.abs(got - ref).max() / max(1e-6, np.abs(ref).max())
            step_ok = err < atol
            ok &= step_ok
            print(f"[{name}/{mode}] decode step {g} rel-err={err:.2e} "
                  f"{'OK' if step_ok else 'FAIL'}")
        else:
            got = np.asarray(jax.device_get(logits_dec))
            step_ok = np.isfinite(got).all()
            ok &= step_ok
            print(f"[{name}/{mode}] decode step {g} finite "
                  f"{'OK' if step_ok else 'FAIL'}")
    return ok


def main():
    ok = True
    dense = ModelConfig(
        name="tiny-dense", arch_type="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=64,
        mlp_kind="swiglu", norm_kind="rmsnorm", pos="rope",
        tie_embeddings=True)
    ok &= check("dense", dense, "exact", atol=5e-5)
    ok &= check("dense", dense, "prism", atol=0.5)
    ok &= check("dense", dense, "tp", atol=5e-5)
    # forced-Pallas (interpret off-TPU): the kernels on a real 4-way
    # sequence-sharded mesh — exact vs the full-forward oracle proves
    # the cross-shard stat combine over kernel stats; prism exercises
    # the in-kernel means columns with real per-shard gz
    ok &= check("dense", dense, "exact", atol=5e-5, backend="pallas")
    ok &= check("dense", dense, "prism", atol=0.5, backend="pallas")

    window = ModelConfig(
        name="tiny-window", arch_type="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=1, head_dim=16, d_ff=128, vocab_size=64,
        blocks=("attn_local", "attn"), window=8, mlp_kind="geglu",
        norm_kind="rmsnorm", pos="rope", qk_norm=True, tie_embeddings=True)
    ok &= check("window", window, "exact", atol=5e-5)

    ssm = ModelConfig(
        name="tiny-xlstm", arch_type="ssm", n_layers=2, d_model=64,
        n_heads=2, n_kv_heads=2, d_ff=0, vocab_size=64,
        blocks=("mlstm", "slstm"), norm_kind="rmsnorm", pos="none",
        ssm_heads=2, tie_embeddings=False)
    ok &= check("ssm", ssm, "exact", atol=5e-4)

    hybrid = ModelConfig(
        name="tiny-zamba", arch_type="hybrid", n_layers=3, d_model=64,
        n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=64,
        blocks=("mamba", "shared_attn", "mamba"), norm_kind="rmsnorm",
        pos="rope", ssm_state=8, ssm_heads=4, shared_attn_every=2,
        tie_embeddings=False)
    ok &= check("hybrid", hybrid, "exact", atol=5e-4)

    moe = ModelConfig(
        name="tiny-moe", arch_type="moe", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=4, head_dim=16, d_ff=64, vocab_size=64,
        blocks=("moe", "moe"), mlp_kind="swiglu", norm_kind="rmsnorm",
        pos="rope", n_experts=4, top_k=2, expert_d_ff=64,
        capacity_factor=8.0, tie_embeddings=False)
    ok &= check("moe", moe, "exact", atol=5e-4)
    ok &= check("moe", moe, "tp", atol=5e-4)

    print("ALL OK" if ok else "SERVE FAILURES")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
