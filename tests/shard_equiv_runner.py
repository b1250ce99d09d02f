"""Subprocess runner: sharded-vs-single-device equivalence on 8 host CPUs.

Run as:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
         PYTHONPATH=src python tests/shard_equiv_runner.py

Exits non-zero on any mismatch.  Invoked by tests/test_distributed.py so
the main pytest process keeps its single-device view.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.protocol import PrismConfig
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.context import SimulatedContext
from repro.optim import adamw_init
from repro.runtime.train import make_train_step, TrainHParams
from repro.runtime.losses import softmax_xent
from repro.launch.mesh import make_mesh


def ref_loss(cfg, params, tokens, labels, prism):
    ctx = SimulatedContext(prism, prefix_len=cfg.prefix_len)
    logits, aux = T.forward(cfg, params, tokens, ctx=ctx, chunk=8)
    return softmax_xent(logits, labels)


def check(name, cfg, prism, *, atol=2e-4, compare_grads=True):
    mesh = make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(0)
    params = T.init(cfg, key)
    B, N = 8, 32
    tokens = jax.random.randint(key, (B, N), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(1), (B, N), 0,
                                cfg.vocab_size)

    # ---- single-device reference (simulated P-device protocol) ----
    ref, ref_grads = jax.value_and_grad(
        lambda p: ref_loss(cfg, p, tokens, labels, prism))(params)

    # ---- sharded path ----
    hp = TrainHParams(loss_chunks=4, remat=True, ssm_chunk=8, lr=0.0,
                      grad_clip=1e9)
    step, rules, psh, osh, bsh = make_train_step(cfg, mesh, params, prism, hp)
    params_sh = jax.device_put(params, psh)
    opt = jax.device_put(adamw_init(params), osh)
    batch = jax.device_put({"tokens": tokens, "labels": labels}, bsh)
    new_params, new_opt, metrics = step(params_sh, opt, batch)
    loss_sh = float(metrics["loss"])

    dl = abs(loss_sh - float(ref))
    ok = dl < atol
    print(f"[{name}] loss ref={float(ref):.6f} sharded={loss_sh:.6f} "
          f"diff={dl:.2e} {'OK' if ok else 'FAIL'}")

    if compare_grads and ok:
        # recompute grads via a zero-lr step is awkward; instead re-run the
        # body via a dedicated grads-only step: lr=0 keeps params unchanged,
        # so compare updated optimizer first moment m = (1-b1)*grad.
        got_m = jax.tree.leaves(jax.device_get(new_opt["m"]))
        want = jax.tree.leaves(jax.device_get(ref_grads))
        worst = 0.0
        for gm, wg in zip(got_m, want):
            g = np.asarray(gm) / 0.1          # m = (1-b1)*g with b1=0.9
            w = np.asarray(wg)
            denom = max(1e-6, float(np.abs(w).max()))
            worst = max(worst, float(np.abs(g - w).max()) / denom)
        ok = worst < 5e-3
        print(f"[{name}] grads rel-err={worst:.2e} {'OK' if ok else 'FAIL'}")
    return ok


def main():
    ok = True

    dense = ModelConfig(
        name="tiny-dense", arch_type="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=64,
        mlp_kind="swiglu", norm_kind="rmsnorm", pos="rope",
        tie_embeddings=True)
    ok &= check("dense/prism", dense, PrismConfig(P=4, L=2))
    ok &= check("dense/voltage", dense, PrismConfig(P=4, mode="voltage"))

    window = ModelConfig(
        name="tiny-window", arch_type="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=1, head_dim=16, d_ff=128, vocab_size=64,
        blocks=("attn_local", "attn"), window=12, mlp_kind="geglu",
        norm_kind="rmsnorm", pos="rope", qk_norm=True, tie_embeddings=True)
    ok &= check("window/prism", window, PrismConfig(P=4, L=2))

    ssm = ModelConfig(
        name="tiny-xlstm", arch_type="ssm", n_layers=2, d_model=64,
        n_heads=2, n_kv_heads=2, d_ff=0, vocab_size=64,
        blocks=("mlstm", "slstm"), norm_kind="rmsnorm", pos="none",
        ssm_heads=2, tie_embeddings=False)
    ok &= check("ssm/xlstm", ssm, PrismConfig(P=4, L=2))

    hybrid = ModelConfig(
        name="tiny-zamba", arch_type="hybrid", n_layers=3, d_model=64,
        n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=64,
        blocks=("mamba", "shared_attn", "mamba"), norm_kind="rmsnorm",
        pos="rope", ssm_state=8, ssm_heads=4, shared_attn_every=2,
        tie_embeddings=False)
    ok &= check("hybrid/zamba", hybrid, PrismConfig(P=4, L=2))

    moe = ModelConfig(
        name="tiny-moe", arch_type="moe", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=4, head_dim=16, d_ff=64, vocab_size=64,
        blocks=("moe", "moe"), mlp_kind="swiglu", norm_kind="rmsnorm",
        pos="rope", n_experts=4, top_k=2, expert_d_ff=64,
        capacity_factor=8.0, router_aux_weight=0.0, tie_embeddings=False)
    ok &= check("moe", moe, PrismConfig(P=4, L=2), compare_grads=False)

    print("ALL OK" if ok else "EQUIVALENCE FAILURES")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
