"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch gpt2-small \
        --steps 200 --batch 8 --seq 256 --mesh 2x4 --mode prism --cr 4

Uses host devices (set XLA_FLAGS=--xla_force_host_platform_device_count=N
to exceed the physical count); on a real TPU slice the same entry point
picks up the platform devices.  The production 16x16 / 2x16x16 meshes are
exercised via ``repro.launch.dryrun`` (this container compiles but cannot
execute 256-chip programs).
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-small")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale variant of the arch family")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mesh", default="2x4", help="DATAxMODEL")
    ap.add_argument("--mode", default="prism",
                    choices=("prism", "voltage", "single"))
    ap.add_argument("--cr", type=float, default=4.0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args()

    import jax
    from repro.configs import get_config
    from repro.core.protocol import PrismConfig
    from repro.data.pipeline import CharTokenizer, lm_batches, synthetic_text
    from repro.models import transformer as T
    from repro.optim import adamw_init
    from repro.runtime.train import make_train_step, TrainHParams
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_mesh

    use_compile_cache()
    data, model = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((data, model), ("data", "model"))
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"[train] {cfg.name}: {cfg.n_layers}L d={cfg.d_model} "
          f"mesh={data}x{model} mode={args.mode} cr={args.cr}")

    key = jax.random.PRNGKey(0)
    params = T.init(cfg, key)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"[train] {n_params / 1e6:.1f}M params")

    prism = PrismConfig(P=model, cr=args.cr, mode=args.mode)
    hp = TrainHParams(lr=args.lr, total_steps=args.steps,
                      warmup=max(1, args.steps // 10))
    step, rules, psh, osh, bsh = make_train_step(cfg, mesh, params, prism, hp)
    params = jax.device_put(params, psh)
    opt = jax.device_put(adamw_init(params), osh)

    tok = CharTokenizer()
    corpus = tok.encode(synthetic_text(500_000, seed=1))
    it = lm_batches(corpus, batch=args.batch, seq=args.seq, seed=0)
    t0 = time.time()
    for i in range(args.steps):
        x, y = next(it)
        batch = jax.device_put({"tokens": x, "labels": y}, bsh)
        params, opt, metrics = step(params, opt, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            loss = float(metrics["loss"])
            print(f"step {i:5d}  loss {loss:.4f}  "
                  f"gnorm {float(metrics['gnorm']):.3f}  "
                  f"lr {float(metrics['lr']):.2e}  "
                  f"{(time.time() - t0):.1f}s")
    if args.checkpoint:
        from repro.checkpoint.io import save_checkpoint
        path = save_checkpoint(args.checkpoint, args.steps,
                               jax.device_get(params))
        print(f"[train] saved {path}")


if __name__ == "__main__":
    main()
