"""End-to-end driver: train a ~25M-param char-LM for a few hundred steps
with the PRISM-sharded training step (sequence parallelism over 'model',
FSDP over 'data', Segment-Means exchange per block), then evaluate bpc.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python examples/train_char_lm.py --steps 300

Scale up with --d-model/--layers (the default is sized for this CPU
container; the same script drives the production mesh on real TPUs).
"""
import argparse
import math
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--d-model", type=int, default=384)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--cr", type=float, default=4.0)
    ap.add_argument("--mode", default="prism",
                    choices=("prism", "voltage"))
    ap.add_argument("--mesh", default="2x4")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from repro.core.protocol import PrismConfig
    from repro.data.pipeline import CharTokenizer, lm_batches, synthetic_text
    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.optim import adamw_init
    from repro.runtime.train import make_train_step, TrainHParams
    from repro.launch.mesh import make_mesh

    data, model = (int(x) for x in args.mesh.split("x"))
    if len(jax.devices()) < data * model:
        print(f"note: {len(jax.devices())} devices < mesh {args.mesh}; "
              "set XLA_FLAGS=--xla_force_host_platform_device_count=8")
        sys.exit(1)
    mesh = make_mesh((data, model), ("data", "model"))

    tok = CharTokenizer()
    corpus = tok.encode(synthetic_text(1_000_000, seed=1))
    held = tok.encode(synthetic_text(50_000, seed=2))
    cfg = ModelConfig(
        name="char-lm-25m", arch_type="dense", n_layers=args.layers,
        d_model=args.d_model, n_heads=8, n_kv_heads=4,
        head_dim=args.d_model // 8, d_ff=4 * args.d_model,
        vocab_size=tok.vocab, mlp_kind="swiglu", norm_kind="rmsnorm",
        pos="rope", tie_embeddings=True)

    params = T.init(cfg, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"model: {n_params / 1e6:.1f}M params, mesh {args.mesh}, "
          f"mode={args.mode} CR={args.cr}")

    prism = PrismConfig(P=model, cr=args.cr, mode=args.mode)
    hp = TrainHParams(lr=1e-3, warmup=20, total_steps=args.steps,
                      loss_chunks=8)
    step, rules, psh, osh, bsh = make_train_step(cfg, mesh, params,
                                                 prism, hp)
    params = jax.device_put(params, psh)
    opt = jax.device_put(adamw_init(params), osh)

    it = lm_batches(corpus, batch=args.batch, seq=args.seq, seed=0)
    import time
    t0 = time.time()
    for i in range(args.steps):
        x, y = next(it)
        params, opt, m = step(params, opt,
                              jax.device_put({"tokens": x, "labels": y},
                                             bsh))
        if i % 25 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(m['loss']):.4f}  "
                  f"bpc {float(m['loss']) / math.log(2):.3f}  "
                  f"gnorm {float(m['gnorm']):.2f}  "
                  f"{time.time() - t0:.0f}s")

    # held-out bpc, evaluated THROUGH the sharded PRISM step's loss
    ev = lm_batches(held, batch=args.batch, seq=args.seq, seed=9)
    tot = 0.0
    for _ in range(5):
        x, y = next(ev)
        # the step donates its inputs, so rethread params/opt; the loss
        # metric is computed BEFORE the update, so this is a clean eval
        params, opt, m = step(params, opt,
                              jax.device_put({"tokens": x, "labels": y},
                                             bsh))
        tot += float(m["loss"])
    print(f"held-out bpc ≈ {tot / 5 / math.log(2):.3f} "
          f"({args.mode}, CR={args.cr})")

    if args.ckpt_dir:
        from repro.checkpoint.io import save_checkpoint
        print("saved:", save_checkpoint(args.ckpt_dir, args.steps,
                                        jax.device_get(params)))


if __name__ == "__main__":
    main()
