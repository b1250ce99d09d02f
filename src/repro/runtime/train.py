"""Sharded training step: PRISM sequence-parallel forward, FSDP parameter
gathering, vocab-parallel loss, explicit gradient reductions, AdamW.

Structure (DESIGN.md §4):
  outer jax.jit
    ├─ shard_map body: per-device forward + backward with explicit
    │    collectives (PRISM Segment-Means all-gather per block, FSDP
    │    param all-gather per layer, vocab-parallel chunked loss,
    │    gradient psums per sharding rule)
    └─ global-norm clip + AdamW in auto-SPMD land (optimizer state can
         carry different sharding; XLA inserts the reshards)
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.protocol import PrismConfig
from ..models import transformer as T
from ..models.config import ModelConfig
from ..models.layers import norm
from ..optim import adamw_update, clip_by_global_norm, cosine_schedule
from ..sharding.context import ShardedPrismContext
from ..sharding.rules import (GradReduce, gather_tree, opt_state_specs,
                              param_specs, spec_tree)
from ..launch.mesh import batch_axes, mesh_axes


# --------------------------------------------------------------------------
# vocab-parallel embedding + loss (embed table sharded over 'model' on vocab)
# --------------------------------------------------------------------------

def embed_vp(table_local, tokens, *, sharded_vocab: bool):
    """Vocab-parallel lookup.  tokens are SEQ-sharded and the table is
    VOCAB-sharded over the same 'model' axis, so each device first gathers
    all token ids (cheap ints), contributes its vocab shard's rows for the
    *full* sequence, and a psum_scatter sums the partials while handing
    each device back exactly its own sequence shard."""
    if not sharded_vocab:
        return jnp.take(table_local, tokens, axis=0)
    v_loc = table_local.shape[0]
    vstart = lax.axis_index("model") * v_loc
    tg = lax.all_gather(tokens, "model", axis=1, tiled=True)   # (B, N)
    t = tg - vstart
    valid = (t >= 0) & (t < v_loc)
    emb = jnp.take(table_local, jnp.clip(t, 0, v_loc - 1), axis=0)
    emb = jnp.where(valid[..., None], emb, 0)
    return lax.psum_scatter(emb, "model", scatter_dimension=1, tiled=True)


def vp_lm_loss(x_local, table_local, labels_local, *, softcap=None,
               sharded_vocab: bool, n_chunks: int = 16,
               global_tokens: int = 0):
    """x_local (B, N_loc, D) seq-sharded over 'model'; table_local
    (V_loc, D) vocab-sharded over 'model'.  Gathers activations chunk by
    chunk (Megatron-style sequence-parallel → vocab-parallel transition;
    the gather's transpose reduce-scatters the backward).

    Returns the *local-share* mean: token-nll summed over whatever tokens
    this device computed, divided by the GLOBAL token count — so a plain
    psum over the relevant axes reconstructs the global mean, and gradient
    contributions combine without double counting."""
    b, n_loc, d = x_local.shape
    v_loc = table_local.shape[0]
    n_chunks = min(n_chunks, n_loc)
    while n_loc % n_chunks:
        n_chunks -= 1
    xc = x_local.reshape(b, n_chunks, n_loc // n_chunks, d).swapaxes(0, 1)
    yc = labels_local.reshape(b, n_chunks, -1).swapaxes(0, 1)
    vstart = (lax.axis_index("model") * v_loc) if sharded_vocab else 0

    def body(carry, xs):
        x_c, y_c = xs
        if sharded_vocab:
            x_c = lax.all_gather(x_c, "model", axis=1, tiled=True)
            y_c = lax.all_gather(y_c, "model", axis=1, tiled=True)
        logits = (x_c @ table_local.T.astype(x_c.dtype)).astype(jnp.float32)
        if softcap:
            logits = softcap * jnp.tanh(logits / softcap)
        mx = lax.stop_gradient(jnp.max(logits, -1, keepdims=True))
        if sharded_vocab:
            mx = lax.pmax(mx, "model")
        ssum = jnp.sum(jnp.exp(logits - mx), -1)
        if sharded_vocab:
            ssum = lax.psum(ssum, "model")
        lse = mx[..., 0] + jnp.log(ssum)
        t = y_c - vstart
        valid = (t >= 0) & (t < v_loc)
        gold = jnp.take_along_axis(
            logits, jnp.clip(t, 0, v_loc - 1)[..., None], axis=-1)[..., 0]
        gold = jnp.where(valid, gold, 0.0)
        if sharded_vocab:
            gold = lax.psum(gold, "model")
        return carry + (lse - gold).sum(), None

    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (xc, yc))
    if sharded_vocab:
        # `total` is the all-model-shards sum (post-psum, replicated over
        # 'model'); convert to this device's share so downstream psums
        # remain uniform across both vocab modes.
        total = total / lax.axis_size("model")
    return total / global_tokens


# --------------------------------------------------------------------------
# sharded forward (per-layer FSDP gather + PRISM context)
# --------------------------------------------------------------------------

def output_table(params, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return params["embed"]["table"]
    w = params["lm_head"]["w"]          # (D, V_loc) -> (V_loc, D)
    return w.T


def sharded_forward(cfg: ModelConfig, params, rules, batch, ctx,
                    *, remat: bool = True, chunk: int = 128):
    """Returns (features (B, N_loc, D), aux)."""
    tokens = batch.get("tokens")
    embeds = batch.get("embeds")
    n_loc = (tokens.shape[1] if tokens is not None else embeds.shape[1])
    start = ctx._index() * n_loc

    vocab_sharded = (rules["embed"]["table"].kind == "vocab"
                     if "embed" in rules else False)
    if tokens is not None:
        x = embed_vp(params["embed"]["table"], tokens,
                     sharded_vocab=vocab_sharded)
    else:
        fp = gather_tree(params["frontend_proj"], rules["frontend_proj"])
        x = embeds @ fp["w"].astype(embeds.dtype)
    if cfg.arch_type == "vlm" and embeds is not None and tokens is not None:
        # image prefix injection: embeds (B, prefix, D) replicated
        pe = gather_tree(params["frontend_proj"], rules["frontend_proj"])
        proj = pe["w"].astype(embeds.dtype)
        fe = embeds @ proj
        pos = start + jnp.arange(n_loc)
        idx = jnp.clip(pos, 0, cfg.prefix_len - 1)
        fe_rows = jnp.take(fe, idx, axis=1)
        x = jnp.where((pos < cfg.prefix_len)[None, :, None], fe_rows, x)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.pos == "learned":
        tbl = gather_tree(params["pos_embed"], rules["pos_embed"])["table"]
        x = x + lax.dynamic_slice_in_dim(tbl, start, n_loc).astype(x.dtype)
    elif cfg.pos == "sincos":
        x = x + T.sincos_embed(n_loc, cfg.d_model, start).astype(x.dtype)

    shared_rules = rules.get("shared")
    aux_total = jnp.zeros((), jnp.float32)
    u, n_units, n_tail = cfg.scan_split
    unit_kinds = cfg.block_kinds[:u]

    def unit_body(x, sliced, shared_local):
        """One repeating unit (u sublayers) — the lax.scan body."""
        shared = (gather_tree(shared_local, shared_rules)
                  if shared_rules else None)
        aux = jnp.zeros((), jnp.float32)
        for j, kind in enumerate(unit_kinds):
            p = gather_tree(sliced[j], rules["scan"][j])
            x, a = T.block_apply(cfg, kind, p, shared, x, ctx, chunk=chunk)
            aux = aux + a
        return x, aux

    fn = jax.checkpoint(unit_body) if remat else unit_body
    if n_units > 1:
        x, auxs = lax.scan(
            lambda c, xs: fn(c, xs, params.get("shared")),
            x, tuple(params["scan"]))
        aux_total = aux_total + auxs.sum()
    else:
        x, aux = fn(x, tuple(T.layer_slice(s, 0)
                             if jax.tree.leaves(s) else s
                             for s in params["scan"]),
                    params.get("shared"))
        aux_total = aux_total + aux

    for t, tree in enumerate(params["tail"]):
        kind = cfg.block_kinds[n_units * u + t]

        def one_block(x, p_local, shared_local, _kind=kind, _t=t):
            p = gather_tree(p_local, rules["tail"][_t])
            shared = (gather_tree(shared_local, shared_rules)
                      if shared_rules else None)
            return T.block_apply(cfg, _kind, p, shared, x, ctx, chunk=chunk)

        tfn = jax.checkpoint(one_block) if remat else one_block
        x, aux = tfn(x, tree, params.get("shared"))
        aux_total = aux_total + aux

    x = norm(params["final_norm"], x, cfg.norm_kind)
    return x, aux_total


# --------------------------------------------------------------------------
# train step factory
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class TrainHParams:
    lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10000
    grad_clip: float = 1.0
    weight_decay: float = 0.1
    loss_chunks: int = 16
    remat: bool = True
    ssm_chunk: int = 128


def batch_spec(cfg: ModelConfig, mesh):
    ba = batch_axes(mesh)
    spec = {"tokens": P(ba, "model"), "labels": P(ba, "model")}
    if cfg.arch_type == "vlm":
        spec["embeds"] = P(ba, None, None)       # replicated prefix
    elif cfg.frontend == "encodec_stub":
        spec["embeds"] = P(ba, "model", None)
        del spec["tokens"]
    return spec


def make_train_step(cfg: ModelConfig, mesh, params, prism: PrismConfig,
                    hp: TrainHParams = TrainHParams()):
    rules = param_specs(params, mesh, cfg.vocab_size)
    pspecs = spec_tree(rules)
    ospecs = opt_state_specs(rules, params, mesh)
    bspec = batch_spec(cfg, mesh)
    axes = mesh_axes(mesh)
    n_model = axes["model"]
    n_devices = int(np.prod(list(axes.values())))
    ba = batch_axes(mesh)
    all_ax = tuple(mesh.axis_names)
    vocab_sharded = (rules["embed"]["table"].kind == "vocab"
                     if cfg.tie_embeddings else
                     rules["lm_head"]["w"].kind == "vocab")

    def body(params_local, batch_local):
        ctx = ShardedPrismContext(prism, n_shards=n_model,
                                  prefix_len=cfg.prefix_len)
        some = next(iter(batch_local.values()))
        b_loc = some.shape[0]
        n_loc = (batch_local["labels"].shape[1])
        global_tokens = b_loc * n_loc * n_devices

        def loss_fn(pl):
            feats, aux = sharded_forward(
                cfg, pl, rules, batch_local, ctx,
                remat=hp.remat, chunk=hp.ssm_chunk)
            table = output_table(pl, cfg)
            nll = vp_lm_loss(feats, table, batch_local["labels"],
                             softcap=cfg.logit_softcap,
                             sharded_vocab=vocab_sharded,
                             n_chunks=hp.loss_chunks,
                             global_tokens=global_tokens)
            # aux is a per-device statistic; average it over the mesh so
            # the psum-combined gradient matches the mean-aux objective.
            return nll + cfg.router_aux_weight * aux / n_devices, (nll, aux)

        (loss, (nll, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params_local)
        grads = GradReduce.apply(grads, rules, mesh)
        metrics = {
            "loss": lax.psum(nll, all_ax),
            "moe_aux": lax.pmean(aux, all_ax),
        }
        return grads, metrics

    body_sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspecs, bspec),
        out_specs=(pspecs, P()),
        check_vma=False)

    def step(params, opt_state, batch):
        grads, metrics = body_sm(params, batch)
        grads, gnorm = clip_by_global_norm(grads, hp.grad_clip)
        lr = cosine_schedule(opt_state["step"], base_lr=hp.lr,
                             warmup=hp.warmup, total=hp.total_steps)
        new_params, new_opt = adamw_update(
            params, grads, opt_state, lr=lr,
            weight_decay=hp.weight_decay)
        metrics = dict(metrics, gnorm=gnorm, lr=lr)
        return new_params, new_opt, metrics

    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    opt_sh = {"m": jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs),
              "v": jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs),
              "step": NamedSharding(mesh, P())}
    batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), bspec)
    rep = NamedSharding(mesh, P())

    jitted = jax.jit(
        step,
        in_shardings=(param_sh, opt_sh, batch_sh),
        out_shardings=(param_sh, opt_sh,
                       {"loss": rep, "moe_aux": rep, "gnorm": rep, "lr": rep}),
        donate_argnums=(0, 1),
    )
    return jitted, rules, param_sh, opt_sh, batch_sh
