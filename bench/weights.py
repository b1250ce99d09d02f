"""Seeded random weights, made on the device in one jitted call.

The tree has the layout the engine's programs take (read from the
shapes of the program's own initializer, never its values), and each
leaf is placed under the sharding the programs declare for it
(``sharding.rules.param_specs``), as a deployment loads a checkpoint
onto its mesh.  The values are the benchmark's own: weights and
embeddings normal with standard deviation 1/sqrt(fan-in), norm scales
1 + 0.1 N(0, 1), biases 0.02 N(0, 1), so that every parameter the
reference reads moves the logits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding


def _leaf(key, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "scale":
        return (1.0 + 0.1 * z).astype(dtype)
    if name in ("b", "bias"):
        return (0.02 * z).astype(dtype)
    if name == "w":                    # (..., d_in, d_out)
        return (z * shape[-2] ** -0.5).astype(dtype)
    if name == "table":                # (rows, d)
        return (z * shape[-1] ** -0.5).astype(dtype)
    raise ValueError(f"no rule for weight {path!r}")


def _path(p) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)


def make_weights(cfg, mesh, seed: int, dtype=jnp.float32):
    """The parameter tree for ``cfg`` from ``seed``, on ``mesh``."""
    from repro.models import transformer as T
    from repro.sharding.rules import param_specs, spec_tree

    shapes = jax.eval_shape(
        functools.partial(T.init, cfg, dtype=dtype), jax.random.PRNGKey(0))
    specs = spec_tree(param_specs(shapes, mesh, cfg.vocab_size))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [_path(p) for p, _ in flat]
    out_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)

    @functools.partial(jax.jit, out_shardings=out_sh)
    def build(key):
        keys = jax.random.split(key, len(flat))
        leaves = [_leaf(k, path, leaf.shape, leaf.dtype)
                  for k, path, (_, leaf) in zip(keys, paths, flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    seq = np.random.SeedSequence([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  0x3E16])
    key = jax.random.PRNGKey(int(seq.generate_state(1)[0]))
    return build(key)
