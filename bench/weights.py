"""Random weights for a cell, made on the device by one jitted call.

The benchmark, not the program, draws the weights, so that the plain
reference (``bench/reference/``) and the program under test read the same
arrays and the reference depends on nothing the program made.  The tree
takes its layout (names, shapes, dtypes and, under tensor parallelism,
shardings) from the program's abstract parameter tree, so the program can
serve it as it is.

Draws (every leaf from its own fold of the seed's key):

    matrices                 N(0, 0.02)
    attention and MLP output projections (wo, w_down)
                             N(0, 0.02 / sqrt(2 * layers))
    norm scales (RMSNorm, q/k norm)   1 + N(0, 0.1), float32
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic import rng_for

STD = 0.02
NORM_STD = 0.1


def seed_key(seed: int):
    """A JAX key from any Python int seed (two 32-bit words of it)."""
    hi, lo = rng_for(seed, 100).integers(0, 2 ** 31, 2)
    return jax.random.fold_in(jax.random.PRNGKey(int(lo)), int(hi))


def _path_names(path) -> tuple:
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _draw(names: tuple, leaf, key, layers: int):
    last = names[-1]
    if leaf.dtype == jnp.float32:            # norm scales
        return 1.0 + NORM_STD * jax.random.normal(key, leaf.shape, jnp.float32)
    std = STD / math.sqrt(2 * layers) if last in ("wo", "w_down") else STD
    return (std * jax.random.normal(key, leaf.shape, jnp.float32)
            ).astype(leaf.dtype)


def abstract_params(cfg, tp: int):
    """The program's parameter tree, as shapes (nothing is computed)."""
    from repro.models import api
    return jax.eval_shape(lambda k: api.init_params(k, cfg, tp),
                          jax.random.PRNGKey(0))


def make_weights(cfg, tp: int, seed: int, mesh=None) -> Any:
    """The benchmark's weights in the program's layout, on the device."""
    shapes = abstract_params(cfg, tp)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out_shardings = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.decoder import decoder_param_specs
        out_shardings = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec),
            decoder_param_specs(shapes), is_leaf=lambda x: isinstance(x, P))

    def build(key):
        leaves = [_draw(_path_names(path), leaf,
                        jax.random.fold_in(key, i), cfg.num_layers)
                  for i, (path, leaf) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=out_shardings)(seed_key(seed))


def tree_bytes(tree) -> int:
    return int(sum(np.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(tree)))
