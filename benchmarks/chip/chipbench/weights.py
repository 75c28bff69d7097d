"""Weights from a seed, made on the device in one jitted call, in the
type they are served or trained in.

The tree's structure (names and shapes) is the program's, read with
``jax.eval_shape`` of its initializer; the values are the benchmark's
own: norms 0 (they scale by 1 + w), the embedding N(0, 0.02), every other
matrix N(0, 1 / fan_in) with fan_in its second-to-last axis.  The
reference builds the same tree from the same seed, so it never takes
weights the program made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def param_shapes(cfg):
    from repro.models import init_params
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


def _leaf(path, sd, key):
    name = str(getattr(path[-1], "key", path[-1]))
    if "norm" in name or len(sd.shape) < 2:
        return jnp.zeros(sd.shape, jnp.float32)
    x = jax.random.normal(key, sd.shape, jnp.float32)
    if name == "embed":
        return x * 0.02
    return x * jax.lax.rsqrt(jnp.float32(sd.shape[-2]))


def seed_key(seed: int):
    """A raw threefry key holding all 64 bits of ``seed``; passed to the
    jitted makers as an argument, so a new seed compiles nothing."""
    import numpy as np
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


def _one(shapes, base, dtype):
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = [_leaf(p, sd, jax.random.fold_in(base, i)).astype(
        dtype or sd.dtype) for i, (p, sd) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make(shapes, seeds: tuple, dtype=None):
    """One parameter tree per seed, stacked on a leading axis when more
    than one seed is given (a seed named twice gives two equal copies);
    leaves in ``dtype`` (default: the shapes' own)."""
    uniq = sorted(set(seeds))

    def build(keys):
        trees = {s: _one(shapes, k, dtype) for s, k in zip(uniq, keys)}
        if len(seeds) == 1:
            return trees[seeds[0]]
        return jax.tree.map(lambda *ls: jnp.stack(ls),
                            *[trees[s] for s in seeds])
    return jax.jit(build)([seed_key(s) for s in uniq])
