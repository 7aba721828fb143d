"""Seeded weights for the served models, made on the device.

The program serves float32 master weights and casts them where it
computes.  The benchmark makes them itself, in one jitted call from
the seed, in the layout the program's models take (read from their
``init`` as shapes only): norm scales 1, embedding rows N(0, 0.02^2),
every matrix N(0, 1/fan_in).  The reference reads the same arrays.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _leaf_init(path, shape, key):
    name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)
    last = name.rsplit("/", 1)[-1]
    if last.startswith("ln") or last.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    z = jax.random.normal(key, shape, jnp.float32)
    if last == "embed":
        return z * 0.02
    return z * (1.0 / shape[-2] ** 0.5)


def make(models, seed: int, skip=None):
    """One float32 weight tree per model of ``models``, all from
    ``seed``, in one jitted call on the default device.  ``skip[i]``
    names top-level entries model ``i`` is served without."""
    shapes = [jax.eval_shape(m.init, jax.random.key(0)) for m in models]
    if skip:
        shapes = [{k: v for k, v in t.items() if k not in s}
                  for t, s in zip(shapes, skip)]

    def fill(key):
        out = []
        for i, tree in enumerate(shapes):
            leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
            keys = jax.random.split(jax.random.fold_in(key, i), len(leaves))
            out.append(jax.tree_util.tree_unflatten(treedef, [
                _leaf_init(path, leaf.shape, k)
                for (path, leaf), k in zip(leaves, keys)]))
        return out

    return jax.jit(fill)(jax.random.key(seed))
