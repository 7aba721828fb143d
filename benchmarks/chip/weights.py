"""Seeded weights for the served models, made on the device.

The program serves float32 master weights and casts them where it
computes.  The benchmark makes them itself, in one jitted call from
the seed, in the layout the program's models take (read from their
``init`` as shapes only).  A model's family may set a leaf first (its
``leaf_init``); the common rule then sets norm scales 1, embedding rows
N(0, 0.02^2) and every matrix N(0, 1/fan_in), and refuses a leaf that
is one vector per layer but no norm, which no fan-in fits.  The
reference reads the same arrays.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _name(path):
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _is_norm(name):
    last = name.rsplit("/", 1)[-1]
    return last.startswith("ln") or last.endswith("norm")


def _layer_axes(names, shapes):
    """Leading layer axes of each leaf: a layer group's leaves are
    stacked over its layers (``groups/<i>/...``), and its norm scale,
    one vector a layer, shows how many axes that stack has."""
    axes = {}
    for name, shape in zip(names, shapes):
        if name.startswith("groups/") and _is_norm(name):
            axes.setdefault(name.split("/", 2)[1], len(shape) - 1)
    return [axes.get(n.split("/", 2)[1], 1) if n.startswith("groups/")
            else 0 for n in names]


def _leaf_init(name, shape, n_layer_axes, key):
    last = name.rsplit("/", 1)[-1]
    if _is_norm(name):
        return jnp.ones(shape, jnp.float32)
    if len(shape) - n_layer_axes < 2:
        raise ValueError(
            f"weight leaf {name} {tuple(shape)} is one vector a layer and "
            f"no norm: its family's leaf_init has to set it")
    z = jax.random.normal(key, shape, jnp.float32)
    if last == "embed":
        return z * 0.02
    return z * (1.0 / shape[-2] ** 0.5)


def make(models, seed: int, skip=None, rules=None):
    """One float32 weight tree per model of ``models``, all from
    ``seed``, in one jitted call on the default device.  ``skip[i]``
    names top-level entries model ``i`` is served without;
    ``rules[i]``, where given, is model ``i``'s family ``leaf_init``."""
    shapes = [jax.eval_shape(m.init, jax.random.key(0)) for m in models]
    if skip:
        shapes = [{k: v for k, v in t.items() if k not in s}
                  for t, s in zip(shapes, skip)]
    rules = rules or [None] * len(models)

    def fill(key):
        out = []
        for i, tree in enumerate(shapes):
            leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
            keys = jax.random.split(jax.random.fold_in(key, i), len(leaves))
            names = [_name(path) for path, _ in leaves]
            axes = _layer_axes(names, [leaf.shape for _, leaf in leaves])
            made = []
            for name, (_, leaf), n_axes, k in zip(names, leaves, axes, keys):
                a = rules[i](name, leaf.shape, k) if rules[i] else None
                made.append(_leaf_init(name, leaf.shape, n_axes, k)
                            if a is None else a)
            out.append(jax.tree_util.tree_unflatten(treedef, made))
        return out

    return jax.jit(fill)(jax.random.key(seed))
