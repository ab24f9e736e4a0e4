"""Random weights from ``--seed``, made by the benchmark, on the device.

The benchmark makes the weights itself, so that its plain reference takes
nothing the program made.  The program only fixes the layout: the tree
``Model.init`` would return, read as shapes (``jax.eval_shape``), and
filled here leaf by leaf in one jitted call, in the type the model serves.

Values: matrices are normal with standard deviation ``fan_in ** -0.5``, the
embedding table unit normal, and the norm scales ``0.1 * normal`` (the
model multiplies by ``1 + scale``), so that every weight the reference
reads moves the logits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key for any whole ``seed``, also past 32 bits."""

    s = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(s[0]) >> 1), int(s[1]) >> 1)


def _path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _leaf(key, name: str, shape, dtype):
    if name.endswith("scale"):
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if name.endswith("table"):
        return jax.random.normal(key, shape, dtype)
    fan_in = shape[-2]
    return (jax.random.normal(key, shape, dtype) * (fan_in ** -0.5)).astype(dtype)


def make_weights(like, seed: int):
    """Weights in the layout of ``like`` (a ShapeDtypeStruct tree)."""

    flat, treedef = jax.tree_util.tree_flatten_with_path(like)
    names = [_path_name(p) for p, _ in flat]
    specs = [(tuple(a.shape), a.dtype) for _, a in flat]

    def build(key):
        return [
            _leaf(jax.random.fold_in(key, i), n, s, d)
            for i, (n, (s, d)) in enumerate(zip(names, specs))
        ]

    leaves = jax.jit(build)(seed_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)
