"""Random weights from the seed, made on the device in one jitted call.

The benchmark, not the program, makes the weights: the program gets them
as a checkpoint would hand them over, and the reference reads the same
arrays. The tree has the program's layout (``Model.abstract_params``)
and its served dtype. Every matrix and bias is normal with the
configuration's ``initializer_range``; norm scales are ones; the
attention output bias is zero, since the published architecture has
none; embedding rows past the published vocabulary (the program pads it)
are zero, as they are when a real checkpoint is loaded into a padded
table, so a padded id can never win the argmax.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A JAX key from any whole-number seed, 64 bits of it kept."""
    words = np.random.SeedSequence(seed % 2 ** 64).generate_state(2)
    return jax.random.wrap_key_data(words.astype(np.uint32),
                                    impl="threefry2x32")


def _leaf_init(path: tuple[str, ...], shape, dtype, key, std: float,
               vocab: int):
    name = path[-1]
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name == "bo":
        return jnp.zeros(shape, dtype)
    x = (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
    if path[-2:] == ("embed", "table"):
        x = jnp.where(jnp.arange(shape[0])[:, None] < vocab, x,
                      jnp.zeros((), dtype))
    return x


def make_params(model, seed: int, std: float, vocab: int):
    """The program-layout parameter tree for ``model`` from ``seed``."""
    shapes, _ = model.abstract_params()
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [tuple(str(getattr(k, "key", k)) for k in p) for p, _ in flat]

    def build(key):
        leaves = [_leaf_init(path, sd.shape, sd.dtype,
                             jax.random.fold_in(key, i), std, vocab)
                  for i, (path, (_, sd)) in enumerate(zip(paths, flat))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))
