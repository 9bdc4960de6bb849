"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights itself, so that the program under test and
the reference start from the same numbers without the reference taking
anything the program made.  The layout (the pytree of leaf shapes and
dtypes) is the program's parameter layout; the values follow the usual
conventions, by the leaf's name:

  * norm ``scale``: ones; norm ``bias``, projection biases and the
    biases ``b0``, ``b1``, ... of a tower's layers: zeros;
  * embedding table and output head: N(0, s) with the configuration's
    ``init_embed_std``;
  * every other matrix: N(0, 1 / fan_in), fan_in its second-to-last axis.

Each leaf's draw is keyed by its position in the flattened tree, so a leaf
does not change when another is added.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BIAS_NAMES = ("bias", "bq", "bk", "bv")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed, all 64 bits of it kept."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _draw(key, path, sds, embed_std: float):
    name, where = _leaf_name(path), _path_str(path)
    if name == "scale":
        return jnp.ones(sds.shape, sds.dtype)
    if name in BIAS_NAMES or (name[0] == "b" and name[1:].isdigit()):
        return jnp.zeros(sds.shape, sds.dtype)
    if where in ("embed/table", "head/w"):
        std = embed_std
    else:
        std = 1.0 / float(sds.shape[-2]) ** 0.5
    return (jax.random.normal(key, sds.shape, jnp.float32) * std
            ).astype(sds.dtype)


def make_params(layout, key, embed_std: float, out_shardings=None):
    """Params shaped like ``layout`` (a pytree of ShapeDtypeStruct), made
    from ``key`` on the device in one call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(layout)

    def make(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _draw(jax.random.fold_in(key, i), path, sds, embed_std)
            for i, (path, sds) in enumerate(flat)])

    return jax.jit(make, out_shardings=out_shardings)(key)


def leaf_names(layout) -> list[str]:
    """'/'-joined paths of the flattened leaves, in tree order."""
    return [_path_str(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(layout)[0]]
