"""The benchmark's own weights for a ``dense_lm`` configuration: drawn
on the device from the run's seed, in one jitted call, in the type they
are served in, in the layout the program reads (its parameter tree's
structure and shapes, taken from the program's abstract parameters).
Matrices are N(0, 1/fan_in), embedding rows N(0, 1) and norm scales
1 + 0.1 N(0, 1). Stacked per-layer leaves are drawn one layer at a time
(``lax.map``), which keeps the draw's compile short."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _leaf(key, path: str, shape, stacked: bool, dtype):
    if stacked:
        return jax.lax.map(lambda k: _leaf(k, path, shape[1:], False, dtype),
                           jax.random.split(key, shape[0]))
    x = jax.random.normal(key, shape, jnp.float32)
    if path.endswith(("ln", "final_norm")):
        x = 1.0 + 0.1 * x
    elif path != "embed":
        x = x / jnp.sqrt(jnp.float32(shape[-2]))
    return x.astype(dtype)


@partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, paths: tuple, shapes: tuple, dtype):
    keys = jax.random.split(key, len(paths))
    return [_leaf(keys[i], p, shapes[i], p.startswith("layers/"), dtype)
            for i, p in enumerate(paths)]


def draw(abstract, seed: int, dtype=jnp.bfloat16):
    """A tree shaped like ``abstract`` (ShapeDtypeStructs) from ``seed``
    (any non-negative integer)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = tuple("/".join(str(getattr(k, "key", k)) for k in kp)
                  for kp, _ in flat)
    shapes = tuple(tuple(leaf.shape) for _, leaf in flat)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)
    return jax.tree_util.tree_unflatten(treedef,
                                        _draw(key, paths, shapes, dtype))
