"""The benchmark's own weights for a serve kind's configuration: drawn
on the device from the run's seed, in one jitted call, in the type they
are served in, in the layout the program reads (its parameter tree's
structure and shapes, taken from the program's abstract parameters).
A leaf whose path ends in ``ln`` or ``norm`` is a norm scale,
1 + 0.1 N(0, 1); the embedding's rows are N(0, 1); every other leaf is
a matrix (or a stack of them, as experts are) drawn N(0, 1/fan_in), its
fan-in the second-to-last size. A 1-D leaf that is no norm scale has no
fan-in and is an error. Leaves under a top-level key that ends in
``layers`` are stacked per layer and drawn one layer at a time
(``lax.map``), which keeps the draw's compile short."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from chipbench.harness import BenchError


def _leaf(key, path: str, shape, stacked: bool, dtype):
    if stacked:
        return jax.lax.map(lambda k: _leaf(k, path, shape[1:], False, dtype),
                           jax.random.split(key, shape[0]))
    x = jax.random.normal(key, shape, jnp.float32)
    if _is_norm(path):
        x = 1.0 + 0.1 * x
    elif path != "embed":
        x = x / jnp.sqrt(jnp.float32(shape[-2]))
    return x.astype(dtype)


def _is_norm(path: str) -> bool:
    return path.endswith(("ln", "norm"))


def _stacked(path: str) -> bool:
    return path.split("/", 1)[0].endswith("layers") and "/" in path


@partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, paths: tuple, shapes: tuple, dtype):
    keys = jax.random.split(key, len(paths))
    return [_leaf(keys[i], p, shapes[i], _stacked(p), dtype)
            for i, p in enumerate(paths)]


def draw(abstract, seed: int, dtype=jnp.bfloat16):
    """A tree shaped like ``abstract`` (ShapeDtypeStructs) from ``seed``
    (any non-negative integer)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = tuple("/".join(str(getattr(k, "key", k)) for k in kp)
                  for kp, _ in flat)
    shapes = tuple(tuple(leaf.shape) for _, leaf in flat)
    for p, shape in zip(paths, shapes):
        if len(shape) - _stacked(p) < 2 and not _is_norm(p):
            raise BenchError(f"weight {p} {shape}: a 1-D leaf that is no "
                             f"norm scale (a path ending in ln or norm) "
                             f"has no fan-in to draw it by")
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)
    return jax.tree_util.tree_unflatten(treedef,
                                        _draw(key, paths, shapes, dtype))
