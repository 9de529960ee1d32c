"""Parameter-spec system.

Each parameter is declared once as a ``Param`` (shape + logical axes +
initializer). From the spec tree we derive, consistently:

* materialized parameters           (``init_params``)
* ShapeDtypeStruct stand-ins        (``abstract_params``) — dry-run, no alloc
* logical-axes tree                 (``logical_axes``) → mesh shardings

Logical axis vocabulary (mapped to mesh axes by ``repro.sharding.rules``):
  "embed"   — model width D            (FSDP'd over data for params)
  "vocab"   — vocabulary               (TP over model)
  "heads"   — attention head blocks    (TP over model)
  "kv_heads"— kv head blocks
  "mlp"     — FFN hidden               (TP over model)
  "experts" — MoE expert dim           (EP over model)
  "layers"  — stacked scan dim         (never sharded; PP would split it)
  None      — replicated dim
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Param(NamedTuple):
    shape: tuple[int, ...]
    axes: tuple[Any, ...]           # logical axis name (str) or None per dim
    init: str = "normal"            # normal|zeros|ones|embed
    scale: float = 0.0              # 0 -> 1/sqrt(fan_in) (last-dim-out conv.)

    def fan_in(self) -> int:
        return int(np.prod(self.shape[:-1])) if len(self.shape) > 1 else 1


def is_param(x) -> bool:
    return isinstance(x, Param)


def tree_map_params(fn, specs):
    return jax.tree.map(fn, specs, is_leaf=is_param)


def stack_specs(specs, n: int, axis_name: str = "layers"):
    """Prepend a stacked (scan) dimension to every spec in the subtree."""
    return tree_map_params(
        lambda p: Param((n,) + p.shape, (axis_name,) + p.axes, p.init, p.scale),
        specs)


def abstract_params(specs, dtype):
    return tree_map_params(
        lambda p: jax.ShapeDtypeStruct(p.shape, dtype), specs)


def logical_axes(specs):
    return tree_map_params(lambda p: p.axes, specs)


def _draw_leaf(key, p: Param, dtype):
    if p.init == "zeros":
        return jnp.zeros(p.shape, dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, dtype)
    scale = p.scale if p.scale else 1.0 / np.sqrt(max(p.fan_in(), 1))
    if p.init == "embed":
        scale = 0.02

    def normal(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * float(scale)).astype(dtype)
    if p.axes[:1] == ("layers",):
        # a stacked leaf is drawn one layer at a time, under a key per
        # layer: the TPU compiler's time for one draw grows with its
        # size, and a loop over layers compiles as one layer does
        return jax.lax.map(lambda k: normal(k, p.shape[1:]),
                           jax.random.split(key, p.shape[0]))
    return normal(key, p.shape)


@partial(jax.jit, static_argnums=(1, 2))
def _draw(key, leaves: tuple, dtype):
    # one program for the whole tree, compiled once per spec tree; each
    # leaf's float32 draw fuses with its cast, so a leaf costs its own
    # bytes in ``dtype`` on the device
    keys = jax.random.split(key, max(len(leaves), 1))
    return [_draw_leaf(keys[i], p, dtype) for i, p in enumerate(leaves)]


def init_params(specs, key, dtype):
    """Materialize parameters on the default device. Deterministic: a
    key per leaf, split again per layer for stacked leaves."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_param)
    return jax.tree.unflatten(treedef, _draw(key, tuple(leaves), dtype))


def count_specs(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=is_param)
    return int(sum(np.prod(p.shape) for p in leaves))
