"""Mesh context threaded through model code.

``MeshCtx`` names the mesh axes used by the model layer implementations
(shard_map MoE dispatch, sharding constraints). ``batch_axes`` is
``("data",)`` single-pod or ``("pod", "data")`` multi-pod.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class MeshCtx:
    mesh: Mesh
    batch_axes: tuple[str, ...] = ("data",)
    model_axis: str = "model"

    @property
    def model_size(self) -> int:
        return int(self.mesh.shape[self.model_axis])

    @property
    def data_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))


def constrain(x, ctx: "MeshCtx | None", *dims):
    """with_sharding_constraint helper: 'B' -> batch axes, 'M' -> model
    axis, None -> replicated; dims whose size doesn't divide the assigned
    axes stay replicated."""
    import jax
    if ctx is None or ctx.mesh.size == 1:
        return x
    spec = []
    for i, d in enumerate(dims):
        if d == "B":
            spec.append(ctx.batch_axes if x.shape[i] % ctx.data_size == 0
                        else None)
        elif d == "M":
            spec.append(ctx.model_axis if x.shape[i] % ctx.model_size == 0
                        else None)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, ctx.sharding(*spec))


def make_mesh(shape, axis_names) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: jax defaults to
    ``Explicit`` axes, and the model's sharding constraints are written
    for ``Auto``."""
    return jax.make_mesh(shape, axis_names, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axis_names))


def trivial_ctx() -> MeshCtx:
    """1x1 mesh on the default device — used by CPU smoke tests."""
    mesh = make_mesh((1, 1), ("data", "model"))
    return MeshCtx(mesh=mesh, batch_axes=("data",), model_axis="model")
