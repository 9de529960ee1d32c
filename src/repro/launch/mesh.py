"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any JAX
import; smoke tests and benchmarks see the real single device.
"""
from __future__ import annotations

from repro.sharding.ctx import MeshCtx, make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_ctx(mesh) -> MeshCtx:
    ba = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    return MeshCtx(mesh=mesh, batch_axes=ba, model_axis="model")


def make_smoke_mesh(data: int = 1, model: int = 1):
    return make_mesh((data, model), ("data", "model"))
