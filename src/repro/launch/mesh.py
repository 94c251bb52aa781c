"""Production meshes (TPU v5e pods).

Importing this module never touches jax device state — meshes are built
lazily by the functions (the dry-run sets XLA_FLAGS *before* any jax
import; tests/benches see the 1 real device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.distributed.sharding import make_mining_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16)=('data','model') single pod; (2,16,16)=('pod','data','model')
    two pods = 512 chips. Uses a prefix of the available devices so the
    single-pod mesh builds in the 512-device dry-run process."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devs)} — run via "
            "launch/dryrun.py which sets xla_force_host_platform_device_count")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devs[:need])


def make_host_mesh(model_parallel: int | None = None):
    """Largest (data, model) mesh over the actually-present devices —
    used by tests, examples and CPU training runs."""
    n = len(jax.devices())
    mp = model_parallel or 1
    assert n % mp == 0
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_chips(mesh) -> int:
    return int(mesh.devices.size)


__all__ = ["make_host_mesh", "make_mining_mesh", "make_production_mesh",
           "mesh_chips"]
