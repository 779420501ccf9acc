"""Production meshes.  Functions, not module constants — importing this module
never touches jax device state (jax locks the device count on first use, and
only launch/dryrun.py is allowed to set the 512-host-device XLA flag).
"""

from __future__ import annotations

import jax

POD_SHAPE = (16, 16)              # 256 chips per v5e pod
MULTI_POD_SHAPE = (2, 16, 16)     # 2 pods = 512 chips


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    # Auto axes: the sharding rules (repro.distributed.sharding) constrain
    # activations GSPMD-style, which jax.make_mesh's default Explicit axes
    # refuse.
    return jax.make_mesh(shape, axes, axis_types=(
        (jax.sharding.AxisType.Auto,) * len(axes)))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh for CPU tests (uses however many devices exist)."""
    return _auto_mesh((data, model), ("data", "model"))


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """The batch/ZeRO axes: ("pod","data") on multi-pod, ("data",) otherwise."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_size(mesh: jax.sharding.Mesh) -> int:
    return mesh.shape.get("model", 1)
