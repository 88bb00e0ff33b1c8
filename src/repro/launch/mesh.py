"""Mesh construction (production dry-run + serving engine). Importing this
module never touches JAX device state — meshes are built only inside the
functions.

Single pod : (data=16, model=16)            = 256 chips
Multi-pod  : (pod=2, data=16, model=16)     = 512 chips
Engine     : (data=d, model=m) from ``--mesh dxm`` (launch/serve.py); each
             data row is one replicated engine lane, the model axis carries
             tensor-parallel decode (DESIGN.md §4).

All mesh construction and mesh-context entry goes through the helpers
below.
"""
from __future__ import annotations

from contextlib import contextmanager

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """jax.make_mesh with Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


@contextmanager
def mesh_context(mesh: Mesh):
    """Make ``mesh`` ambient for bare-PartitionSpec constraints."""
    with jax.set_mesh(mesh):
        yield mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None):
    """Tiny mesh over whatever devices exist (tests on CPU)."""
    n = n_devices or len(jax.devices())
    model = 1
    for cand in (4, 2, 1):
        if n % cand == 0:
            model = cand
            break
    return make_mesh((n // model, model), ("data", "model"))


# ---------------------------------------------------------------------------
# serving-engine meshes (launch/serve.py --mesh dxm; DESIGN.md §4)
# ---------------------------------------------------------------------------

def parse_mesh_spec(spec: str) -> tuple[int, int]:
    """'dxm' -> (data, model), e.g. '1x2' -> (1, 2). '' / 'none' -> (1, 1)."""
    if not spec or spec.lower() == "none":
        return (1, 1)
    parts = spec.lower().split("x")
    if len(parts) != 2:
        raise ValueError(f"--mesh expects 'DxM' (e.g. 2x2), got {spec!r}")
    d, m = int(parts[0]), int(parts[1])
    if d < 1 or m < 1:
        raise ValueError(f"mesh axes must be >= 1, got {spec!r}")
    return d, m


def make_engine_mesh(data: int, model: int) -> Mesh:
    """(data, model) mesh over the first data*model local devices."""
    devs = jax.devices()
    need = data * model
    if len(devs) < need:
        raise ValueError(
            f"mesh {data}x{model} needs {need} devices, have {len(devs)} "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"before jax initializes for CPU testing)")
    arr = np.array(devs[:need]).reshape(data, model)
    return Mesh(arr, ("data", "model"))


def lane_meshes(mesh: Mesh) -> list[Mesh]:
    """One single-axis ('model',) submesh per data row: each lane hosts one
    replicated serving engine whose params/KV pools shard over its row."""
    if "data" not in mesh.axis_names or mesh.shape["data"] == 1:
        devs = np.array(mesh.devices).reshape(-1)
        return [Mesh(devs, ("model",))]
    rows = np.array(mesh.devices).reshape(mesh.shape["data"], -1)
    return [Mesh(rows[i], ("model",)) for i in range(rows.shape[0])]


def lane_meshes_for_spec(spec: str) -> list:
    """Lane meshes for a '--mesh DxM' spec; the 1x1 spec maps to ``[None]``
    (single-device engine, seed-exact placement) so callers — the
    ``serving.build`` factory — need no special case."""
    d, m = parse_mesh_spec(spec)
    if (d, m) == (1, 1):
        return [None]
    return lane_meshes(make_engine_mesh(d, m))
