"""Production mesh factory.

Defined as a FUNCTION so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).

Topology: 16x16 = 256 chips per pod (TPU v5e pod); the multi-pod mesh adds a
leading "pod" axis (2 pods = 512 chips). The "pod" axis carries only
data-parallel traffic (gradient all-reduce) — the right assignment for the
slowest (inter-pod DCN/ICI) links; "model" carries tensor-parallel
collectives inside a pod.
"""
from __future__ import annotations


import jax
from jax.sharding import AxisType

# what JAX reports as `device_kind` for the production mesh's chips
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the sharding plan places arrays with
    # with_sharding_constraint, which refuses Explicit axes
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_for(devices_or_count=None, model_parallelism: int = 16,
                  pods: int = 1):
    """Elastic variant: build the largest viable mesh from an arbitrary
    device count (see distributed.elastic)."""
    import numpy as np

    if devices_or_count is None:
        devices = jax.devices()
    elif isinstance(devices_or_count, int):
        devices = jax.devices()[:devices_or_count]
    else:
        devices = list(devices_or_count)
    from repro.distributed.elastic import shrink_mesh

    return shrink_mesh(devices, model_parallelism, pods)


def describe_mesh(mesh) -> str:
    return " x ".join(f"{n}={s}" for n, s in mesh.shape.items()) \
        + f" ({mesh.devices.size} chips)"
