"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (required so smoke tests see 1 device while the
dry-run sees 512 fake hosts)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi_pod adds a 2-pod DCN axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_tp_mesh(tp: int):
    """1-D tensor-parallel mesh over the first ``tp`` local devices (the
    serving engine's ``ServeEngine(tp=N)`` mesh, DESIGN.md §11).  On a TPU
    host these are chips; a CPU run (``JAX_PLATFORMS=cpu``) gets them from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    import numpy as np
    devs = jax.devices()
    if len(devs) < tp:
        raise RuntimeError(
            f"tp={tp} needs {tp} devices but only {len(devs)} "
            f"{devs[0].platform} devices are visible; run on a {tp}-chip "
            "host, or on the CPU with JAX_PLATFORMS=cpu and XLA_FLAGS="
            f"--xla_force_host_platform_device_count={tp}")
    return jax.sharding.Mesh(np.array(devs[:tp]), ("model",))


def tp_size(mesh) -> int:
    return mesh.shape["model"]


def dp_size(mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n
