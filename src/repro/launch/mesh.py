"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run process
must set XLA_FLAGS *before* the first jax initialization.

Mesh shapes (TPU v5e):
  single pod:  (data=16, model=16)            = 256 chips
  multi-pod:   (pod=2, data=16, model=16)     = 512 chips

The FA *worker* axis is (pod, data): p = 16 workers single-pod, 32 workers
multi-pod; the ``model`` axis carries Megatron-style tensor parallelism.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def _model_factor(n: int) -> int:
    """Widest model axis (of 4/2/1) that divides ``n`` with data > 1."""
    return next((m for m in (4, 2) if n % m == 0 and n > m), 1)


def make_host_mesh(n_devices: int | None = None):
    """(data, model) mesh over the FIRST ``n_devices`` host devices.

    The sharded-aggregation tests sweep device counts on a single host
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``), which needs
    meshes over a *prefix* of the device list — ``jax.make_mesh`` insists
    on consuming every device, so this builds the Mesh explicitly.
    """
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"make_host_mesh: asked for {n} devices but only "
                         f"{len(devs)} exist (set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count={n})")
    model = _model_factor(n)
    return Mesh(np.asarray(devs[:n]).reshape(n // model, model),
                ("data", "model"))


def worker_count(mesh) -> int:
    n = 1
    for ax in ("pod", "data"):
        if ax in mesh.shape:
            n *= mesh.shape[ax]
    return n
