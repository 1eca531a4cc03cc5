"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (device count is locked at first jax init, and only
launch/dryrun.py sets the 512-host-device XLA flag)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axis roles (see distributed/sharding.py):
      pod  — data parallel across pods (gradient sync over DCI)
      data — DP/FSDP within a pod
      model — tensor/expert parallel (heads, ffn, experts, decode kv-seq)
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh(*, multi_pod: bool = False):
    """Small mesh for CI-scale sharding validation (2x2 / 2x2x2)."""
    shape = (2, 2, 2) if multi_pod else (2, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
