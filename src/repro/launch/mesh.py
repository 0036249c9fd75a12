"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the pod axis carries
pure data parallelism by default (one cross-pod gradient all-reduce per
step) and can alternatively host pipeline stages (dist.pipeline).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization)."""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    # Auto axes: the serving and training paths place arrays with
    # shardings the compiler propagates, not with sharding-typed arrays
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_serving_mesh(data: int = 0):
    """Pure data-parallel mesh for the DCNN bucket-serving / WGAN paths:
    one ``data`` axis over ``data`` devices (default: every visible
    device).  Params replicate; only the batch dim shards."""
    n = data or len(jax.devices())
    return _make_mesh((n,), ("data",))


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for host-device tests (subprocesses set
    --xla_force_host_platform_device_count accordingly)."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))
