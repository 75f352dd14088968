"""Production mesh factory. Importing this module never touches jax device
state; call the functions."""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(devices: np.ndarray, axes) -> Mesh:
    """Mesh over ``devices`` with every axis ``AxisType.Auto``."""
    return Mesh(devices, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """(16,16) 'data','model' single pod (256 chips, v5e) or
    (2,16,16) 'pod','data','model' for 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)}; "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512")
    dev = np.array(devices[:n]).reshape(shape)
    return make_mesh(dev, axes)


def make_host_mesh(shape=(1, 1), axes=("data", "model")) -> Mesh:
    """Small mesh over however many (host) devices exist — smoke tests."""
    n = int(np.prod(shape))
    dev = np.array(jax.devices()[:n]).reshape(shape)
    return make_mesh(dev, axes)


def cohort_shape(r: int, n_dev: int):
    """(pod, data) extents for a cohort of r clients on n_dev devices: the
    total is the LARGEST divisor of r that fits, so an awkward r degrades to
    fewer shards — and ultimately to (1, 1), the replicated single-device
    path — instead of failing to lower (the same drop-to-replicated
    convention as ``sharding.rules.resolve_spec``). The shard count is split
    pod-major with pod <= data (pods are the scarcer physical unit)."""
    n = min(max(int(n_dev), 1), max(int(r), 1))
    while n > 1 and r % n:
        n -= 1
    pod = 1
    for p in range(int(n ** 0.5), 0, -1):
        if n % p == 0:
            pod = p
            break
    return pod, n // pod


def make_cohort_mesh(r: int, *, devices=None) -> Mesh:
    """('pod', 'data') mesh for sharded cohort execution (DESIGN.md §7):
    each of the r selected FL clients lives on exactly one mesh slot, so
    the AirComp sum is a physical cross-device psum. Degrades via
    :func:`cohort_shape` when r does not divide the device count."""
    devices = list(jax.devices()) if devices is None else list(devices)
    pod, data = cohort_shape(r, len(devices))
    dev = np.array(devices[: pod * data]).reshape(pod, data)
    return make_mesh(dev, ("pod", "data"))
