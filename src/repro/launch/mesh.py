"""Production meshes.

TPU v5e: one pod = 256 chips. Single-pod mesh is ``(data=16, model=16)``;
multi-pod adds a leading pure-DP ``pod`` axis mapped onto DCN:
``(pod=2, data=16, model=16)`` = 512 chips. Functions, not module
constants — importing this module never touches jax device state.

For the dry-run on this CPU-only box, ``launch/dryrun.py`` sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; these builders then slice however many placeholder devices each
mesh needs.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax


def _mk(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devs)} — "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"before the first jax import (dryrun.py does this)")
    return jax.make_mesh(shape, axes, devices=devs[:need],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(data: int, model: int, pod: Optional[int] = None):
    """Arbitrary mesh for tests / small boxes (e.g. (4, 2) on 8 CPUs)."""
    if pod:
        return _mk((pod, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))


def make_serving_mesh(model: int):
    """Tensor-parallel serving mesh for the InferenceEngine: one
    ``model`` axis of `model` devices (the data axis is size 1 — the
    engine's slot pool is one replica; scale-out across replicas is
    DP at the request-router level, not inside one engine)."""
    return _mk((1, model), ("data", "model"))
