"""Production meshes.

``make_production_mesh`` is the physical mesh mandated by the deployment:
one pod = (data=16, model=16) = 256 chips; two pods = (pod=2, data=16,
model=16) = 512 chips.

``make_train_mesh`` is the per-architecture logical view: the 16-wide
"model" axis is factored into (stage, tensor) for the pipeline engine
(DESIGN.md §3). Both are FUNCTIONS so importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
import numpy as np

MODEL_AXIS = 16
DATA_AXIS = 16
NUM_PODS = 2


def axis_types_kwarg(n: int) -> dict:
    """``axis_types=`` kwarg for ``jax.make_mesh`` / ``jax.sharding.Mesh``:
    every axis Auto, so jit resolves ``P(...)`` names from the ambient
    mesh that ``jax.set_mesh`` installs."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (NUM_PODS, DATA_AXIS, MODEL_AXIS) if multi_pod \
        else (DATA_AXIS, MODEL_AXIS)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **axis_types_kwarg(len(axes)))


def make_train_mesh(pipeline_stages: int, tensor_parallel: int, *,
                    extra_data: int = 1, multi_pod: bool = False,
                    devices=None):
    """Logical mesh (pod?, data, extra?, stage, tensor) over the same device
    order as the production mesh — stage x tensor x extra_data tiles the
    contiguous model axis (extra_data becomes additional data parallelism)."""
    assert pipeline_stages * tensor_parallel * extra_data == MODEL_AXIS, \
        (pipeline_stages, tensor_parallel, extra_data)
    devices = devices if devices is not None else jax.devices()
    n = (NUM_PODS if multi_pod else 1) * DATA_AXIS * MODEL_AXIS
    assert len(devices) >= n, (len(devices), n)
    arr = np.asarray(devices[:n])
    shape = (DATA_AXIS, extra_data, pipeline_stages, tensor_parallel)
    names = ("data", "extra", "stage", "tensor")
    if multi_pod:
        shape = (NUM_PODS,) + shape
        names = ("pod",) + names
    if extra_data == 1:
        shape = tuple(s for s, nm in zip(shape, names) if nm != "extra")
        names = tuple(nm for nm in names if nm != "extra")
    return jax.sharding.Mesh(
        arr.reshape(shape), names, **axis_types_kwarg(len(names)))


def make_debug_mesh(data: int = 2, stage: int = 2, tensor: int = 2):
    """Small host-device mesh for CPU tests (requires
    --xla_force_host_platform_device_count >= data*stage*tensor)."""
    return jax.make_mesh(
        (data, stage, tensor), ("data", "stage", "tensor"),
        **axis_types_kwarg(3))
