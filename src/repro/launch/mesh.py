"""The serving engine's ("data", "model") device mesh.

Host meshes adapt to however many host devices exist —
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` simulates an
N-device CPU mesh, which is what the multi-device CI lane and the
sharded-serving tests run on.

Defined as functions (never module-level constants) so importing this
module touches no jax device state. Axes are ``Auto``: the steps place
their data with sharding constraints that the compiler propagates, which
JAX's default ``Explicit`` axes would refuse (e.g. the embedding gather
from a vocab-sharded table).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def resolve_host_mesh_shape(data: Optional[int] = None,
                            model: Optional[int] = None,
                            device_count: Optional[int] = None
                            ) -> Tuple[int, int]:
    """Resolve a ``(data, model)`` host-mesh shape against the available
    devices. ``None`` axes adapt: a missing ``model`` (or both) soaks up
    whatever ``data`` leaves, a missing ``data`` fills
    ``devices / model``. Requested sizes are validated with a clear
    error instead of jax's opaque "devices cannot be reshaped".
    """
    n = jax.device_count() if device_count is None else device_count

    def _check(name: str, val: int) -> None:
        if val < 1:
            raise ValueError(f"mesh axis {name!r} must be >= 1, got {val}")
        if n % val != 0 or val > n:
            raise ValueError(
                f"mesh axis {name}={val} does not divide the {n} available "
                f"device(s); run with XLA_FLAGS="
                f"--xla_force_host_platform_device_count=<N> to simulate "
                f"more CPU devices")

    if data is None and model is None:
        data, model = n, 1
    elif data is None:
        _check("model", model)
        data = n // model
    elif model is None:
        _check("data", data)
        model = n // data
    _check("data", data)
    _check("model", model)
    if data * model != n:
        raise ValueError(
            f"mesh (data={data}, model={model}) needs {data * model} "
            f"devices but {n} are available")
    return data, model


def make_host_mesh(data: Optional[int] = None, model: Optional[int] = None):
    """Device mesh with the axes ("data", "model").

    With no arguments this adapts to ``jax.device_count()`` (all devices
    on the data axis). Explicit sizes are validated against the
    available devices; ``None`` axes are inferred (see
    ``resolve_host_mesh_shape``).
    """
    data, model = resolve_host_mesh_shape(data, model)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

