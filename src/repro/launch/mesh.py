"""Production mesh builders (functions, not constants — importing this module
never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod adds a leading 2-pod axis (512 chips).

    Axes: 'pod' (outer data parallel, DCN-ish), 'data' (in-pod data parallel),
    'model' (tensor parallel over ICI).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — used by tests."""
    return jax.make_mesh(
        (data, model), ("data", "model"), (AxisType.Auto, AxisType.Auto)
    )
