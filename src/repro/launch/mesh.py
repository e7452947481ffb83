"""Mesh construction: the one place the program builds a ``Mesh``.

FUNCTIONS, not module-level constants, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first init).

Every mesh carries ``Auto`` axis types. ``jax.make_mesh`` defaults to
``Explicit`` axes, under which every gather, dot and scan of the model would
have to name its output sharding; the sharding rules in
``models/sharding.py`` are written for GSPMD propagation instead.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> Mesh:
    """A mesh of ``shape`` over ``axes`` with ``Auto`` axis types.

    ``devices``: use exactly these devices, in this order (a serving
    replica's contiguous block); ``None`` lets ``jax.make_mesh`` lay the
    first ``prod(shape)`` devices out for the physical topology."""
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)
    arr = np.asarray(list(devices), dtype=object).reshape(tuple(shape))
    return Mesh(arr, tuple(axes), axis_types=types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model)."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """(data, model) mesh over every device of this host (one CPU device
    gives a 1x1 mesh)."""
    n = jax.device_count()
    if n % model_parallel != 0:
        raise ValueError(f"{n} devices not divisible by mp={model_parallel}")
    return make_mesh((n // model_parallel, model_parallel),
                     ("data", "model"))


# TPU v5e hardware constants (per chip) used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link
