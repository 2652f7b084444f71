"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required for the smoke tests, which must see a
single CPU device.
"""
from __future__ import annotations

import jax
import numpy as np


def _auto(axes):
    """Auto axis types: the sharding rules place arrays with
    ``with_sharding_constraint``, which explicit axes refuse."""
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one v5e pod's worth of chips) or 2x16x16 (two pods).

    The dry-run process forces 512 host devices; the single-pod mesh uses
    the first 256, so both meshes are constructible in one process.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devs)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return jax.make_mesh(shape, axes, _auto(axes), devices=devs[:n])


def make_host_mesh(data: int = 2, model: int = 2, pod: int = 1):
    """Small mesh over however many host devices exist (tests/examples)."""
    shape = (pod, data, model) if pod > 1 else (data, model)
    axes = ("pod", "data", "model") if pod > 1 else ("data", "model")
    n = int(np.prod(shape))
    return jax.make_mesh(shape, axes, _auto(axes), devices=jax.devices()[:n])
