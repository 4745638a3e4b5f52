"""Production mesh builders (single-pod 16x16, multi-pod 2x16x16 v5e).

Functions, not module-level constants: importing this module never
touches jax device state (device count locks on first backend init).
Axes are Auto: the model code places activations with
``with_sharding_constraint`` (``parallel.sharding.hint``), which jax
refuses on the Explicit axes that ``jax.make_mesh`` makes by default.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 (dryrun.py "
            f"sets this automatically)")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_host_mesh(model_parallel: int = 1):
    """Degenerate mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    mp = model_parallel if n % model_parallel == 0 else 1
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
