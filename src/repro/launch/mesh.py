"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device initialization — the dry-run driver
must set XLA_FLAGS *before* the first jax call and smoke tests must keep
seeing one device.

Every mesh here has ``Auto`` axes: the model code places activations with
``with_sharding_constraint`` hints (:func:`repro.distributed.sharding.
constrain`), which accept only ``Auto`` axes, while ``jax.make_mesh``
defaults to ``Explicit`` ones.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], *,
              devices: Optional[Sequence] = None):
    """Arbitrary mesh with ``Auto`` axes (elastic re-mesh path and tests)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: Optional[int] = None, *,
                   devices: Optional[Sequence] = None):
    """Mesh over ``devices`` (default: every device this process sees;
    CPU tests usually have 1)."""
    devices = list(devices) if devices is not None else jax.devices()
    model = model or 1
    return make_mesh((len(devices) // model, model), ("data", "model"),
                     devices=devices)
