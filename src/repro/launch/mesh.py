"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).

Topology (TPU v5e target):
  * single pod: 16 x 16 = 256 chips, axes ("data", "model")
  * multi pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model")

The "pod" axis only ever carries batch (pure DP; one grad all-reduce per
step) — DCI links between pods are ~10x scarcer than intra-pod ICI, and the
design target is 1000+ nodes: nothing below assumes pod count <= 2.

Every mesh is built with Auto axes: the sharding rules place arrays with
``with_sharding_constraint`` and let XLA propagate the rest, which
``jax.make_mesh``'s default Explicit axes refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(devices=None, *, model: int = 1):
    """The local devices (or ``devices``) as a ("data", "model") mesh with
    ``model`` devices on the tensor-parallel axis and the rest on data."""
    devices = list(jax.devices() if devices is None else devices)
    if len(devices) % model:
        raise ValueError(f"{len(devices)} devices do not split into "
                         f"model={model}")
    return make_mesh((len(devices) // model, model), ("data", "model"),
                     devices=devices)
