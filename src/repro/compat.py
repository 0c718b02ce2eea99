"""Choices that follow from the installed JAX and the platform it runs on.

``make_mesh`` is the one constructor every mesh in this repo goes through:
``jax.make_mesh`` without ``axis_types`` gives Explicit axes, whose
sharding-in-types rules reject the GSPMD-propagated shardings this code
relies on (``ShardingTypeError``), so all meshes here are Auto.

``pallas_interpret`` resolves every ``interpret=None`` in the repo: Pallas
kernels run interpreted off a TPU and compiled on one.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axis_names, *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types on every axis."""
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def pallas_interpret(interpret: bool | None = None) -> bool:
    """Pallas interpret mode: ``interpret`` when given, else on exactly
    when JAX's default backend is not a TPU (a CPU cannot compile Mosaic
    kernels; a TPU must never fall back to the interpreter)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
