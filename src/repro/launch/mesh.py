"""Mesh construction: every mesh in the repository is built here.

One rule: all axes are ``AxisType.Auto``.  The model code states layouts
as ``with_sharding_constraint`` hints (``utils.hint``) and lets GSPMD
partition the tensor-parallel math; ``jax.make_mesh`` defaults to
``Explicit`` axes, under which those hints are type errors.

Functions, not module-level constants: importing this module never touches
jax device state.  Single pod = 256 v5e chips as (data=16, model=16);
multi-pod = 2 pods = 512 chips as (pod=2, data=16, model=16) — the DCSGD
worker set is the (pod, data) axes product.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """Mesh of ``shape`` over the first ``prod(shape)`` devices (or
    ``devices``), every axis ``Auto``."""
    shape = tuple(shape)
    if devices is None:
        devices = jax.devices()[:math.prod(shape)]
    return jax.make_mesh(shape, tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def parse_mesh(spec: str, devices=None):
    """CLI mesh spec: ``DxM`` -> (data, model); ``PxDxM`` -> (pod, data,
    model)."""
    dims = tuple(int(x) for x in spec.split("x"))
    axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    return make_mesh(dims, axes, devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
