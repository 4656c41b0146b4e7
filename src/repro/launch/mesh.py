"""Mesh construction.

FUNCTIONS, not module constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).

Axes:
  data   — batch parallelism + FSDP shard axis for params/optimizer
  model  — tensor parallelism (heads / mlp / vocab / experts)
  pod    — the multi-pod axis; composes with data for batch parallelism,
           giving elastic scaling across pod counts (checkpoints restore
           onto any mesh shape, dist/checkpoint reshards).

Every mesh has Auto axis types: the model constrains activations with
logical-axis `with_sharding_constraint` specs (dist/sharding.py), which
jax accepts only on Auto axes (`jax.make_mesh` defaults to Explicit).
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Mesh of `shape` over the first prod(shape) devices present;
    raises if the host has fewer."""
    n = math.prod(shape)
    devices = jax.devices()
    if n > len(devices):
        raise ValueError(
            f"mesh {shape} over {axes} needs {n} devices, "
            f"{len(devices)} present")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices[:n])


def parse_mesh(spec: str) -> tuple[int, int]:
    """'DxM' -> (data, model), e.g. '2x2' -> (2, 2)."""
    try:
        data, model = (int(v) for v in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"mesh {spec!r} is not DATAxMODEL, e.g. '2x2'") from None
    if data < 1 or model < 1:
        raise ValueError(f"mesh {spec!r} needs positive axis sizes")
    return data, model


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh clamped to however many devices the host has
    (tests)."""
    n = len(jax.devices())
    d = min(data, n)
    m = min(model, n // d)
    return make_mesh((d, m), ("data", "model"))
