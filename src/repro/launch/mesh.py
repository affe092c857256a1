"""Mesh constructors — the one place a ``jax.sharding.Mesh`` is built.

FUNCTIONS, not module constants — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS *before* the first jax call; smoke
tests and benches must keep seeing one CPU device).

Every axis is ``AxisType.Auto``: the model code pins activations with
``with_sharding_constraint`` as a hint to the partitioner, which an
``Explicit`` axis (``jax.make_mesh``'s default) would turn into an assert.

Mesh topology:
  single-pod : (16, 16)    axes ('data', 'model')   — 256 chips, fast ICI
  multi-pod  : (2, 16, 16) axes ('pod', 'data', 'model') — 2 pods over DCI

'pod' is the slow inter-pod axis: the sharding rules keep parameters off it
(pure DP), and the optional int8 gradient ring (optim/grad_compress) shrinks
its wire bytes. 'data' carries FSDP + batch, 'model' carries TP/EP.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """Mesh of ``shape`` over ``axes``, all of type Auto.

    Without ``devices`` JAX picks a topology-aware device order; with an
    explicit list (the survivors of an elastic restore) its first
    ``prod(shape)`` devices are laid out in order.
    """
    shape, axes = tuple(shape), tuple(axes)
    auto = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=auto)
    devs = np.asarray(list(devices)[:math.prod(shape)], dtype=object)
    return Mesh(devs.reshape(shape), axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over however many (CPU) devices exist — tests/examples."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return make_mesh((data, model), ("data", "model"))
