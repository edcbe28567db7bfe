"""Production meshes.

Single pod: 256 chips as (16, 16) = ("data", "model").
Multi-pod:  2 pods x 256 chips as (2, 16, 16) = ("pod", "data", "model") —
the "pod" axis composes with "data" into the batch/FSDP super-axis (DCN-class
links carry only data-parallel collectives, the TPU-pod-topology-aware choice).

Defined as functions so importing this module never touches jax device state
(device count is locked at first jax init; the dry-run forces 512 host
devices *before* any import).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return jax.make_mesh(
        (n // model, model), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )


def make_reliability_mesh(n_shards: int | None = None, model: int = 1):
    """Mesh for the sharded reliability layer (DESIGN.md §13).

    ``n_shards`` data-parallel replicas (default: every available device) x
    ``model`` TP ways; the "data" axis is the reliability shard axis — one
    replica = one chip with its own rails and fault population. Unlike
    ``make_host_mesh`` this may use a *subset* of the devices, so a 1-shard
    mesh (the bit-identity anchor) can be built in a forced-8-device
    process alongside the full-width one.
    """
    import numpy as np

    n = len(jax.devices())
    if n_shards is None:
        assert n % model == 0, (n, model)
        n_shards = n // model
    assert n_shards * model <= n, (n_shards, model, n)
    devs = np.array(jax.devices()[: n_shards * model]).reshape(n_shards, model)
    return jax.sharding.Mesh(devs, ("data", "model"))
