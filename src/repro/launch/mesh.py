"""Production mesh construction.

Single pod: 256 chips as (data=16, model=16) — model axis sized to one ICI
torus dimension so TP collectives stay on fastest links.  Multi-pod: 2 pods
x 256 chips as (pod=2, data=16, model=16); the pod axis crosses DCN and is
used for coarse-grained parallelism only (extra DP with one grad all-reduce
per step — optionally int8-compressed — or pipeline stages).

Functions, not module constants: importing this module must never touch JAX
device state (the dry-run sets XLA_FLAGS before first jax init).  Every
axis is in Auto mode.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Whatever this host offers (tests / local runs); elastic by device count."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
