"""Production mesh construction.

Single pod: (data=16, model=16) — 256 TPU v5e chips.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the 'pod' axis rides
DCN and is data-parallel by default (optionally pipeline, runtime/pipeline).

Defined as functions (not module constants) so importing never touches jax
device state — the dry-run must set XLA_FLAGS before first jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis Auto: the sharding rules of this
    repo (zoo.param_pspecs, the shard_map kernels) are written for
    compiler-propagated shardings, while jax.make_mesh now defaults to
    Explicit axes, under which e.g. the embedding gather raises a
    ShardingTypeError.  Every mesh in the repo is built here."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def data_axes(mesh) -> tuple:
    """Axes that shard the batch: ('pod', 'data') multi-pod, else ('data',)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
