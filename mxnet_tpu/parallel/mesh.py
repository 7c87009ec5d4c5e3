"""Device meshes and sharding helpers.

TPU-native replacement for the reference's device-list plumbing: where the
reference passes ``ctx=[gpu(0), gpu(1), ...]`` into Python-side batch
slicing (``executor_manager.py:13``) and reduces gradients through KVStore
merge buffers (``kvstore_local.h:135-236``), the TPU design lays devices
out in a named :class:`jax.sharding.Mesh` and lets XLA insert ICI
collectives for whatever crosses an axis ("How to Scale Your Model"
recipe: pick a mesh, annotate shardings, let XLA do the rest).

Canonical axis names (used throughout :mod:`mxnet_tpu.parallel`):

* ``data``   — batch / data parallelism (gradients psum over it)
* ``model``  — tensor parallelism (params sharded over it)
* ``seq``    — sequence/context parallelism (ring attention)
* ``pipe``   — pipeline stages
* ``expert`` — MoE expert parallelism
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..base import MXNetError

__all__ = ["make_mesh", "data_parallel_mesh", "current_mesh", "default_mesh",
           "in_manual_region", "replicated", "batch_sharding",
           "param_sharding",
           "DATA_AXIS", "MODEL_AXIS", "SEQ_AXIS", "PIPE_AXIS", "EXPERT_AXIS"]

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"

_mesh_stack: List[Mesh] = []


def make_mesh(axes: Union[Dict[str, int], Sequence[Tuple[str, int]]],
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a named mesh over ``devices`` (default: all local devices).

    ``axes`` maps axis name -> size; one size may be ``-1`` meaning
    "everything left".  Axis order is layout order: put the axis whose
    collectives are hottest (usually ``model``) innermost so it rides the
    fastest ICI links.
    """
    items = list(axes.items()) if isinstance(axes, dict) else list(axes)
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    known = 1
    wild = None
    for i, (name, size) in enumerate(items):
        if size == -1:
            if wild is not None:
                raise MXNetError("make_mesh: only one axis may be -1")
            wild = i
        else:
            known *= size
    if wild is not None:
        if n % known:
            raise MXNetError(f"make_mesh: {n} devices not divisible by {known}")
        items[wild] = (items[wild][0], n // known)
        known = n
    if known != n:
        raise MXNetError(f"make_mesh: axes {items} need {known} devices, "
                         f"have {n}")
    shape = tuple(size for _, size in items)
    names = tuple(name for name, _ in items)
    return Mesh(np.asarray(devices).reshape(shape), names)


def data_parallel_mesh(num_devices: Optional[int] = None,
                       axis: str = DATA_AXIS) -> Mesh:
    """1-D mesh over the first ``num_devices`` local devices — the analog of
    the reference's ``ctx=[gpu(i) for i in range(N)]`` device list."""
    devices = jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    return make_mesh({axis: len(devices)}, devices)


@contextlib.contextmanager
def default_mesh(mesh: Mesh):
    """Scope a default mesh (``with default_mesh(m): ...``)."""
    _mesh_stack.append(mesh)
    try:
        yield mesh
    finally:
        _mesh_stack.pop()


def current_mesh() -> Optional[Mesh]:
    return _mesh_stack[-1] if _mesh_stack else None


def in_manual_region() -> bool:
    """True while tracing inside a ``shard_map`` body.  Mosaic (Pallas
    TPU) kernels only lower in a fully manual region — GSPMD cannot
    partition the opaque custom call — so kernel wrappers ask this
    before opening one of their own.  Read off the trace context, not
    the operands: under ``check_vma=False`` no value carries a vma."""
    return bool(jax.sharding.get_abstract_mesh().manual_axes)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def batch_sharding(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Shard dim 0 (the batch) over ``axis``; everything else replicated."""
    return NamedSharding(mesh, PartitionSpec(axis))


def param_sharding(mesh: Mesh, spec: Optional[PartitionSpec]) -> NamedSharding:
    return NamedSharding(mesh, spec if spec is not None else PartitionSpec())
