"""Production mesh construction.

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches jax device state — required because the
dry-run must set XLA_FLAGS before any jax initialization.

Mesh axes:
  pod    cross-pod data parallelism (2 pods in the multi-pod dry-run)
  data   in-pod data parallel / FSDP axis (params + optimizer sharded here)
  model  tensor/expert parallel axis; also the database-shard axis for UDG
         serving
"""
from __future__ import annotations

from typing import Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    if model_parallel < 1 or n % model_parallel:
        raise ValueError(
            f"{n} device(s) cannot be split into {model_parallel} shards: "
            f"the shard count must divide the device count")
    return jax.make_mesh((n // model_parallel, model_parallel), ("data", "model"))


def mesh_axis_names(mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def data_axes(mesh) -> Tuple[str, ...]:
    """All batch-parallel axes (pod absorbed into data when present)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
