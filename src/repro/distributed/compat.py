"""The two mesh APIs the repo calls, in the form JAX 0.9 offers them."""
from __future__ import annotations

import jax


def shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off (the
    serving steps mix per-shard and replicated values freely)."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def abstract_mesh(shape: dict):
    """``AbstractMesh`` from an ordered ``{axis_name: size}`` dict."""
    return jax.sharding.AbstractMesh(tuple(shape.values()), tuple(shape.keys()))
