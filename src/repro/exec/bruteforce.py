"""Brute-force valid-subset scan on the gather-fused Pallas kernel.

The planner's ``BRUTE_VALID`` path (and the exact ``PreFilter`` baseline,
now a thin wrapper over this module): the valid ids are enumerated exactly
on the host (``SelectivityEstimator.exact_valid_ids``), padded to a static
capacity, and the *vector rows* are gathered inside the kernel
(``ops.filter_dist_gather`` — per-row HBM DMA off scalar-prefetched ids,
cached-norm distances). No ``[B, V, D]`` intermediate, no label test needed
(all-zero rectangles + the all-zero state pass every tuple: the ids are the
valid set by construction), and ``-1`` padding is annihilated in-kernel.

Scoring matches the search paths bit-for-bit (same kernel, same
``‖c‖² − 2·q·c + ‖q‖²`` arithmetic), so brute results merge cleanly with
graph-tier results inside one executor.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.layout import gather_vectors

_INF = jnp.inf


def effective_norms(vectors, dim: int, scales=None, norms=None):
    """Cached ‖row‖² of the rows the kernel scores (dequantized if int8);
    ``vectors`` is a logical ``[n, dim]`` table or its kernel rows."""
    if norms is not None:
        return norms.astype(jnp.float32)
    v32 = gather_vectors(vectors, slice(None), dim)
    out = jnp.sum(v32 * v32, axis=1)
    if scales is not None:
        out = out * scales * scales
    return out


def brute_topk_impl(
    table: jnp.ndarray,     # [n, D] f32 (or int8 with scales), or its rows
    norms: jnp.ndarray,     # [n] f32 cached ‖row‖²
    q: jnp.ndarray,         # [B, D]
    bf_ids: jnp.ndarray,    # [B, V] int32 valid ids (-1 padded)
    *,
    k: int,
    use_ref: bool | None,
    scales: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Traceable core: gather-scan the id lists, return ascending top-k.

    Ties break toward the smaller id (stable w.r.t. the exact ground-truth
    rule in ``repro.data.workloads.ground_truth``).
    """
    B, V = bf_ids.shape
    n = table.shape[0]
    q = q.astype(jnp.float32)
    labels = jnp.zeros((B, V, 4), dtype=jnp.int32)   # all-pass rectangles
    states = jnp.zeros((B, 2), dtype=jnp.int32)
    visited = jnp.zeros((B, (n + 31) // 32), dtype=jnp.uint32)
    d, _ = ops.filter_dist_gather(
        table, norms, q, bf_ids, labels, states, visited,
        scales=scales, use_ref=use_ref,
    )
    ids = jnp.where(jnp.isfinite(d), bf_ids, -1)
    if V < k:  # degenerate capacity: pad out to the requested k
        pad_d = jnp.full((B, k - V), _INF, dtype=d.dtype)
        pad_i = jnp.full((B, k - V), -1, dtype=ids.dtype)
        d = jnp.concatenate([d, pad_d], axis=1)
        ids = jnp.concatenate([ids, pad_i], axis=1)
    # num_keys=2: distance ties break toward the smaller id (every
    # inf-distance entry already has id -1, so padding stays last among
    # finite rows). The id lists arrive in CSR (bucket, y-rank) order, so a
    # stable 1-key sort would NOT give the id tie-break the ground-truth
    # rule uses.
    sd, si = jax.lax.sort((d, ids), dimension=1, num_keys=2)
    return si[:, :k], sd[:, :k]


@functools.partial(jax.jit, static_argnames=("k", "use_ref"))
def brute_force_topk(
    table, norms, q, bf_ids, *, k: int, use_ref: bool = False, scales=None
):
    """Jitted standalone brute scan (the planned executor inlines
    ``brute_topk_impl`` instead, so mixed-plan batches stay one program)."""
    return brute_topk_impl(
        table, norms, q, bf_ids, k=k, use_ref=use_ref, scales=scales
    )
