"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the per-kernel sweeps in
``tests/test_kernels.py`` and the search path on every backend but TPU
(``repro.kernels.ops.use_reference``). The table arguments take the
logical ``[n, d]`` layout or the kernels' row layout
(``repro.kernels.layout``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.layout import gather_vectors, label_words

INF = jnp.float32(jnp.inf)


def l2dist_ref(q: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Squared L2 distance matrix. q: [Bq, D], c: [Bc, D] -> [Bq, Bc] f32."""
    q = q.astype(jnp.float32)
    c = c.astype(jnp.float32)
    qs = jnp.sum(q * q, axis=-1, keepdims=True)       # [Bq, 1]
    cs = jnp.sum(c * c, axis=-1)[None, :]             # [1, Bc]
    return qs - 2.0 * (q @ c.T) + cs


def filter_dist_ref(
    q: jnp.ndarray,           # [B, D] query vectors
    cand: jnp.ndarray,        # [B, E, D] gathered candidate vectors
    labels: jnp.ndarray,      # [B, E, 4] int32 label rectangles (l, r, b, e)
    state: jnp.ndarray,       # [B, 2] int32 canonical rank state (a, c)
    cand_ids: jnp.ndarray,    # [B, E] int32 (-1 = padding)
) -> jnp.ndarray:
    """Fused edge-label validity + squared distance (paper Alg. 2 line 9).

    Returns [B, E] f32: squared L2 where the tuple is active for (a, c),
    +inf otherwise (so invalid neighbors never enter the beam).
    """
    q = q.astype(jnp.float32)
    cand = cand.astype(jnp.float32)
    diff = cand - q[:, None, :]
    dist = jnp.sum(diff * diff, axis=-1)
    a = state[:, 0:1]
    cc = state[:, 1:2]
    ok = (
        (labels[..., 0] <= a)
        & (a <= labels[..., 1])
        & (labels[..., 2] <= cc)
        & (cc <= labels[..., 3])
        & (cand_ids >= 0)
    )
    return jnp.where(ok, dist, INF)


def filter_dist_gather_ref(
    table: jnp.ndarray,       # [n, D] vector table (f32 or int8) or its rows
    norms: jnp.ndarray,       # [n] f32 cached ‖c‖² (of the dequantized rows)
    q: jnp.ndarray,           # [B, D] query vectors
    cand_ids: jnp.ndarray,    # [B, C] int32 candidate row ids (-1 = padding)
    labels: jnp.ndarray,      # [B, C, 4] int32 label rectangles (l, r, b, e)
    state: jnp.ndarray,       # [B, 2] int32 canonical rank state (a, c)
    visited: jnp.ndarray,     # [B, ceil(n/32)] uint32 bit-packed visited set
    scales: jnp.ndarray | None = None,   # [n] f32 int8 dequant scales
) -> jnp.ndarray:
    """Oracle for the gather-fused kernel: gathers the candidate rows itself
    (materializing the [B, C, D] intermediate the Pallas kernel avoids) and
    applies the identical arithmetic — cached-norm distance
    ``‖c‖² − 2·q·c + ‖q‖²`` plus label-validity AND not-visited masking.

    Returns [B, C] f32: squared L2 where the tuple is active for (a, c) and
    the candidate's bit is clear in ``visited``; +inf otherwise.
    """
    n = table.shape[0]
    q = q.astype(jnp.float32)
    safe = jnp.clip(cand_ids, 0, n - 1)
    cand = gather_vectors(table, safe, q.shape[-1])   # [B, C, D]
    cross = jnp.einsum("bd,bcd->bc", q, cand)
    if scales is not None:
        cross = cross * scales[safe]
    qs = jnp.sum(q * q, axis=-1, keepdims=True)
    dist = norms[safe] - 2.0 * cross + qs
    a = state[:, 0:1]
    cc = state[:, 1:2]
    word = jnp.take_along_axis(visited, safe >> 5, axis=1)
    shift = (safe & 31).astype(jnp.uint32)
    seen = (jax.lax.shift_right_logical(word, shift)
            & jnp.uint32(1)) == jnp.uint32(1)
    ok = (
        (labels[..., 0] <= a)
        & (a <= labels[..., 1])
        & (labels[..., 2] <= cc)
        & (cc <= labels[..., 3])
        & (cand_ids >= 0)
        & ~seen
    )
    return jnp.where(ok, dist, INF)


def unpack_labels_jnp(plabels: jnp.ndarray) -> jnp.ndarray:
    """Packed uint32 word pairs ``[..., 2]`` -> int32 rectangles
    ``[..., 4]`` (l, r, b, e) — the traced twin of
    ``repro.search.device_graph.unpack_labels``; the single definition of
    the word layout on the jnp side (kernel oracle + serving steps)."""
    mask = jnp.uint32(0xFFFF)
    w0 = plabels[..., 0]
    w1 = plabels[..., 1]
    return jnp.stack(
        [
            (w0 & mask).astype(jnp.int32),
            (w0 >> 16).astype(jnp.int32),
            (w1 & mask).astype(jnp.int32),
            (w1 >> 16).astype(jnp.int32),
        ],
        axis=-1,
    )


def filter_dist_gather_packed_ref(
    table: jnp.ndarray,       # [n, D] vector table (f32 or int8) or its rows
    plabels: jnp.ndarray,     # [n, E, 2] uint32 packed words, or their rows
    norms: jnp.ndarray,       # [n] f32 cached ‖c‖²
    q: jnp.ndarray,           # [B, D] query vectors
    cur_ids: jnp.ndarray,     # [B, M] int32 expanded nodes (label rows), -1 = none
    cand_ids: jnp.ndarray,    # [B, M*E] int32 candidate row ids (-1 = padding)
    state: jnp.ndarray,       # [B, 2] int32 canonical rank state (a, c)
    visited: jnp.ndarray,     # [B, ceil(n/32)] uint32 bit-packed visited set
    scales: jnp.ndarray | None = None,   # [n] f32 int8 dequant scales
) -> jnp.ndarray:
    """Oracle for the packed-metadata superkernel: gathers the packed label
    rows of the ``M`` expanded nodes itself (the ``[B, M·E, 2]``
    intermediate the Pallas kernel avoids by DMAing label rows in-kernel),
    unpacks the 16-bit ranks, and reuses the gather-kernel oracle so the
    distance / visited arithmetic is bit-identical to the int32 path."""
    n = table.shape[0]
    B, M = cur_ids.shape
    E = cand_ids.shape[1] // M
    rows = label_words(plabels, jnp.clip(cur_ids, 0, n - 1), E)  # [B, M, E, 2]
    labels = unpack_labels_jnp(rows.reshape(B, M * E, 2))
    # a -1 expanded node marks a dead tile: all of its candidates +inf
    cand_ids = jnp.where(jnp.repeat(cur_ids >= 0, E, axis=1), cand_ids, -1)
    return filter_dist_gather_ref(
        table, norms, q, cand_ids, labels, state, visited, scales
    )


def beam_merge_ref(
    beam_d: jnp.ndarray,     # [B, L] f32 ascending beam distances
    beam_ids: jnp.ndarray,   # [B, L] int32 (-1 padding)
    beam_exp: jnp.ndarray,   # [B, L] bool expanded flags
    cand_d: jnp.ndarray,     # [B, C] f32 (+inf = dead candidate)
    cand_ids: jnp.ndarray,   # [B, C] int32
    *,
    n: int,
):
    """Stable-``lax.sort`` oracle for the top-L beam merge.

    Semantics: suppress every candidate whose id already appeared on an
    earlier *finite* candidate (keep-first), then stable-sort the
    ``[beam, candidates]`` concat by distance and keep the best L — ties
    resolve by concat position (beam first, then candidate arrival order).
    ``beam_merge_jnp`` (top_k) and ``beam_merge_pallas`` (bitonic network)
    must match this bitwise; pinned in ``tests/test_kernels.py``.
    Returns ``(new_ids, new_d, new_exp, keep)``.
    """
    from repro.kernels.beam_merge import dedup_mask

    B, L = beam_d.shape
    C = cand_d.shape[1]
    dup = dedup_mask(cand_d, cand_ids, n)
    d_dd = jnp.where(dup, INF, cand_d)
    keep = jnp.isfinite(d_dd)
    all_d = jnp.concatenate([beam_d, d_dd], axis=1)
    all_ids = jnp.concatenate([beam_ids, cand_ids], axis=1)
    all_exp = jnp.concatenate([beam_exp, ~keep], axis=1)
    sd, si, se = jax.lax.sort(
        (all_d, all_ids, all_exp), dimension=1, num_keys=1, is_stable=True
    )
    return si[:, :L], sd[:, :L], se[:, :L], keep


def int8_l2dist_ref(
    q: jnp.ndarray,        # [Bq, D] f32 queries
    c_q: jnp.ndarray,      # [Bc, D] int8 quantized candidates
    c_scale: jnp.ndarray,  # [Bc] f32 per-vector dequant scales
) -> jnp.ndarray:
    """Squared L2 against int8-quantized vectors (c ~ c_q * scale)."""
    c = c_q.astype(jnp.float32) * c_scale[:, None]
    return l2dist_ref(q, c)
