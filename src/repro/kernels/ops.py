"""Jit'd public wrappers around the Pallas kernels, and the one place that
decides between a kernel and its jnp oracle.

``use_ref`` on every entry point of the search, serve and build paths
means: ``True`` the pure-jnp oracle (``repro.kernels.ref``), ``False`` the
Pallas kernel, ``None`` (their default) the backend's choice —
:func:`use_reference`: the compiled kernels on TPU, the oracle everywhere
else. Tests run on the CPU, where a kernel asked for explicitly runs under
``interpret=True`` (the kernel body executed by the Pallas interpreter,
for correctness only); on TPU a kernel is always compiled, never
interpreted, and nothing falls back to the oracle unless a caller asks for
it.

The gather kernels read tables in the row layout of
:mod:`repro.kernels.layout`. The wrappers accept a logical ``[n, d]``
table too and convert it in-graph; serving bundles store the row layout
once per export so no call pays that copy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.beam_merge import beam_merge_jnp, beam_merge_pallas
from repro.kernels.filter_dist import (
    fetch_rows,
    filter_dist_gather_packed_pallas,
    filter_dist_gather_pallas,
    filter_dist_pallas,
    label_passes,
    packed_fetch_rows,
)
from repro.kernels.int8dist import int8_l2dist_pallas, quantize_int8
from repro.kernels.l2dist import l2dist_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_reference(use_ref: bool | None = None) -> bool:
    """Resolve a caller's ``use_ref``: an explicit ``True``/``False`` is
    kept; ``None`` selects the kernels on TPU and the jnp oracle on any
    other backend."""
    if use_ref is None:
        return not _on_tpu()
    return bool(use_ref)


def _interpret() -> bool:
    """Kernels run compiled on TPU and interpreted anywhere else."""
    return not _on_tpu()


def l2dist(q: jnp.ndarray, c: jnp.ndarray, *, use_ref: bool | None = False) -> jnp.ndarray:
    """Squared-L2 distance matrix [Bq, Bc]."""
    if use_reference(use_ref):
        return ref.l2dist_ref(q, c)
    return l2dist_pallas(q, c, interpret=_interpret())


def filter_dist(
    q: jnp.ndarray,
    cand: jnp.ndarray,
    labels: jnp.ndarray,
    state: jnp.ndarray,
    cand_ids: jnp.ndarray,
    *,
    use_ref: bool | None = False,
) -> jnp.ndarray:
    """Fused label-validity + squared distance [B, E] (+inf = inactive)."""
    if use_reference(use_ref):
        return ref.filter_dist_ref(q, cand, labels, state, cand_ids)
    return filter_dist_pallas(q, cand, labels, state, cand_ids, interpret=_interpret())


def _gathered(table, norms, cand_ids, visited, scales):
    """The 4-byte per-candidate metadata the gather kernels take, gathered
    on the XLA side: cached norms, visited words and dequant scales."""
    n = table.shape[0]
    safe = jnp.clip(cand_ids, 0, n - 1)
    g_norms = norms[safe].astype(jnp.float32)
    g_words = jnp.take_along_axis(visited, safe >> 5, axis=1)
    if scales is not None:
        g_scales = scales[safe].astype(jnp.float32)
    else:
        g_scales = jnp.ones_like(g_norms)
    return g_norms, g_words, g_scales


def _count(fetch):
    return jnp.sum((fetch >= 0).astype(jnp.int32))


def filter_dist_gather(
    table: jnp.ndarray,      # [n, D] vector table (f32 or int8) or its rows
    norms: jnp.ndarray,      # [n] f32 cached ‖c‖² of the (dequantized) rows
    q: jnp.ndarray,          # [B, D]
    cand_ids: jnp.ndarray,   # [B, C] int32 candidate row ids (-1 = padding)
    labels: jnp.ndarray,     # [B, C, 4] int32
    state: jnp.ndarray,      # [B, 2] int32
    visited: jnp.ndarray,    # [B, ceil(n/32)] uint32 bit-packed visited set
    *,
    scales: jnp.ndarray | None = None,   # [n] f32 int8 dequant scales
    use_ref: bool | None = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Gather-fused label-validity + visited test + squared distance —
    ``(d [B, C], fetched)``.

    The candidate *vector rows* are gathered inside the Pallas kernel (HBM →
    VMEM DMA driven by scalar-prefetched ids) — no [B, C, D] intermediate —
    and only for candidates that can still pass: not padding, not visited,
    passing the label test (``filter_dist.fetch_rows``). ``fetched`` is the
    i32 count of those row DMAs, the same on the oracle path. Only the
    4-byte per-candidate metadata (cached norm, visited word, dequant
    scale) is gathered here on the XLA side before the call.
    """
    g_norms, g_words, g_scales = _gathered(
        table, norms, cand_ids, visited, scales)
    fetched = _count(fetch_rows(cand_ids, g_words, table.shape[0],
                                label_passes(labels, state)))
    if use_reference(use_ref):
        return ref.filter_dist_gather_ref(
            table, norms, q, cand_ids, labels, state, visited, scales
        ), fetched
    return filter_dist_gather_pallas(
        table, q, cand_ids, labels, state, g_norms, g_words, g_scales,
        interpret=_interpret(),
    ), fetched


def filter_dist_gather_packed(
    table: jnp.ndarray,      # [n, D] vector table (f32 or int8) or its rows
    plabels: jnp.ndarray,    # [n, E, 2] uint32 packed words, or their rows
    norms: jnp.ndarray,      # [n] f32 cached ‖c‖² of the (dequantized) rows
    q: jnp.ndarray,          # [B, D]
    cur_ids: jnp.ndarray,    # [B, M] int32 expanded beam nodes (-1 = none)
    cand_ids: jnp.ndarray,   # [B, M*E] int32 candidate row ids (-1 = padding)
    state: jnp.ndarray,      # [B, 2] int32
    visited: jnp.ndarray,    # [B, ceil(n/32)] uint32 bit-packed visited set
    *,
    scales: jnp.ndarray | None = None,   # [n] f32 int8 dequant scales
    use_ref: bool | None = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Packed-metadata superkernel: gather-fused label + visited test +
    squared distance, ``(d [B, M·E], fetched)``, where the label metadata
    is DMA'd in-kernel from the packed ``[n, E, 2]`` uint32 table — no
    XLA-side label gather at all. Per-candidate XLA-side traffic is the
    same 12 bytes of (norm, visited word, scale) as ``filter_dist_gather``.
    Vector rows are fetched only for candidates that are not padding and
    not visited, and a tile of a ``-1`` expanded node, or with no such
    candidate, fetches nothing (``filter_dist.packed_fetch_rows``);
    ``fetched`` counts the row DMAs, the same on the oracle path."""
    g_norms, g_words, g_scales = _gathered(
        table, norms, cand_ids, visited, scales)
    fetched = _count(packed_fetch_rows(
        cur_ids, cand_ids, g_words, table.shape[0])[1])
    if use_reference(use_ref):
        return ref.filter_dist_gather_packed_ref(
            table, plabels, norms, q, cur_ids, cand_ids, state, visited,
            scales,
        ), fetched
    return filter_dist_gather_packed_pallas(
        table, plabels, q, cur_ids, cand_ids, state, g_norms, g_words,
        g_scales, interpret=_interpret(),
    ), fetched


def beam_merge(
    beam_d: jnp.ndarray,     # [B, L] f32 ascending beam distances
    beam_ids: jnp.ndarray,   # [B, L] int32 (-1 padding)
    beam_exp: jnp.ndarray,   # [B, L] bool expanded flags
    cand_d: jnp.ndarray,     # [B, C] f32 (+inf = dead candidate)
    cand_ids: jnp.ndarray,   # [B, C] int32
    *,
    n: int,
    use_ref: bool | None = False,
):
    """Deduplicating top-L beam merge — ``(new_ids, new_d, new_exp, keep)``.

    The reference (and any backend but TPU) runs the pure-jnp formulation
    (matrix dedup + ``lax.top_k``); the kernel path on TPU runs the Pallas
    bitonic sort-and-merge network. Both are pinned bitwise — including exact
    distance ties — to the stable-``lax.sort`` oracle
    ``ref.beam_merge_ref`` in ``tests/test_kernels.py``, so path choice
    never changes results."""
    if use_reference(use_ref) or not _on_tpu():
        return beam_merge_jnp(
            beam_d, beam_ids, beam_exp, cand_d, cand_ids, n=n)
    return beam_merge_pallas(
        beam_d, beam_ids, beam_exp, cand_d, cand_ids, n=n)


def topk_merge(
    acc_d: jnp.ndarray,      # [B, L] f32 ascending (+inf padding)
    acc_ids: jnp.ndarray,    # [B, L] int32 (-1 padding)
    cand_d: jnp.ndarray,     # [B, C] f32 (+inf = dead candidate)
    cand_ids: jnp.ndarray,   # [B, C] int32
    *,
    n: int,
    use_ref: bool | None = False,
):
    """Fold a candidate block into a running ascending top-L — ``(ids, d)``.

    The segment-merge form of :func:`beam_merge`: the accumulator plays the
    beam (no expanded flags to carry) and each per-segment result block
    plays the candidates. Ids must be globally unique across live entries
    (disjoint segment memberships guarantee this); ``n`` is any bound
    strictly above every live id (the dedup sentinel). Ties at exactly
    equal distance resolve toward the accumulator, then candidate arrival
    order — so folding segments in a fixed order is deterministic, and
    both backends (jnp / Pallas bitonic) are pinned bitwise by the same
    oracle as ``beam_merge``.
    """
    exp = jnp.zeros(acc_ids.shape, dtype=bool)
    new_ids, new_d, _, _ = beam_merge(
        acc_d, acc_ids, exp, cand_d, cand_ids, n=n, use_ref=use_ref
    )
    return new_ids, new_d


def int8_l2dist(
    q: jnp.ndarray, c_q: jnp.ndarray, c_scale: jnp.ndarray, *, use_ref: bool | None = False
) -> jnp.ndarray:
    """Squared-L2 against int8-quantized candidates [Bq, Bc]."""
    if use_reference(use_ref):
        return ref.int8_l2dist_ref(q, c_q, c_scale)
    return int8_l2dist_pallas(q, c_q, c_scale, interpret=_interpret())


__all__ = [
    "beam_merge",
    "use_reference",
    "filter_dist",
    "filter_dist_gather",
    "filter_dist_gather_packed",
    "int8_l2dist",
    "l2dist",
    "quantize_int8",
    "topk_merge",
]
