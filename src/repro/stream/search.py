"""Fused two-tier streaming search (jit-able, one static shape per epoch).

One jitted step searches both tiers and merges:

  graph tier   lockstep beam search over the compacted UDG
               (``_batched_search_core`` asked for the full beam; with the
               packed ``[N, E, 2]`` uint32 label layout this is the
               packed-metadata superkernel path — in-kernel HBM row + label
               DMA, cached norms, bit-packed visited, beam-merge primitive),
               then tombstone-masked — deleted nodes still *route* (soft
               delete, as in FreshDiskANN) but never surface in results;
  delta tier   masked brute-force scan of the statically-padded delta
               segment through the same gather-fused Pallas kernel (label
               rectangles in monotone float-key space; slot ids double as
               the gather indices, so the ``[B, C, d]`` broadcast of the
               old scan disappears);
  merge        single ascending sort over the concatenated candidate lists,
               keep the best k, reporting *external* ids.

Every array argument has a capacity-fixed shape, so epoch swaps (compaction
publishing a new graph tier + drained delta) hit the same jit cache entry —
no recompilation while serving. ``fused=False`` selects the pre-gather
baseline in both tiers for parity testing.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.exec.executor import planned_exec_core
from repro.kernels import ops
from repro.obs.stats import LOOP_TOTALS, SearchStats
from repro.search.batched import _batched_search_core


def two_tier_merge(
    ids_g: jnp.ndarray,        # [B, L] graph-tier beam ids (node space)
    d_g: jnp.ndarray,          # [B, L] graph-tier distances
    live: jnp.ndarray,         # [N] bool
    ext_ids: jnp.ndarray,      # [N] int32
    q: jnp.ndarray,            # [B, d] f32
    dvec: jnp.ndarray,         # [C, d] delta tier
    dlab: jnp.ndarray,         # [C, 4] int32
    dids: jnp.ndarray,         # [C] int32
    dext: jnp.ndarray,         # [C] int32
    dstate: jnp.ndarray,       # [B, 2] int32
    *,
    k: int,
    use_ref: bool | None,
    fused: bool = True,
    st: SearchStats | None = None,   # graph-tier stats to annotate
) -> Tuple[jnp.ndarray, ...]:
    """Tombstone-mask the graph beam, scan the delta tier through the fused
    kernel, and merge to the best k external ids. Shared by the single-host
    streaming step and the per-shard body of the mesh serving step. When a
    graph-tier ``st`` is passed, it is returned with ``delta_valid`` set to
    the per-query count of delta-tier candidates passing the filter."""
    n = live.shape[0]
    B, d = q.shape
    C = dvec.shape[0]
    safe = jnp.clip(ids_g, 0, n - 1)
    ok = (ids_g >= 0) & live[safe]
    d_g = jnp.where(ok, d_g, jnp.inf)
    eid_g = jnp.where(ok, ext_ids[safe], -1)

    lab = jnp.broadcast_to(dlab[None], (B, C, 4))
    slot = jnp.broadcast_to(dids[None], (B, C))
    if fused:
        # slot ids double as gather indices (dead slots are -1 → masked);
        # the delta is append-only within an epoch, so norms of the fixed
        # [C, d] buffer are one tiny reduction per step, not per candidate
        dnorms = jnp.sum(dvec.astype(jnp.float32) ** 2, axis=1)
        dvis = jnp.zeros((B, (C + 31) // 32), dtype=jnp.uint32)
        d_d, _ = ops.filter_dist_gather(
            dvec, dnorms, q, slot, lab, dstate, dvis, use_ref=use_ref
        )
    else:
        cand = jnp.broadcast_to(dvec[None], (B, C, d))
        d_d = ops.filter_dist(q, cand, lab, dstate, slot, use_ref=use_ref)
    eid_d = jnp.where(jnp.isfinite(d_d), dext[None], -1)

    all_d = jnp.concatenate([d_g, d_d], axis=1)
    all_e = jnp.concatenate([eid_g, eid_d], axis=1)
    sd, se = jax.lax.sort((all_d, all_e), dimension=1, num_keys=1)
    if st is not None:
        st = st._replace(
            delta_valid=jnp.sum(jnp.isfinite(d_d).astype(jnp.int32), axis=1)
        )
        return se[:, :k], sd[:, :k], st
    return se[:, :k], sd[:, :k]


@functools.partial(
    jax.jit,
    static_argnames=("k", "beam", "max_iters", "use_ref", "fused", "stats"),
)
def streaming_search_core(
    vectors: jnp.ndarray,      # [N, d]  compacted tier (capacity-padded)
    nbr: jnp.ndarray,          # [N, E] int32
    labels: jnp.ndarray,       # [N, E, 2] uint32 packed (or [N, E, 4] int32)
    live: jnp.ndarray,         # [N] bool   (False = tombstoned or padding)
    ext_ids: jnp.ndarray,      # [N] int32  external id per node (-1 padding)
    dvec: jnp.ndarray,         # [C, d]  delta tier
    dlab: jnp.ndarray,         # [C, 4] int32 key-space rectangles
    dids: jnp.ndarray,         # [C] int32 slot ids (-1 = dead)
    dext: jnp.ndarray,         # [C] int32 external ids (-1 = dead)
    q: jnp.ndarray,            # [B, d]
    states: jnp.ndarray,       # [B, 2] int32 canonical rank state (graph tier)
    ep: jnp.ndarray,           # [B] int32 entry nodes (-1 = empty valid set)
    dstate: jnp.ndarray,       # [B, 2] int32 float-key state (delta tier)
    *,
    k: int,
    beam: int,
    max_iters: int,
    use_ref: bool | None,
    fused: bool = True,
    norms: jnp.ndarray | None = None,   # [N] f32 cached graph-tier norms
    stats: bool = False,
) -> Tuple[jnp.ndarray, ...]:
    q = q.astype(jnp.float32)
    out = _batched_search_core(
        vectors, nbr, labels, q, states, ep,
        k=beam, beam=beam, max_iters=max_iters, use_ref=use_ref,
        fused=fused, norms=norms, stats=stats,
    )
    ids_g, d_g = out[0], out[1]
    merged = two_tier_merge(
        ids_g, d_g, live, ext_ids, q, dvec, dlab, dids, dext, dstate,
        k=k, use_ref=use_ref, fused=fused,
        st=out[3] if stats else None,
    )
    return merged[:2] + (out[2].reshape(-1, len(LOOP_TOTALS)),) + merged[2:]


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "beam", "wide_beam", "max_iters", "wide_max_iters",
        "use_ref", "fused", "expand", "wide_expand", "stats",
    ),
)
def planned_streaming_search_core(
    vectors: jnp.ndarray,      # [N, d]  compacted tier (capacity-padded)
    nbr: jnp.ndarray,          # [N, E] int32
    labels: jnp.ndarray,       # [N, E, 2] uint32 packed (or [N, E, 4] int32)
    live: jnp.ndarray,         # [N] bool
    ext_ids: jnp.ndarray,      # [N] int32
    dvec: jnp.ndarray,         # [C, d]  delta tier
    dlab: jnp.ndarray,         # [C, 4] int32
    dids: jnp.ndarray,         # [C] int32
    dext: jnp.ndarray,         # [C] int32
    q: jnp.ndarray,            # [B, d]
    states: jnp.ndarray,       # [B, 2] int32 graph-tier rank state
    ep_graph: jnp.ndarray,     # [B] int32 entry ids (-1 unless plan GRAPH)
    ep_wide: jnp.ndarray,      # [B] int32 entry ids (-1 unless plan WIDE)
    bf_ids: jnp.ndarray,       # [B, V] int32 brute valid ids (-1 padded)
    plans: jnp.ndarray,        # [B] int32 QueryPlan values
    dstate: jnp.ndarray,       # [B, 2] int32 delta-tier float-key state
    *,
    k: int,
    beam: int,
    wide_beam: int,
    max_iters: int,
    wide_max_iters: int,
    use_ref: bool | None,
    fused: bool = True,
    expand: int = 1,
    wide_expand: int = 1,
    norms: jnp.ndarray | None = None,
    stats: bool = False,
) -> Tuple[jnp.ndarray, ...]:
    """Planner-routed variant of :func:`streaming_search_core`.

    Both return ``(ext ids [B, k], dists [B, k], totals i32[P, 5])`` —
    ``totals`` the always-on ``LOOP_TOTALS`` of each padded loop, P = 1
    here (``GRAPH``) and 2 in the planned step (``GRAPH``, ``GRAPH_WIDE``)
    — and, with ``stats=True``, a :class:`repro.obs.SearchStats` last.

    The graph tier runs through the three-way planned executor (graph /
    wide / brute-valid, padding-dispatched — one compiled program for any
    plan mix); the delta scan and tombstone-masked merge are unchanged.
    The graph tier is asked for ``beam`` candidates (not ``k``) so that
    tombstone masking in the merge has the same depth to draw on as the
    unplanned path."""
    q = q.astype(jnp.float32)
    out = planned_exec_core(
        vectors, nbr, labels, q, states, ep_graph, ep_wide, bf_ids, plans,
        k=beam, beam=beam, wide_beam=wide_beam,
        max_iters=max_iters, wide_max_iters=wide_max_iters,
        use_ref=use_ref, fused=fused, expand=expand,
        wide_expand=wide_expand, norms=norms, stats=stats,
    )
    ids_g, d_g = out[0], out[1]
    merged = two_tier_merge(
        ids_g, d_g, live, ext_ids, q, dvec, dlab, dids, dext, dstate,
        k=k, use_ref=use_ref, fused=fused,
        st=out[3] if stats else None,
    )
    return merged[:2] + (out[2].reshape(-1, len(LOOP_TOTALS)),) + merged[2:]


def streaming_search_cache_size() -> int:
    """Number of compiled variants of the streaming steps (epoch-swap
    check): plain + planner-routed cores combined, so the no-recompile
    assertions cover whichever path served the queries."""
    return (
        streaming_search_core._cache_size()
        + planned_streaming_search_core._cache_size()
    )
