"""Selectivity-aware batched executor: one program, three strategies.

``execute_batch`` is the unified query entry point: it canonicalizes the
batch, asks the planner (``repro.exec.plan``) for a per-query strategy, and
dispatches the whole fixed-shape batch through ONE jitted program that
contains all three execution paths:

  * the ``GRAPH`` beam search runs with entry points masked to -1 on every
    row planned elsewhere (a masked row's beam starts empty, so the
    ``lax.while_loop`` does zero iterations of work for it);
  * ``GRAPH_WIDE`` is a second instantiation of the same search with the
    widened static (beam, expand), masked the same way;
  * ``BRUTE_VALID`` gather-scans the host-enumerated valid-id lists
    (``[B, brute_max_valid]`` int32, -1 padded — rows planned elsewhere are
    all padding and annihilate in-kernel);

then row-selects by plan. Partitioning is by *padding* (masked entry
points / padded id lists), never by ``lax.cond`` on traced shapes, so a
serving step compiles exactly once and keeps that one program across
arbitrary plan mixes and index epoch swaps — every shape is fixed by the
index capacity and the planner config.

``plan="graph"`` bypasses planning entirely and reproduces today's
single-strategy behavior (the parity oracle); ``plan="wide"`` /
``plan="brute"`` force a strategy for benchmarking.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.exec.bruteforce import brute_topk_impl, effective_norms
from repro.exec.plan import (
    PlanBatch,
    PlannerConfig,
    QueryPlan,
    default_planner_config,
    plan_queries,
)
from repro.obs.stats import SearchStats, combine_stats, stats_to_host
from repro.search.batched import _batched_search_core, prepare_states_extended

PLANS = ("auto", "graph", "wide", "brute")


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "beam", "wide_beam", "max_iters", "wide_max_iters",
        "use_ref", "fused", "expand", "wide_expand", "stats",
    ),
)
def planned_exec_core(
    vectors: jnp.ndarray,    # [n, D] f32 (or int8 with scales), or its rows
    nbr: jnp.ndarray,        # [n, E] int32
    labels: jnp.ndarray,     # packed words / label rows or [n, E, 4] int32
                             # — both graph strategies dispatch on the layout
    q: jnp.ndarray,          # [B, D]
    states: jnp.ndarray,     # [B, 2] int32
    ep_graph: jnp.ndarray,   # [B] int32 entry ids, -1 unless plan==GRAPH
    ep_wide: jnp.ndarray,    # [B] int32 entry ids, -1 unless plan==GRAPH_WIDE
    bf_ids: jnp.ndarray,     # [B, V] int32 valid ids, -1 unless plan==BRUTE
    plans: jnp.ndarray,      # [B] int32 QueryPlan values
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
    scales: jnp.ndarray | None = None,
    norms: jnp.ndarray | None = None,
    stats: bool = False,
) -> Tuple[jnp.ndarray, ...]:
    """All three strategies in one traced program + per-row plan select.

    Returns ``(ids [B, k], dists [B, k], totals i32[2, 5])``: ``totals``
    holds the always-on ``LOOP_TOTALS`` of the graph loop (row 0) and of
    the wide loop (row 1). ``stats=True`` appends a merged :class:`repro.obs.SearchStats`: each
    graph instantiation sees rows planned elsewhere as masked (ep=-1 →
    zero iterations → exact-zero counters), so the two stats pytrees merge
    by addition; ``BRUTE_VALID`` rows do no traversal and stay all-zero
    (their termination cause reads as ``no_entry``)."""
    return _planned_exec_impl(
        vectors, nbr, labels, q, states, ep_graph, ep_wide, bf_ids, plans,
        k=k, beam=beam, wide_beam=wide_beam, max_iters=max_iters,
        wide_max_iters=wide_max_iters, use_ref=use_ref, fused=fused,
        expand=expand, wide_expand=wide_expand, scales=scales, norms=norms,
        stats=stats,
    )


def _planned_exec_impl(
    vectors, nbr, labels, q, states, ep_graph, ep_wide, bf_ids, plans,
    *,
    k: int,
    beam: int,
    wide_beam: int,
    max_iters: int,
    wide_max_iters: int,
    use_ref: bool | None,
    fused: bool,
    expand: int,
    wide_expand: int,
    scales,
    norms,
    stats: bool,
) -> Tuple[jnp.ndarray, ...]:
    """Trace-time body of :func:`planned_exec_core`, shared with the
    segmented tier's :func:`worklist_exec_core` (which wraps it in its own
    jit after the in-graph segment-offset arithmetic)."""
    out_g = _batched_search_core(
        vectors, nbr, labels, q, states, ep_graph,
        k=k, beam=beam, max_iters=max_iters, use_ref=use_ref,
        fused=fused, expand=expand, scales=scales, norms=norms,
        stats=stats,
    )
    out_w = _batched_search_core(
        vectors, nbr, labels, q, states, ep_wide,
        k=k, beam=wide_beam, max_iters=wide_max_iters, use_ref=use_ref,
        fused=fused, expand=wide_expand, scales=scales, norms=norms,
        stats=stats,
    )
    ids_g, d_g = out_g[0], out_g[1]
    ids_w, d_w = out_w[0], out_w[1]
    totals = jnp.stack([out_g[2], out_w[2]])
    nrm = effective_norms(vectors, q.shape[1], scales, norms)
    ids_b, d_b = brute_topk_impl(
        vectors, nrm, q.astype(jnp.float32), bf_ids,
        k=k, use_ref=use_ref, scales=scales,
    )
    sel = plans[:, None]
    ids = jnp.where(
        sel == int(QueryPlan.GRAPH), ids_g,
        jnp.where(sel == int(QueryPlan.GRAPH_WIDE), ids_w, ids_b),
    )
    d = jnp.where(
        sel == int(QueryPlan.GRAPH), d_g,
        jnp.where(sel == int(QueryPlan.GRAPH_WIDE), d_w, d_b),
    )
    if stats:
        return ids, d, totals, combine_stats(out_g[3], out_w[3])
    return ids, d, totals


def planned_exec_cache_size() -> int:
    """Number of compiled variants of the planned executor (no-recompile
    assertions across mixed-plan batches and epoch swaps)."""
    return planned_exec_core._cache_size()


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "beam", "wide_beam", "max_iters", "wide_max_iters",
        "use_ref", "fused", "expand", "wide_expand", "stats",
        "node_cap", "n_sentinel",
    ),
)
def worklist_exec_core(
    vectors: jnp.ndarray,    # [S*node_cap, D] flat stacked storage
    nbr: jnp.ndarray,        # [S*node_cap, E] int32 — PRE-OFFSET by segment
                             # base (repro.search.device_graph.SegmentStack),
                             # so traversal is segment-closed with no per-row
                             # arithmetic in the search loop
    labels: jnp.ndarray,     # [S*node_cap, ...] segment-local label layout
    gid_table: jnp.ndarray,  # [S*node_cap] int32 flat node -> global object
                             # id (-1 on capacity padding rows)
    q: jnp.ndarray,          # [B, D] the ORIGINAL query batch
    qid: jnp.ndarray,        # [W] int32 query row per work item (== B marks
                             # bucket padding, dropped by the scatter)
    seg_ids: jnp.ndarray,    # [W] int32 segment per work item (0 on padding)
    states: jnp.ndarray,     # [W, 2] int32 segment-local canonical states
    ep_graph: jnp.ndarray,   # [W] int32 segment-LOCAL entry ids (-1 masked)
    ep_wide: jnp.ndarray,    # [W] int32
    bf_ids: jnp.ndarray,     # [W, V] int32 segment-local brute ids (-1 pad)
    plans: jnp.ndarray,      # [W] int32 QueryPlan values
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
    scales: jnp.ndarray | None = None,
    norms: jnp.ndarray | None = None,
    stats: bool = False,
    node_cap: int,
    n_sentinel: int,
) -> Tuple[jnp.ndarray, ...]:
    """One compiled dispatch for a whole routed-segment worklist.

    Each work item is one (query, segment) pair: its entry points and
    brute-path ids are offset to the flat row space in-graph, the
    three-strategy planned executor runs over the ``[W]`` worklist, results
    map through the device-resident global-id table, scatter back to
    ``[B, S, k]`` (bucket-padding items carry ``qid == B`` and drop out of
    bounds), and ONE grouped ``topk_merge`` over the segment-ordered
    ``[B, S·k]`` block folds them — bit-identical to the per-segment
    sequential fold because ids are globally unique across segments and the
    merge's ties resolve by arrival order.

    ``stats=True`` appends a ``[B]``-per-query :class:`SearchStats`:
    worklist-row counters scatter-add back to their query row (a query's
    per-segment trajectories are independent, so addition over its routed
    segments equals the legacy loop's ``combine_stats``)."""
    from repro.kernels import ops

    B = q.shape[0]
    n_flat = vectors.shape[0]
    S = n_flat // node_cap
    base = seg_ids.astype(jnp.int32) * jnp.int32(node_cap)
    ep_g = jnp.where(ep_graph >= 0, ep_graph + base, -1).astype(jnp.int32)
    ep_w = jnp.where(ep_wide >= 0, ep_wide + base, -1).astype(jnp.int32)
    bf = jnp.where(bf_ids >= 0, bf_ids + base[:, None], -1).astype(jnp.int32)
    q_w = q[jnp.clip(qid, 0, B - 1)]
    out = _planned_exec_impl(
        vectors, nbr, labels, q_w, states, ep_g, ep_w, bf, plans,
        k=k, beam=beam, wide_beam=wide_beam, max_iters=max_iters,
        wide_max_iters=wide_max_iters, use_ref=use_ref, fused=fused,
        expand=expand, wide_expand=wide_expand, scales=scales, norms=norms,
        stats=stats,
    )
    ids_f, d_w = out[0], out[1]
    glob = jnp.where(
        ids_f >= 0,
        gid_table[jnp.clip(ids_f, 0, n_flat - 1)],
        jnp.int32(-1),
    ).astype(jnp.int32)
    sc_d = jnp.full((B, S, k), jnp.inf, dtype=jnp.float32)
    sc_i = jnp.full((B, S, k), -1, dtype=jnp.int32)
    sc_d = sc_d.at[qid, seg_ids].set(d_w, mode="drop")
    sc_i = sc_i.at[qid, seg_ids].set(glob, mode="drop")
    acc_d = jnp.full((B, k), jnp.inf, dtype=jnp.float32)
    acc_i = jnp.full((B, k), -1, dtype=jnp.int32)
    ids, d = ops.topk_merge(
        acc_d, acc_i, sc_d.reshape(B, S * k), sc_i.reshape(B, S * k),
        n=n_sentinel, use_ref=use_ref,
    )
    if stats:
        st = out[3]

        def scat(v):
            return jnp.zeros(B, dtype=jnp.int32).at[qid].add(
                v.astype(jnp.int32), mode="drop"
            )

        st_b = SearchStats(
            iters=scat(st.iters),
            expanded=scat(st.expanded),
            cand_total=scat(st.cand_total),
            cand_valid=scat(st.cand_valid),
            kept=scat(st.kept),
            visited=scat(st.visited),
            beam_occupancy=scat(st.beam_occupancy),
            hit_max_iters=scat(st.hit_max_iters) > 0,
            delta_valid=scat(st.delta_valid),
        )
        return ids, d, st_b
    return ids, d


def worklist_exec_cache_size() -> int:
    """Compiled variants of the worklist scheduler program (the segmented
    tier's no-recompile gate across routed-mix / bucket changes)."""
    return worklist_exec_core._cache_size()


def mask_entry_points(
    ep: np.ndarray, plans: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Split one entry-point vector into per-strategy padded copies."""
    ep = np.asarray(ep, dtype=np.int32)
    ep_graph = np.where(plans == int(QueryPlan.GRAPH), ep, -1).astype(np.int32)
    ep_wide = np.where(
        plans == int(QueryPlan.GRAPH_WIDE), ep, -1
    ).astype(np.int32)
    return ep_graph, ep_wide


def execute_batch(
    dg,
    q: np.ndarray,
    s_q: np.ndarray,
    t_q: np.ndarray,
    *,
    k: int = 10,
    beam: int = 64,
    max_iters: Optional[int] = None,
    use_ref: bool = False,
    fused: bool = True,
    expand: int = 1,
    plan: str = "auto",
    config: Optional[PlannerConfig] = None,
    return_plans: bool = False,
    packed: bool | None = None,
    stats: bool = False,
    row_mask: Optional[np.ndarray] = None,
):
    """Planned end-to-end batched query over a ``DeviceGraph``.

    ``plan`` is one of ``"auto"`` (selectivity-aware, the default),
    ``"graph"`` (today's single-strategy behavior — the parity oracle),
    ``"wide"`` or ``"brute"`` (forced strategies, for benchmarking).
    ``packed`` selects the label layout for the graph strategies exactly
    as in ``batched_udg_search`` (``None`` = packed when exported,
    ``False`` = int32 parity oracle, ``True`` = require packed).
    ``row_mask`` (``[B]`` bool, optional) drops rows from the batch by the
    same padding dispatch the planner uses: a ``False`` row is treated as
    invalid (entry points masked to -1, brute lists empty), so it returns
    ``ids=-1 / d=+inf`` at zero traversal cost and — critically — without
    changing any traced shape. The segmented router
    (``repro.scale``) relies on this to run mixed per-segment batch
    subsets through the one compiled program.
    Returns ``(ids [B, k], dists [B, k])`` plus the ``PlanBatch`` when
    ``return_plans`` is set (``None`` for the non-auto modes) plus a
    host-side :class:`repro.obs.SearchStats` when ``stats`` is set (always
    the last element when requested).
    """
    if plan not in PLANS:
        raise ValueError(f"plan={plan!r} not in {PLANS}")
    config = config or default_planner_config()
    states, ep, invalid = prepare_states_extended(dg, s_q, t_q)
    B = states.shape[0]
    if row_mask is not None:
        row_mask = np.asarray(row_mask, dtype=bool).reshape(-1)
        if row_mask.shape[0] != B:
            raise ValueError(
                f"row_mask has {row_mask.shape[0]} rows, batch has {B}"
            )
        invalid = invalid | ~row_mask
        ep = np.where(row_mask, ep, -1).astype(np.int32)
    if plan == "auto":
        pb = plan_queries(dg.planner, states, invalid, config=config)
        plans, bf_ids = pb.plans, pb.bf_ids
    elif plan == "graph":
        pb = None
        plans = np.full(B, int(QueryPlan.GRAPH), dtype=np.int32)
        bf_ids = np.full((B, config.brute_max_valid), -1, dtype=np.int32)
    elif plan == "wide":
        pb = None
        plans = np.full(B, int(QueryPlan.GRAPH_WIDE), dtype=np.int32)
        bf_ids = np.full((B, config.brute_max_valid), -1, dtype=np.int32)
    else:  # forced brute: exact valid sets of ANY size (benchmark mode) —
        # capacity grows in power-of-two buckets, so recompiles are O(log n)
        pb = None
        if dg.planner is None:
            raise ValueError("plan='brute' requires a DeviceGraph planner")
        plans = np.full(B, int(QueryPlan.BRUTE_VALID), dtype=np.int32)
        lists = [
            np.empty(0, np.int32) if invalid[i]
            else dg.planner.exact_valid_ids(int(states[i, 0]), int(states[i, 1]))
            for i in range(B)
        ]
        cap = max(int(max((l.shape[0] for l in lists), default=1)), 1)
        cap = 1 << (cap - 1).bit_length()
        bf_ids = np.full((B, cap), -1, dtype=np.int32)
        for i, l in enumerate(lists):
            bf_ids[i, : l.shape[0]] = l
    ep_graph, ep_wide = mask_entry_points(ep, plans)
    wide_beam = max(beam * config.wide_beam_scale, beam)
    wide_expand = config.wide_expand if fused else 1
    mi = max_iters if max_iters is not None else 2 * beam
    # the wide path's iteration cap scales from the caller's cap by the
    # same factor as the beam, so an explicit max_iters latency bound is
    # honored (proportionally) on GRAPH_WIDE rows too
    dev = dg.device()   # memoized bundle — no per-batch table re-staging
    norms = dev.norms if fused else None
    lab = dg.serving_labels(fused=fused, packed=packed)
    out = planned_exec_core(
        dev.table, dev.nbr, lab,
        jnp.asarray(np.asarray(q, dtype=np.float32)),
        jnp.asarray(states),
        jnp.asarray(ep_graph), jnp.asarray(ep_wide),
        jnp.asarray(bf_ids), jnp.asarray(plans),
        k=k, beam=beam, wide_beam=wide_beam,
        max_iters=mi, wide_max_iters=mi * config.wide_beam_scale,
        use_ref=use_ref, fused=fused, expand=expand,
        wide_expand=min(wide_expand, wide_beam),
        scales=dev.scales, norms=norms, stats=stats,
    )
    ret = (np.asarray(out[0]), np.asarray(out[1]))
    if return_plans:
        ret += (pb,)
    if stats:
        ret += (stats_to_host(out[3]),)
    return ret
