"""Segmented UDG: per-segment subgraphs + coarse routing + int8/rerank.

The scale-out form of the index (ROADMAP item 1). The normalized dominance
space is partitioned by :class:`repro.scale.partition.SegmentGrid`; every
non-empty cell becomes a *segment* holding an independent UDG subgraph
over its members, exported in the PR5 packed-label device layout. Queries
flow through three stages:

1. **route** — the grid's corner test selects the cells a query's
   dominance rectangle can intersect at all (recall-safe: over-selects,
   never drops — see ``partition.py``), then each routed segment's
   ``SelectivityEstimator`` refines with its histogram upper bound
   (``hi == 0`` ⇒ the segment provably holds no valid object ⇒ skip,
   equally recall-safe).
2. **execute** — every routed segment runs the whole batch through the
   existing one-compiled-program padding dispatch
   (``exec.executor.execute_batch``) with ``row_mask`` masking the rows
   not routed to it. All segments share one ``node_capacity`` /
   ``edge_capacity`` / label layout, and masking is by padding (entry
   points → -1), so ANY mix of segment counts reuses the same two
   compiled programs (executor + merge fold) — pinned by the jit-cache
   test in ``tests/test_segmented.py``.
3. **merge + rerank** — per-segment top-``fetch`` results (local ids
   mapped to global) fold into one running top-``fetch`` via
   ``ops.topk_merge`` (fixed shapes ⇒ one compile), then a float32
   **exact rerank tail** re-scores the fused candidates against the
   original vectors and emits the final top-k with the ground-truth tie
   rule (distance, then smaller id). int8 residency (``quantize_int8``)
   is the *default* at scale — the rerank tail is what lets the resident
   layout drop to 1 byte/dim without giving up exact final ordering.

Segment membership is disjoint, so global ids never collide in the merge;
distances from int8 segments are dequantized-row distances (the documented
``export_device_graph`` contract) and are replaced by exact f32 distances
whenever ``rerank=True`` (the default).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.build import BuildReport
from repro.core.build_batched import _bucket, build_graphs_concurrent
from repro.core.predicates import (
    DominanceSpace,
    RelationMapping,
    get_relation,
)
from repro.exec.plan import PlannerConfig
from repro.scale.partition import SegmentGrid, canonicalize_batch
from repro.search.device_graph import (
    RANK_LIMIT,
    SegmentStack,
    export_device_graph,
)


@dataclasses.dataclass
class Segment:
    """One dominance-space cell's resident subgraph."""

    cell: int            # flattened grid cell id
    ids: np.ndarray      # [m] int64 global object ids (ascending)
    dg: object           # DeviceGraph over the segment's members
    report: BuildReport  # its wave-build report


@dataclasses.dataclass
class PartialSearchInfo:
    """Degradation flag attached to a search answer when segments are
    quarantined: the answer is the correct top-k over every SURVIVING
    segment; ``missing_segments`` lists the quarantined segment indices
    the batch's route would have touched (objects resident there cannot
    appear until the segment is rebuilt)."""

    degraded: bool
    missing_segments: List[int]


@functools.partial(jax.jit, static_argnames=("n", "use_ref"))
def _fold_topk(acc_d, acc_ids, cand_d, cand_ids, *, n: int, use_ref: bool | None):
    from repro.kernels import ops

    return ops.topk_merge(acc_d, acc_ids, cand_d, cand_ids,
                          n=n, use_ref=use_ref)


def merge_fold_cache_size() -> int:
    """Compiled variants of the segment merge fold (no-recompile
    assertions across mixed routed-segment counts)."""
    return _fold_topk._cache_size()


# process-wide device-dispatch tally: the scheduler issues ONE compiled
# dispatch per batch regardless of routed-segment mix, the legacy loop one
# per routed segment — the delta is what bench_scale's
# `dispatches_per_batch == 1` gate and the empty-worklist test observe.
_dispatch_count = 0


def dispatch_count() -> int:
    """Compiled device dispatches issued by ``SegmentedIndex.search`` so
    far in this process (scheduler path: 1/batch; legacy loop: 1/routed
    segment)."""
    return _dispatch_count


def _note_dispatch() -> None:
    global _dispatch_count
    _dispatch_count += 1


def worklist_capacity(w: int) -> int:
    """Quarter-octave bucketed worklist capacity (floor 8): the padded
    ``[W]`` length the scheduler dispatches with. Buckets are the
    powers of two plus the 1.25/1.5/1.75 intermediate steps (8, 10, 12,
    14, 16, 20, 24, 28, 32, 40, ...), so routed-mix changes land in a
    small closed set of compiled variants (at most 4 per octave) while
    padding waste — dead rows the lockstep search still computes every
    iteration — stays under 25% instead of the up-to-2x of pure
    power-of-two buckets."""
    w = max(int(w), 8)
    p = 1 << (w - 1).bit_length()   # next power of two >= w
    h = p >> 1
    for cap in (h + h // 4, h + h // 2, h + 3 * h // 4):
        if w <= cap:
            return cap
    return p


def _execute_segment(seg: "Segment", q, s_q, t_q, **kw):
    from repro.exec.executor import execute_batch

    _note_dispatch()
    out = execute_batch(seg.dg, q, s_q, t_q, **kw)
    return (np.asarray(out[0]), np.asarray(out[1])) + tuple(out[2:])


class SegmentedIndex:
    """Scale-out UDG: routed per-segment subgraphs behind one search API.

    Build with :func:`build_segmented_index`; query with :meth:`search`.
    All device work reuses the monolithic layers — the segments are plain
    ``DeviceGraph`` exports, execution is ``execute_batch``, merging is
    the ``beam_merge`` primitive — so every kernel-level contract (packed
    labels, padding dispatch, tie rules) is inherited, not re-implemented.
    """

    def __init__(
        self,
        relation: RelationMapping,
        grid: SegmentGrid,
        space: DominanceSpace,
        segments: Sequence[Segment],
        vectors: np.ndarray,
        *,
        node_capacity: int,
        edge_capacity: int,
        quantized: bool,
        packed: bool,
    ):
        self.relation = relation
        self.grid = grid
        self.space = space
        self.segments = list(segments)
        self.vectors = np.ascontiguousarray(vectors, dtype=np.float32)
        self.n = int(self.vectors.shape[0])
        self.node_capacity = int(node_capacity)
        self.edge_capacity = int(edge_capacity)
        self.quantized = bool(quantized)
        self.packed = bool(packed)
        # dedup sentinel for the merge fold: any bound strictly above every
        # global id, bucketed to a power of two so differently sized
        # indices still share the compiled fold
        self._n_sentinel = 1 << max(int(self.n).bit_length(), 1)
        self._stack: Optional[SegmentStack] = None
        self.quarantined: set = set()

    @property
    def num_segments(self) -> int:
        return len(self.segments)

    def device_stack(self) -> SegmentStack:
        """Memoized flat device stack over all segments (pre-offset
        adjacency + global-id table) — built on the first scheduler
        dispatch, reused for every batch after."""
        if self._stack is None:
            st = SegmentStack(
                node_capacity=self.node_capacity,
                edge_capacity=self.edge_capacity,
            )
            for seg in self.segments:
                st.append_segment(seg.dg, seg.ids)
            self._stack = st
        return self._stack

    def segment_sizes(self) -> np.ndarray:
        return np.array([seg.ids.shape[0] for seg in self.segments],
                        dtype=np.int64)

    # --- quarantine -----------------------------------------------------------

    def quarantine_segment(self, si: int, reason: str = "operator") -> None:
        """Mask segment ``si`` out of every future route and scrub its
        device slice (if staged). Route masking means the worklist
        scheduler simply gets fewer rows — identical shapes after padding,
        so the compiled dispatch is reused, never recompiled. Searches
        stay correct over the survivors; ``return_partial=True`` reports
        the gap."""
        from repro.obs.metrics import resolve

        si = int(si)
        if si in self.quarantined:
            return
        self.quarantined.add(si)
        if self._stack is not None:
            self._stack.blank_segment(si)
        resolve(None).gauge(
            "repro_segments_quarantined", "segments currently quarantined"
        ).set(len(self.quarantined), tier="batch")

    def lift_quarantine(self, si: int) -> None:
        """Restore segment ``si`` (its host-side ``Segment`` export is
        intact — quarantine only masked routing and blanked the staged
        device slice)."""
        from repro.obs.metrics import resolve

        si = int(si)
        if si not in self.quarantined:
            return
        self.quarantined.discard(si)
        if self._stack is not None:
            seg = self.segments[si]
            self._stack.set_segment(si, seg.dg, seg.ids)
        resolve(None).gauge(
            "repro_segments_quarantined", "segments currently quarantined"
        ).set(len(self.quarantined), tier="batch")

    # --- routing --------------------------------------------------------------

    def _query_states(
        self, s_q: np.ndarray, t_q: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Transformed + globally canonicalized batch — (x_q, y_q, a, c,
        valid)."""
        s_q = np.asarray(s_q, dtype=np.float64).reshape(-1)
        t_q = np.asarray(t_q, dtype=np.float64).reshape(-1)
        x_q, y_q = self.relation.query_map(s_q, t_q)
        a, c, valid = canonicalize_batch(self.space, x_q, y_q)
        return np.asarray(x_q, np.float64), np.asarray(y_q, np.float64), \
            a, c, valid

    def coarse_route(
        self, s_q: np.ndarray, t_q: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Grid-level routing — ``(route [B, num_segments] bool, valid)``.

        Column order matches ``self.segments``. Over-selection is expected;
        dropping a valid object is a bug (the property test's invariant).
        """
        _, _, a, c, valid = self._query_states(s_q, t_q)
        cells = self.grid.route_ranks(a, c, valid)
        route = np.zeros((cells.shape[0], self.num_segments), dtype=bool)
        for si, seg in enumerate(self.segments):
            route[:, si] = cells[:, seg.cell]
        return route, valid

    def _refine_route(
        self, route: np.ndarray, x_q: np.ndarray, y_q: np.ndarray
    ) -> np.ndarray:
        """AND each routed column with the segment planner's ``hi > 0``.

        ``hi`` is a TRUE upper bound on the segment-local valid count
        (estimator contract), so ``hi == 0`` segments are provably empty
        for the query and skipping them cannot lose recall.
        """
        out = route.copy()
        for si, seg in enumerate(self.segments):
            col = out[:, si]
            if not col.any():
                continue
            dg = seg.dg
            a_loc = np.searchsorted(dg.U_X, x_q, side="left").astype(np.int64)
            c_loc = (np.searchsorted(dg.U_Y, y_q, side="right") - 1).astype(
                np.int64
            )
            _, hi = dg.planner.count_bounds(a_loc, c_loc)
            out[:, si] = col & (hi > 0)
        return out

    # --- search ---------------------------------------------------------------

    def search(
        self,
        q: np.ndarray,
        s_q: np.ndarray,
        t_q: np.ndarray,
        *,
        k: int = 10,
        beam: int = 64,
        fetch_k: Optional[int] = None,
        rerank: bool = True,
        plan: str = "auto",
        config: Optional[PlannerConfig] = None,
        use_ref: bool = False,
        fused: bool = True,
        expand: int = 1,
        max_iters: Optional[int] = None,
        return_route: bool = False,
        return_partial: bool = False,
        scheduler: bool = True,
        stats: bool = False,
    ):
        """Routed top-k over all segments — ``(ids [B, k] int64, d [B, k])``.

        ``fetch_k`` is the per-segment candidate width fed to the merge
        fold (default ``2k`` when the int8 rerank tail is on, else ``k``);
        ``rerank=True`` replaces resident-layout distances with exact f32
        distances over the fused candidates and re-sorts by (distance,
        id) — the ground-truth tie rule. ``return_route`` appends the
        refined ``[B, num_segments]`` routing mask (observability +
        tests); ``stats=True`` appends a per-query
        :class:`repro.obs.SearchStats` (always the LAST element).

        ``scheduler=True`` (default) flattens the routed mask into one
        (query, segment) worklist and executes the whole mix as ONE
        compiled dispatch over the flat :class:`SegmentStack`
        (``exec.executor.worklist_exec_core``), padded to a quarter-octave
        bucket so mixes never recompile; ``scheduler=False`` keeps the
        per-segment host loop — the bit-exact parity oracle (results AND
        stats identical, pinned in tests).
        """
        from repro.exec.plan import default_planner_config
        from repro.obs.stats import (
            combine_stats,
            init_search_stats,
            stats_to_host,
        )

        q = np.asarray(q, dtype=np.float32)
        s_q = np.asarray(s_q, dtype=np.float64).reshape(-1)
        t_q = np.asarray(t_q, dtype=np.float64).reshape(-1)
        B = q.shape[0]
        fetch = int(fetch_k) if fetch_k is not None else (
            2 * k if (rerank and self.quantized) else k
        )
        fetch = max(fetch, k)
        beam_eff = max(beam, fetch)
        cfg = config or default_planner_config()
        x_q, y_q, a, c, valid = self._query_states(s_q, t_q)
        cells = self.grid.route_ranks(a, c, valid)
        route = np.zeros((B, self.num_segments), dtype=bool)
        for si, seg in enumerate(self.segments):
            route[:, si] = cells[:, seg.cell]
        # quarantined segments: drop their route columns BEFORE refinement —
        # the scheduler's worklist just has fewer rows (no shape change, no
        # recompile) and the answer is the exact top-k over the survivors
        missing = [si for si in sorted(self.quarantined)
                   if route[:, si].any()]
        if self.quarantined:
            route[:, sorted(self.quarantined)] = False
        route = self._refine_route(route, x_q, y_q)

        if scheduler:
            ids, d, st = self._search_worklist(
                q, s_q, t_q, route, fetch=fetch, beam_eff=beam_eff,
                max_iters=max_iters, use_ref=use_ref, fused=fused,
                expand=expand, plan=plan, config=cfg, stats=stats,
            )
        else:
            import jax.numpy as jnp

            acc_ids = jnp.full((B, fetch), -1, dtype=jnp.int32)
            acc_d = jnp.full((B, fetch), jnp.inf, dtype=jnp.float32)
            acc_st = None
            for si, seg in enumerate(self.segments):
                mask = route[:, si]
                if not mask.any():
                    continue  # host-side skip: no shapes change downstream
                out_s = _execute_segment(
                    seg, q, s_q, t_q, k=fetch, beam=beam_eff,
                    max_iters=max_iters, use_ref=use_ref, fused=fused,
                    expand=expand, plan=plan, config=cfg, row_mask=mask,
                    packed=self.packed, stats=stats,
                )
                loc_ids, loc_d = out_s[0], out_s[1]
                if stats:
                    seg_st = out_s[-1]
                    acc_st = seg_st if acc_st is None else combine_stats(
                        acc_st, seg_st
                    )
                m = seg.ids.shape[0]
                glob = np.where(
                    loc_ids >= 0,
                    seg.ids[np.clip(loc_ids, 0, m - 1)],
                    -1,
                ).astype(np.int32)
                acc_ids, acc_d = _fold_topk(
                    acc_d, acc_ids, jnp.asarray(loc_d), jnp.asarray(glob),
                    n=self._n_sentinel, use_ref=use_ref,
                )
            ids = np.asarray(acc_ids)
            d = np.asarray(acc_d)
            st = None
            if stats:
                if acc_st is None:
                    acc_st = init_search_stats(B)
                st = stats_to_host(acc_st)
        if rerank:
            ids, d = self._rerank_exact(q, ids, d, k)
        else:
            ids, d = ids[:, :k], d[:, :k]
        out = (ids.astype(np.int64), d.astype(np.float32))
        if return_route:
            out += (route,)
        if return_partial:
            out += (PartialSearchInfo(
                degraded=bool(missing), missing_segments=missing,
            ),)
        if stats:
            out += (st,)
        return out

    def _search_worklist(
        self, q, s_q, t_q, route, *, fetch, beam_eff, max_iters,
        use_ref, fused, expand, plan, config, stats,
    ):
        """One-dispatch scheduler body — ``(ids [B, fetch] int32 global,
        d [B, fetch] f32, stats | None)``.

        Host side: per routed segment, slice the routed query rows,
        canonicalize on the segment grid and plan them (row-independent,
        so plans match the legacy full-batch ``row_mask`` call exactly),
        then concatenate segment-major into one ``[W]`` worklist padded to
        ``worklist_capacity(W)``. Device side: one
        ``worklist_exec_core`` call over the memoized flat stack.
        """
        from repro.exec.executor import (
            PLANS,
            mask_entry_points,
            worklist_exec_core,
        )
        from repro.exec.plan import QueryPlan, plan_queries
        from repro.obs.stats import init_search_stats, stats_to_host
        from repro.search.batched import prepare_states_extended

        if plan not in PLANS:
            raise ValueError(f"plan={plan!r} not in {PLANS}")
        import jax.numpy as jnp

        B = q.shape[0]
        cfg = config
        mi = max_iters if max_iters is not None else 2 * beam_eff
        wide_mi = mi * cfg.wide_beam_scale
        wide_beam = max(beam_eff * cfg.wide_beam_scale, beam_eff)
        wide_expand = cfg.wide_expand if fused else 1
        wide_expand = min(wide_expand, wide_beam)

        qids, segs, sts, eps_g, eps_w, bfs, pls = [], [], [], [], [], [], []
        for si, seg in enumerate(self.segments):
            rows = np.flatnonzero(route[:, si])
            if rows.size == 0:
                continue
            dg = seg.dg
            st_loc, ep, inv = prepare_states_extended(
                dg, s_q[rows], t_q[rows]
            )
            w = rows.shape[0]
            if plan == "auto":
                pb = plan_queries(dg.planner, st_loc, inv, config=cfg)
                pl, bf = pb.plans, pb.bf_ids
            elif plan == "graph":
                pl = np.full(w, int(QueryPlan.GRAPH), dtype=np.int32)
                bf = np.full((w, cfg.brute_max_valid), -1, dtype=np.int32)
            elif plan == "wide":
                pl = np.full(w, int(QueryPlan.GRAPH_WIDE), dtype=np.int32)
                bf = np.full((w, cfg.brute_max_valid), -1, dtype=np.int32)
            else:  # forced brute: exact lists; width unified over the
                # whole worklist below (extra -1 columns annihilate
                # in-kernel, so one global capacity changes nothing)
                pl = np.full(w, int(QueryPlan.BRUTE_VALID), dtype=np.int32)
                bf = [
                    np.empty(0, np.int32) if inv[j]
                    else dg.planner.exact_valid_ids(
                        int(st_loc[j, 0]), int(st_loc[j, 1])
                    )
                    for j in range(w)
                ]
            ep_g, ep_w = mask_entry_points(ep, pl)
            qids.append(rows.astype(np.int32))
            segs.append(np.full(w, si, dtype=np.int32))
            sts.append(st_loc)
            eps_g.append(ep_g)
            eps_w.append(ep_w)
            bfs.append(bf)
            pls.append(pl)

        if not qids:
            # empty worklist: nothing routed anywhere — all-padding result
            # with NO device dispatch (pinned by the dispatch-count test)
            ids = np.full((B, fetch), -1, dtype=np.int32)
            d = np.full((B, fetch), np.inf, dtype=np.float32)
            st = (stats_to_host(init_search_stats(B))
                  if stats else None)
            return ids, d, st

        qid = np.concatenate(qids)
        seg_arr = np.concatenate(segs)
        states = np.concatenate(sts, axis=0).astype(np.int32)
        ep_g = np.concatenate(eps_g)
        ep_w = np.concatenate(eps_w)
        plans = np.concatenate(pls)
        if plan == "brute":
            lists = [l for bl in bfs for l in bl]
            cap = max(int(max((l.shape[0] for l in lists), default=1)), 1)
            cap = 1 << (cap - 1).bit_length()
            bf = np.full((len(lists), cap), -1, dtype=np.int32)
            for i, l in enumerate(lists):
                bf[i, : l.shape[0]] = l
        else:
            bf = np.concatenate(bfs, axis=0).astype(np.int32)

        W0 = qid.shape[0]
        pad = worklist_capacity(W0) - W0
        if pad:
            # padding items: query row B (out of bounds -> scatter-dropped),
            # segment 0, entry points/brute lists empty -> zero device work
            qid = np.concatenate([qid, np.full(pad, B, np.int32)])
            seg_arr = np.concatenate([seg_arr, np.zeros(pad, np.int32)])
            states = np.concatenate(
                [states, np.zeros((pad, 2), np.int32)], axis=0
            )
            ep_g = np.concatenate([ep_g, np.full(pad, -1, np.int32)])
            ep_w = np.concatenate([ep_w, np.full(pad, -1, np.int32)])
            bf = np.concatenate(
                [bf, np.full((pad, bf.shape[1]), -1, np.int32)], axis=0
            )
            plans = np.concatenate(
                [plans, np.full(pad, int(QueryPlan.GRAPH), np.int32)]
            )

        stack = self.device_stack()
        lab = stack.flat_labels(fused=fused, packed=self.packed)
        _note_dispatch()
        out = worklist_exec_core(
            stack.flat("table"), stack.flat("nbr"), lab, stack.flat("gids"),
            jnp.asarray(q), jnp.asarray(qid), jnp.asarray(seg_arr),
            jnp.asarray(states), jnp.asarray(ep_g), jnp.asarray(ep_w),
            jnp.asarray(bf), jnp.asarray(plans),
            k=fetch, beam=beam_eff, wide_beam=wide_beam,
            max_iters=mi, wide_max_iters=wide_mi,
            use_ref=use_ref, fused=fused, expand=expand,
            wide_expand=wide_expand,
            scales=stack.flat("scales"),
            norms=stack.flat("norms") if fused else None,
            stats=stats,
            node_cap=self.node_capacity, n_sentinel=self._n_sentinel,
        )
        ids = np.asarray(out[0])
        d = np.asarray(out[1])
        st = stats_to_host(out[2]) if stats else None
        return ids, d, st

    def _rerank_exact(
        self, q: np.ndarray, ids: np.ndarray, d: np.ndarray, k: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Float32 exact-rerank tail over the fused candidates.

        Gathers the original f32 rows for every fused candidate, re-scores
        ``‖v − q‖²`` exactly, and selects top-k by ``(distance, id)`` —
        the same ``np.lexsort`` tie rule as ``data.workloads.ground_truth``
        — so int8 residency never changes the *final* ordering, only the
        candidate generation.
        """
        safe = np.clip(ids, 0, self.n - 1)
        vv = self.vectors[safe]                       # [B, L, D] f32
        diff = vv - q[:, None, :]
        d_ex = np.einsum("bld,bld->bl", diff, diff).astype(np.float32)
        d_ex = np.where(ids >= 0, d_ex, np.float32(np.inf))
        order = np.lexsort((ids, d_ex))               # per-row (d, id) sort
        sel = order[:, :k]
        out_ids = np.take_along_axis(ids, sel, axis=1)
        out_d = np.take_along_axis(d_ex, sel, axis=1)
        return out_ids, out_d

    # --- accounting -----------------------------------------------------------

    def nbytes_by_component(self) -> dict:
        """Aggregated at-rest bytes: per-segment ``DeviceGraph`` components
        summed key-wise, plus the router's own state under ``"router"``.
        Component sum equals :meth:`nbytes` exactly (pinned in tests —
        the n=1M byte-budget gate depends on these numbers)."""
        agg: dict = {}
        for seg in self.segments:
            for key, v in seg.dg.nbytes_by_component().items():
                agg[key] = agg.get(key, 0) + v
        agg["router"] = self.grid.nbytes()
        return agg

    def nbytes(self) -> int:
        return sum(self.nbytes_by_component().values())


def build_segmented_index(
    vectors: np.ndarray,
    s: np.ndarray,
    t: np.ndarray,
    relation: str,
    *,
    cells_per_axis: int = 4,
    M: int = 16,
    Z: int = 64,
    K_p: int = 8,
    leap: str = "maxleap",
    patch: str = "full",
    wave: int = 256,
    lane: int = 8,
    quantize_int8: bool = True,
    planner_buckets: int = 64,
    use_ref: bool | None = None,
) -> SegmentedIndex:
    """Partition, build all segment subgraphs concurrently, export.

    Every non-empty grid cell becomes a segment; the per-segment UDGs are
    built through ONE interleaved wave pipeline
    (``build_graphs_concurrent`` — each graph keeps its own incremental
    ``BroadExport`` adjacency, device searches overlap host sweeps) and
    exported with UNIFORM ``node_capacity``/``edge_capacity``/label
    layout, which is what lets every segment execute through the same
    compiled program at query time. ``quantize_int8`` defaults ON here —
    the scale tier's resident layout — because the rerank tail restores
    exact final ordering (see :class:`SegmentedIndex`).
    """
    vectors = np.ascontiguousarray(vectors, dtype=np.float32)
    rel = get_relation(relation)
    X, Y = rel.transform_data(s, t)
    space = DominanceSpace.build(X, Y)
    xr, yr = space.ranks()
    grid = SegmentGrid.from_space(space, cells_per_axis)
    cell = grid.assign_ranks(xr, yr)

    members: List[np.ndarray] = []
    cells_used: List[int] = []
    for cc in np.unique(cell):
        ids = np.flatnonzero(cell == cc).astype(np.int64)  # ascending
        members.append(ids)
        cells_used.append(int(cc))

    node_cap = _bucket(max(int(ids.shape[0]) for ids in members))
    s = np.asarray(s, dtype=np.float64)
    t = np.asarray(t, dtype=np.float64)
    datasets = [(vectors[ids], s[ids], t[ids]) for ids in members]
    built = build_graphs_concurrent(
        datasets, relation, M=M, Z=Z, K_p=K_p,
        leap=leap, patch=patch, wave=wave, pad_nodes=node_cap,
        use_ref=use_ref,
    )

    # uniform lane-aligned edge capacity = the max natural degree anywhere
    E = lane
    fits = True
    for g, _ in built:
        deg = max((g.adj[u].size for u in range(g.n)), default=1)
        E = max(E, ((deg + lane - 1) // lane) * lane)
        fits &= (g.space.U_X.shape[0] <= RANK_LIMIT
                 and g.space.U_Y.shape[0] <= RANK_LIMIT)

    segments = []
    for cc, ids, (g, rep) in zip(cells_used, members, built):
        dg = export_device_graph(
            g, lane=lane, node_capacity=node_cap, edge_capacity=E,
            quantize_int8=quantize_int8, planner_buckets=planner_buckets,
            packed_labels=True if fits else False,
        )
        segments.append(Segment(cell=cc, ids=ids, dg=dg, report=rep))

    return SegmentedIndex(
        rel, grid, space, segments, vectors,
        node_capacity=node_cap, edge_capacity=E,
        quantized=quantize_int8, packed=fits,
    )
