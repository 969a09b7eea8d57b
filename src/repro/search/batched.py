"""Batched lockstep UDG search (jit/pjit-able) — TPU adaptation of Alg. 2.

Every query in the batch advances one *step* per iteration of a
``lax.while_loop``; finished queries no-op behind masks until the whole
batch terminates. Per iteration and per query the packed-metadata
superkernel path (the default — selected whenever the ``DeviceGraph``
carries bit-packed ``[n, E, 2]`` uint32 label rectangles):

  1. select the best ``expand`` (M ≥ 1) unexpanded beam entries (fixed-size
     beam = pool+ann) — multi-expand amortizes the while-loop/merge
     overhead across M beam expansions and cuts iteration count for wide
     beams;
  2. read their padded neighbor ids ([B, M*E] int32 — the only per-edge
     metadata that crosses the XLA boundary);
  3. packed-metadata superkernel (``ops.filter_dist_gather_packed``): the
     kernel DMAs the vector rows of the candidates that are neither
     padding nor visited, *and* the M expanded nodes' packed label rows,
     from the HBM-resident tables (scalar-prefetched ids, double-buffered
     VMEM tiles; a query row with no live entry is a dead tile that
     fetches nothing), unpacks the 16-bit ranks with a
     mask-and-shift, applies the dominance + visited tests, and computes
     ``‖c‖² − 2·q·c + ‖q‖²`` from cached per-node norms — neither the
     ``[B, E, D]`` candidate tensor nor the ``[B, M·E, 4]`` label gather
     of the older paths ever materializes;
  4. deduplicate + merge with ``ops.beam_merge``: an ``[M·E, M·E]``
     predicated compare suppresses intra-iteration duplicates (no argsort)
     and a top-L selection (``lax.top_k`` on CPU/jnp, a bitonic
     sort-and-merge network on TPU) replaces the full stable
     ``lax.sort`` over ``[B, L + M·E]`` triples;
  5. set the kept candidates' bits in the bit-packed ``[B, ceil(n/32)]``
     uint32 visited bitmap (the kernel already suppressed
     previously-visited candidates in-kernel).

With int32 ``[n, E, 4]`` labels the fused loop keeps the PR 2 structure —
XLA-side label gather, argsort dedup, stable ``lax.sort`` merge — as the
packed path's parity oracle (``batched_udg_search(packed=False)``).
``fused=False`` keeps the original pre-gather loop — XLA gather of a dense
``[B, E, D]`` candidate tensor, per-iteration ``sum(c*c)`` recompute, dense
``[B, n]`` bool visited — as the deepest baseline
(``tests/test_batched_search.py`` pins the paths to identical results).

Tie note: the packed merge resolves exact distance ties in candidate
arrival order, the legacy merge in candidate id order (it id-sorts for the
argsort dedup). Same-id duplicates always carry bit-equal distances, so
results can differ only when two *distinct* rows sit at exactly the same
squared distance from the query.

Termination — "no unexpanded entry within the beam" — is the batched
equivalent of Alg. 2 line 7 (the best pool entry being worse than the worst
of a full ann): any pool entry that survives the beam merge is by
construction within the current top-L, and everything else is discarded.

int8-quantized tables ride the same loops: pass ``scales`` ([n] f32) and the
kernel (or the unfused gather) dequantizes per candidate; ``norms`` must
then be the norms of the *dequantized* rows so cached-norm distances match
a dequantize-then-score oracle.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.predicates import get_relation
from repro.kernels import ops
from repro.kernels.layout import gather_vectors, is_int32_rects
from repro.obs.stats import (
    accumulate_iteration,
    finalize_stats,
    init_search_stats,
    loop_totals,
    stats_to_host,
)
from repro.search.device_graph import DeviceGraph

_INF = jnp.inf


def prepare_states_extended(
    dg: DeviceGraph, s_q: np.ndarray, t_q: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Map + canonicalize a batch of query intervals (Lemma 1, vectorized).

    Returns (states [B, 2] int32 rank pairs, ep [B] int32 entry ids; ep=-1
    marks an empty valid set / no entry, invalid [B] bool — True where
    canonicalization itself failed, i.e. the valid set is provably empty
    and the clipped state rows are meaningless)."""
    rel = get_relation(dg.relation)
    s_q = np.asarray(s_q, dtype=np.float64)
    t_q = np.asarray(t_q, dtype=np.float64)
    x_q, y_q = rel.query_map(s_q, t_q)  # arithmetic lambdas broadcast fine
    a = np.searchsorted(dg.U_X, x_q, side="left")
    c = np.searchsorted(dg.U_Y, y_q, side="right") - 1
    num_x = dg.U_X.shape[0]
    invalid = (a >= num_x) | (c < 0)
    a_cl = np.clip(a, 0, max(num_x - 1, 0))
    ep = dg.entry_node[a_cl].astype(np.int64)
    ep_y = dg.entry_y_rank[a_cl].astype(np.int64)
    ep = np.where(invalid | (ep < 0) | (ep_y > c), -1, ep)
    states = np.stack([a_cl, np.maximum(c, 0)], axis=1).astype(np.int32)
    return states, ep.astype(np.int32), invalid


def prepare_states(
    dg: DeviceGraph, s_q: np.ndarray, t_q: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Back-compat two-tuple form of :func:`prepare_states_extended`."""
    states, ep, _ = prepare_states_extended(dg, s_q, t_q)
    return states, ep


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "beam", "max_iters", "use_ref", "fused", "expand",
        "unroll_iters", "stats",
    ),
)
def _batched_search_core(
    vectors: jnp.ndarray,   # [n, D] f32 (or int8 with scales), or its rows
    nbr: jnp.ndarray,       # [n, E] int32
    labels: jnp.ndarray | None,  # [n, E, 4] int32, packed words or label
                                 # rows; None = label-ignoring (broad)
    q: jnp.ndarray,         # [B, D]
    states: jnp.ndarray,    # [B, 2] int32
    ep: jnp.ndarray,        # [B] int32
    *,
    k: int,
    beam: int,
    max_iters: int,
    use_ref: bool | None,
    fused: bool = True,
    expand: int = 1,
    unroll_iters: int = 0,
    scales: jnp.ndarray | None = None,   # [n] f32: int8-quantized vectors
    norms: jnp.ndarray | None = None,    # [n] f32: cached ‖c‖² (fused path)
    stats: bool = False,  # also return a SearchStats traversal-counter pytree
) -> Tuple[jnp.ndarray, ...]:
    """Returns ``(ids [B, k], dists [B, k], totals i32[5])`` — ``totals``
    are the loop's always-on ``repro.obs.stats.LOOP_TOTALS`` — and, with
    ``stats=True``, a :class:`repro.obs.SearchStats` last."""
    n = vectors.shape[0]
    B, D = q.shape
    E = nbr.shape[1]
    L = beam
    q = q.astype(jnp.float32)
    if not fused and expand != 1:
        raise ValueError("multi-expand (expand > 1) requires fused=True")
    if not 1 <= expand <= beam:
        raise ValueError(f"expand={expand} must be in [1, beam={beam}]")
    if not fused and labels is not None and not is_int32_rects(labels):
        raise ValueError(
            "the unfused baseline needs the int32 [n, E, 4] label layout "
            "(pass DeviceGraph.labels_i32(), not the packed words)"
        )

    def deq(idx):
        """Rows ``idx`` in f32 (dequantizing int8 storage)."""
        out = gather_vectors(vectors, idx, D)
        if scales is not None:
            out = out * scales[idx][..., None]
        return out

    has_ep = ep >= 0
    ep_safe = jnp.where(has_ep, ep, 0)
    d_ep = jnp.sum((q - deq(ep_safe)) ** 2, axis=-1)

    beam_ids = jnp.full((B, L), -1, dtype=jnp.int32)
    beam_d = jnp.full((B, L), _INF, dtype=jnp.float32)
    beam_exp = jnp.zeros((B, L), dtype=bool)
    beam_ids = beam_ids.at[:, 0].set(jnp.where(has_ep, ep, -1))
    beam_d = beam_d.at[:, 0].set(jnp.where(has_ep, d_ep, _INF))

    def cond(carry):
        beam_d_, beam_exp_, it = carry[1], carry[2], carry[4]
        active = jnp.any(~beam_exp_ & jnp.isfinite(beam_d_))
        return jnp.logical_and(it < max_iters, active)

    # label layout is static at trace time: [n, E, 4] int32 = legacy layout
    # (the parity oracle), anything else = bit-packed words or their rows
    # (superkernel + beam_merge pipeline), None = broad/label-ignoring mode
    packed = labels is not None and not is_int32_rects(labels)

    if fused:
        M = expand
        ME = M * E
        if norms is None:
            v32 = gather_vectors(vectors, slice(None), D)
            norms_ = jnp.sum(v32 * v32, axis=1)
            if scales is not None:
                norms_ = norms_ * scales * scales
        else:
            norms_ = norms.astype(jnp.float32)
        W = (n + 31) // 32
        visited = jnp.zeros((B, W), dtype=jnp.uint32)
        ep_bit = jnp.where(
            has_ep,
            jnp.uint32(1) << (ep_safe & 31).astype(jnp.uint32),
            jnp.uint32(0),
        )
        visited = visited.at[jnp.arange(B), ep_safe >> 5].add(ep_bit)

        def body(carry):
            (beam_ids_, beam_d_, beam_exp_, visited_, it, rows_it, kept_,
             fetched_) = carry[:8]
            # 1. best M unexpanded entries per query
            cand_d = jnp.where(beam_exp_, _INF, beam_d_)
            if M == 1:
                j = jnp.argmin(cand_d, axis=1)[:, None]            # [B, 1]
            else:
                _, j = jax.lax.top_k(-cand_d, M)                   # [B, M]
            sel_d = jnp.take_along_axis(cand_d, j, 1)
            live = sel_d < _INF                                    # [B, M]
            cur = jnp.take_along_axis(beam_ids_, j, 1)
            cur_safe = jnp.where(live, cur, 0)
            rows_m = jnp.broadcast_to(jnp.arange(B)[:, None], (B, M))
            beam_exp_ = beam_exp_.at[rows_m, j].max(live)
            # 2. neighbor ids — with packed labels the ONLY per-edge
            # metadata gathered on the XLA side. Broad mode (labels=None,
            # the constructor's label-ignoring search) skips the label
            # gather entirely: all-zero rectangles + the all-zero state
            # make every tuple pass the containment test.
            nb = jnp.where(live[:, :, None], nbr[cur_safe], -1)    # [B, M, E]
            nb = nb.reshape(B, ME)
            if packed:
                # 3. packed superkernel: in-kernel DMA of the vector rows
                # AND the M expanded nodes' packed label rows; dominance +
                # visited tests and cached-norm distance fused in-kernel
                # a -1 expanded node (no live entry) is a dead tile
                d_new, fetched = ops.filter_dist_gather_packed(
                    vectors, labels, norms_, q, jnp.where(live, cur, -1),
                    nb, states, visited_, scales=scales, use_ref=use_ref,
                )
                # 4. dedup + top-L merge primitive (no argsort, no full
                # stable sort); `keep` = deduped survivors, in nb order
                beam_ids_, beam_d_, beam_exp_, keep = ops.beam_merge(
                    beam_d_, beam_ids_, beam_exp_, d_new, nb,
                    n=n, use_ref=use_ref,
                )
                # 5. bitmap update: kept candidates are deduped and
                # previously unvisited, so each (query, bit) lands at most
                # once — scatter-add == scatter-or
                ids_safe = jnp.clip(nb, 0, n - 1)
                rows = jnp.broadcast_to(jnp.arange(B)[:, None], (B, ME))
                bits = jnp.where(
                    keep,
                    jnp.uint32(1) << (ids_safe & 31).astype(jnp.uint32),
                    jnp.uint32(0),
                )
                visited_ = visited_.at[rows, ids_safe >> 5].add(bits)
                out = (beam_ids_, beam_d_, beam_exp_, visited_, it + 1,
                       rows_it + jnp.any(live, axis=1).astype(jnp.int32),
                       kept_ + jnp.sum(keep.astype(jnp.int32)),
                       fetched_ + fetched)
                if stats:
                    out += (accumulate_iteration(
                        carry[8], live=live, nb=nb, d_new=d_new, keep=keep,
                    ),)
                return out
            if labels is None:
                lb = jnp.zeros((B, ME, 4), dtype=jnp.int32)
            else:
                lb = labels[cur_safe].reshape(B, ME, 4)
            # 3. gather-fused label + visited test + cached-norm distance
            d_new, fetched = ops.filter_dist_gather(
                vectors, norms_, q, nb, lb, states, visited_,
                scales=scales, use_ref=use_ref,
            )
            # 4. intra-batch duplicate suppression + bitmap update
            id_key = jnp.where(jnp.isfinite(d_new), nb, jnp.int32(n))
            order = jnp.argsort(id_key, axis=1)
            ids_s = jnp.take_along_axis(nb, order, 1)
            d_s = jnp.take_along_axis(d_new, order, 1)
            dup = jnp.concatenate(
                [jnp.zeros((B, 1), bool), ids_s[:, 1:] == ids_s[:, :-1]], axis=1
            )
            d_s = jnp.where(dup, _INF, d_s)
            keep = jnp.isfinite(d_s)
            ids_safe = jnp.clip(ids_s, 0, n - 1)
            rows = jnp.broadcast_to(jnp.arange(B)[:, None], (B, ME))
            # kept candidates are deduped and previously unvisited, so each
            # (query, bit) lands at most once — scatter-add == scatter-or
            bits = jnp.where(
                keep,
                jnp.uint32(1) << (ids_safe & 31).astype(jnp.uint32),
                jnp.uint32(0),
            )
            visited_ = visited_.at[rows, ids_safe >> 5].add(bits)
            # 5. stable merge, keep best L
            all_d = jnp.concatenate([beam_d_, d_s], axis=1)
            all_ids = jnp.concatenate([beam_ids_, ids_s], axis=1)
            all_exp = jnp.concatenate(
                [beam_exp_, jnp.ones((B, ME), dtype=bool) & ~keep], axis=1
            )
            sd, si, se = jax.lax.sort(
                (all_d, all_ids, all_exp), dimension=1, num_keys=1,
                is_stable=True,
            )
            out = (si[:, :L], sd[:, :L], se[:, :L], visited_, it + 1,
                   rows_it + jnp.any(live, axis=1).astype(jnp.int32),
                   kept_ + jnp.sum(keep.astype(jnp.int32)),
                   fetched_ + fetched)
            if stats:
                out += (accumulate_iteration(
                    carry[8], live=live, nb=nb, d_new=d_new, keep=keep,
                ),)
            return out

    else:
        visited = jnp.zeros((B, n), dtype=bool)
        visited = visited.at[jnp.arange(B), ep_safe].max(has_ep)

        def body(carry):
            (beam_ids_, beam_d_, beam_exp_, visited_, it, rows_it, kept_,
             fetched_) = carry[:8]
            # 1. best unexpanded entry per query
            cand_d = jnp.where(beam_exp_, _INF, beam_d_)
            j = jnp.argmin(cand_d, axis=1)
            live = jnp.take_along_axis(cand_d, j[:, None], 1)[:, 0] < _INF
            cur = jnp.take_along_axis(beam_ids_, j[:, None], 1)[:, 0]
            cur_safe = jnp.where(live, cur, 0)
            beam_exp_ = beam_exp_ | (jax.nn.one_hot(j, L, dtype=bool) & live[:, None])
            # 2. gather neighbor rows
            nb = nbr[cur_safe]                          # [B, E]
            if labels is None:
                lb = jnp.zeros((B, E, 4), dtype=jnp.int32)
            else:
                lb = labels[cur_safe]                   # [B, E, 4]
            nb = jnp.where(live[:, None], nb, -1)
            nb_safe = jnp.clip(nb, 0, n - 1)
            cand_vecs = deq(nb_safe)                     # [B, E, D] f32
            # 3. fused label test + distance
            d_new = ops.filter_dist(q, cand_vecs, lb, states, nb, use_ref=use_ref)
            # 4. visited + duplicate suppression
            seen = jnp.take_along_axis(visited_, jnp.clip(nb, 0, n - 1).astype(jnp.int32), 1)
            d_new = jnp.where(seen | (nb < 0), _INF, d_new)
            id_key = jnp.where(jnp.isfinite(d_new), nb, jnp.int32(n))
            order = jnp.argsort(id_key, axis=1)
            ids_s = jnp.take_along_axis(nb, order, 1)
            d_s = jnp.take_along_axis(d_new, order, 1)
            dup = jnp.concatenate(
                [jnp.zeros((B, 1), bool), ids_s[:, 1:] == ids_s[:, :-1]], axis=1
            )
            d_s = jnp.where(dup, _INF, d_s)
            keep = jnp.isfinite(d_s)
            rows = jnp.broadcast_to(jnp.arange(B)[:, None], (B, E))
            visited_ = visited_.at[rows, jnp.clip(ids_s, 0, n - 1)].max(keep)
            # 5. stable merge, keep best L
            all_d = jnp.concatenate([beam_d_, d_s], axis=1)
            all_ids = jnp.concatenate([beam_ids_, ids_s], axis=1)
            all_exp = jnp.concatenate(
                [beam_exp_, jnp.ones((B, E), dtype=bool) & ~keep], axis=1
            )
            sd, si, se = jax.lax.sort(
                (all_d, all_ids, all_exp), dimension=1, num_keys=1, is_stable=True
            )
            # XLA gathers every candidate's row: all B x E are fetched
            out = (si[:, :L], sd[:, :L], se[:, :L], visited_, it + 1,
                   rows_it + live.astype(jnp.int32),
                   kept_ + jnp.sum(keep.astype(jnp.int32)),
                   fetched_ + B * E)
            if stats:
                out += (accumulate_iteration(
                    carry[8], live=live[:, None], nb=nb, d_new=d_new,
                    keep=keep,
                ),)
            return out

    # the always-on totals' carry: trips (it), row iterations, kept,
    # rows fetched
    carry = (beam_ids, beam_d, beam_exp, visited, jnp.int32(0),
             jnp.zeros(B, dtype=jnp.int32), jnp.int32(0), jnp.int32(0))
    if stats:
        carry += (init_search_stats(B),)
    if unroll_iters > 0:
        # cost-probe mode: a fixed number of python-unrolled expansions so
        # HLO cost analysis sees per-iteration work (a while body is counted
        # once); inactive queries no-op behind the same masks.
        for _ in range(unroll_iters):
            carry = body(carry)
    else:
        carry = jax.lax.while_loop(cond, body, carry)
    beam_ids, beam_d, beam_exp, visited, trips, rows_it, kept, fetched = (
        carry[:8])
    out = (beam_ids[:, :k], beam_d[:, :k],
           loop_totals(trips, rows_it, kept, fetched, width=expand * E))
    if stats:
        out += (finalize_stats(
            carry[8], beam_d=beam_d, beam_exp=beam_exp, visited=visited
        ),)
    return out


def batched_udg_search(
    dg: DeviceGraph,
    q: np.ndarray,
    s_q: np.ndarray,
    t_q: np.ndarray,
    *,
    k: int = 10,
    beam: int = 64,
    max_iters: int | None = None,
    use_ref: bool = False,
    fused: bool = True,
    expand: int = 1,
    plan: str = "graph",
    packed: bool | None = None,
    stats: bool = False,
) -> Tuple[np.ndarray, ...]:
    """End-to-end batched query: canonicalize on host, search on device.

    Device arrays come from the graph's memoized ``dg.device()`` bundle —
    built once per export instead of re-staging the full table per batch —
    including int8 storage (``dg.vec_q`` + ``dg.scales``, exported with
    ``quantize_int8=True``) when present and the cached norms on the fused
    path. ``packed`` selects the label layout: ``None`` (default) uses the
    packed-metadata superkernel whenever the export carries packed labels;
    ``False`` forces the legacy int32 fused loop (the packed path's parity
    oracle); ``True`` requires packed labels (raises if the export fell
    back). ``fused=False`` selects the deepest pre-gather baseline (dense
    visited, per-iteration norm recompute).

    ``plan`` selects the execution strategy: the default ``"graph"`` is the
    pure beam search (the planner's parity oracle); ``"auto"`` /
    ``"wide"`` / ``"brute"`` route through the selectivity-aware executor
    (``repro.exec.execute_batch``), which dispatches mixed-plan batches
    through one compiled program.

    ``stats=True`` appends a host-side :class:`repro.obs.SearchStats`
    pytree of device traversal counters to the return tuple."""
    if plan != "graph":
        from repro.exec import execute_batch

        return execute_batch(
            dg, q, s_q, t_q, k=k, beam=beam, max_iters=max_iters,
            use_ref=use_ref, fused=fused, expand=expand, plan=plan,
            packed=packed, stats=stats,
        )
    states, ep = prepare_states(dg, s_q, t_q)
    dev = dg.device()
    labels = dg.serving_labels(fused=fused, packed=packed)
    norms = dev.norms if fused else None
    out = _batched_search_core(
        dev.table,
        dev.nbr,
        labels,
        jnp.asarray(np.asarray(q, dtype=np.float32)),
        jnp.asarray(states),
        jnp.asarray(ep),
        k=k,
        beam=beam,
        max_iters=max_iters if max_iters is not None else 2 * beam,
        use_ref=use_ref,
        fused=fused,
        expand=expand,
        scales=dev.scales,
        norms=norms,
        stats=stats,
    )
    ids, d = out[0], out[1]
    if stats:
        return np.asarray(ids), np.asarray(d), stats_to_host(out[3])
    return np.asarray(ids), np.asarray(d)


def broad_batched_search(
    table: jnp.ndarray,      # [n_pad, D] f32 full vector table
    norms: jnp.ndarray,      # [n_pad] f32 cached ‖v‖²
    nbr: jnp.ndarray,        # [n_pad, E] int32 broad adjacency (-1 padded)
    q: jnp.ndarray,          # [B, D] f32 wave of inserted objects
    ep: jnp.ndarray,         # [B] int32 entry ids (-1 = masked/padding query)
    *,
    k: int,
    beam: int | None = None,
    max_iters: int | None = None,
    use_ref: bool | None = None,
    fused: bool = True,
    expand: int = 1,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Label-ignoring batched beam search — the constructor's broad search.

    The device analogue of ``udg_search(..., ignore_labels=True)`` (paper
    §V-A): one lockstep search over a *broad adjacency* (unique neighbor ids,
    no label rectangles — see ``repro.search.device_graph.BroadExport``)
    shared by a whole insertion wave. ``labels=None`` in the core skips the
    label gather entirely and substitutes all-zero rectangles + the all-zero
    state, which every tuple passes, so no ``[n, E, 4]`` labels array ever
    exists for the construction-time index. Returns device arrays
    (ids [B, k] int32 with -1 padding, squared dists [B, k] f32, ascending).
    """
    B = q.shape[0]
    L = beam if beam is not None else k
    states = jnp.zeros((B, 2), dtype=jnp.int32)
    ids, d, _ = _batched_search_core(
        table,
        nbr,
        None,
        q,
        states,
        ep,
        k=k,
        beam=L,
        max_iters=max_iters if max_iters is not None else 2 * L,
        use_ref=use_ref,
        fused=fused,
        expand=expand,
        norms=norms,
    )
    return ids, d
