"""Distributed UDG serving over a (data, model[, pod]) mesh.

Layout (classic shard-per-device vector search, DESIGN.md §3):
  * the database is partitioned into ``num_shards`` blocks along the
    ``model`` axis; each shard builds its OWN UDG over its block (top-k over
    a union is the merge of per-shard top-k, so per-shard indexes are exact
    w.r.t. the union);
  * shard-local arrays (graph, canonical grids, entry tables) are stacked on
    a leading shard dim and shard_map'ed with P("model");
  * queries are sharded over ("pod","data") and replicated over "model";
  * canonicalization (Lemma 1) runs per shard on shard-local U_X/U_Y;
  * per-shard top-k results are merged across "model" — baseline via
    all_gather + top_k; optimized via a log2(shards)-step collective-permute
    tournament that moves k instead of shards*k entries per hop
    (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.build import build_udg
from repro.core.entry import EntryTable
from repro.core.predicates import get_relation
from repro.exec import (
    PlannerConfig,
    QueryPlan,
    SelectivityEstimator,
    default_planner_config,
    plan_queries,
)
from repro.exec.executor import planned_exec_core
from repro.obs.stats import SearchStats, per_query_dict
from repro.search.batched import _batched_search_core
from repro.kernels.layout import is_int32_rects, label_rows, table_rows
from repro.search.device_graph import export_device_graph, unpack_labels_device
from repro.serve.admission import validate_query
from repro.distributed.compat import shard_map as _shard_map


def _oracle_labels(lab, nbr, fused: bool):
    """The fused paths dispatch on the label layout; the unfused parity
    baseline needs int32 rectangles, so a packed stack is unpacked
    device-side (trace-time branch — `fused` and the layout are static)."""
    if not fused and not is_int32_rects(lab):
        return unpack_labels_device(lab, nbr.shape[-1])
    return lab


_STACKED = ("vectors", "nbr", "labels", "norms", "U_X", "U_Y", "num_y",
            "entry_node", "entry_y_rank")


def _per_shard(fn, a):
    """Apply a per-table layout function to every shard of a stack."""
    S, n = a.shape[:2]
    out = fn(a.reshape((S * n,) + a.shape[2:]))
    return out.reshape((S, n) + out.shape[1:])


@dataclasses.dataclass
class ShardedIndex:
    """Per-shard UDG arrays stacked on a leading shard dimension."""

    vectors: np.ndarray       # [shards, n_l, d]
    nbr: np.ndarray           # [shards, n_l, E]
    labels: np.ndarray        # [shards, n_l, E, 2] uint32 bit-packed rank
                              # rectangles (the default; [.., E, 4] int32
                              # only when some shard's grid overflowed the
                              # 16-bit rank budget)
    norms: np.ndarray         # [shards, n_l] f32 cached ‖v‖² per node
    U_X: np.ndarray           # [shards, ux_max] f32, +inf padded
    U_Y: np.ndarray           # [shards, uy_max] f32, +inf padded (keeps the
                              # row sorted, so device searchsorted is exact)
    num_y: np.ndarray         # [shards] int32 actual |U_Y| per shard
    entry_node: np.ndarray    # [shards, ux_max] int32
    entry_y_rank: np.ndarray  # [shards, ux_max] int32
    relation: str
    n_local: int
    # per-shard repro.exec.SelectivityEstimator (rank-space histograms for
    # the query planner) — host-side planning state, like the norms are
    # device-side scoring state; rebuilt whenever the shards are rebuilt
    planners: list | None = None
    _cache: dict | None = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def num_shards(self) -> int:
        return int(self.vectors.shape[0])

    def kernel_layout(self, name: str) -> np.ndarray:
        """Host array ``name`` in the layout the serving step consumes:
        vectors as ``[S, n_l, 1, W]`` rows and packed labels as
        ``[S, n_l, 1, W]`` label rows (``repro.kernels.layout``);
        every other array as stacked."""
        a = getattr(self, name)
        if name == "vectors":
            return _per_shard(table_rows, a)
        if name == "labels" and not is_int32_rects(a):
            return _per_shard(label_rows, a)
        return a

    def device(self, mesh) -> dict:
        """Memoized device copies of the stacked database arrays, each
        placed shard-per-device along ``mesh``'s ``model`` axis, so every
        device holds its own shard and nothing else. The serving step's
        inputs are staged once per index build (and mesh) instead of once
        per ``serve_batch`` call, the same fix as ``DeviceGraph.device()``.
        """
        cache = self._cache if self._cache is not None else {}
        held = cache.get("device")
        if held is None or held[0] != mesh:
            staged = cache.get("staged", {})
            put = functools.partial(
                jax.device_put, device=NamedSharding(mesh, P("model")))
            held = (mesh, {
                name: put(staged[name] if name in staged
                          else self.kernel_layout(name))
                for name in _STACKED
            })
            cache["device"] = held
            self._cache = cache
        return held[1]

    def invalidate_device(self) -> None:
        self._cache = None


def build_sharded_index(
    vectors: np.ndarray,
    s: np.ndarray,
    t: np.ndarray,
    relation: str,
    num_shards: int,
    *,
    M: int = 16,
    Z: int = 128,
    K_p: int = 8,
    build_kwargs: dict | None = None,
    edge_capacity: int | None = None,
) -> ShardedIndex:
    """Partition the database round-robin and build one UDG per shard.

    ``build_kwargs`` forwards extra ``build_udg`` options — pass
    ``UdgServeConfig.build_kwargs()`` to select the batched wave
    constructor with shard-capacity padding for production shard sizes.
    ``edge_capacity`` fixes every shard's labeled degree (pass
    ``UdgServeConfig.degree``, the deployment's padded degree): wider rows
    keep their earliest tuples, as in ``export_device_graph``. ``None``
    keeps the widest shard's degree.
    """
    n = vectors.shape[0]
    assert n % num_shards == 0, (n, num_shards)
    n_l = n // num_shards
    parts = [np.arange(sh, n, num_shards) for sh in range(num_shards)]
    dgs = []
    for ids in parts:
        g, _ = build_udg(vectors[ids], s[ids], t[ids], relation, M=M, Z=Z,
                         K_p=K_p, **(build_kwargs or {}))
        dgs.append(export_device_graph(g, EntryTable(g),
                                       edge_capacity=edge_capacity))
    planners = [dg.planner for dg in dgs]
    E = max(dg.max_degree for dg in dgs)
    ux = max(dg.U_X.shape[0] for dg in dgs)
    uy = max(dg.U_Y.shape[0] for dg in dgs)

    def padE(a, e, fill):
        out = np.full(a.shape[:1] + (e,) + a.shape[2:], fill, dtype=a.dtype)
        out[:, : a.shape[1]] = a
        return out

    vec = np.stack([dg.vectors for dg in dgs])
    nbr = np.stack([padE(dg.nbr, E, -1) for dg in dgs])
    # every shard packs under the same 16-bit rank budget (shard grids are
    # <= n_l values); one overflowing shard demotes the whole stack to the
    # int32 layout so the serving step sees a single label shape
    if all(dg.plabels is not None for dg in dgs):
        lab = np.stack([padE(dg.plabels, E, 0) for dg in dgs])
    else:
        lab = np.stack([padE(dg.labels_i32(), E, 0) for dg in dgs])
    nrm = np.stack([dg.norms for dg in dgs])
    UX = np.full((num_shards, ux), np.inf, np.float32)
    UY = np.full((num_shards, uy), np.inf, np.float32)
    ent = np.full((num_shards, ux), -1, np.int32)
    enty = np.full((num_shards, ux), np.iinfo(np.int32).max, np.int32)
    num_y = np.zeros(num_shards, np.int32)
    for i, dg in enumerate(dgs):
        kx = dg.U_X.shape[0]
        UX[i, :kx] = dg.U_X.astype(np.float32)
        UY[i, : dg.U_Y.shape[0]] = dg.U_Y.astype(np.float32)
        num_y[i] = dg.U_Y.shape[0]
        ent[i, :kx] = dg.entry_node
        enty[i, :kx] = dg.entry_y_rank
    return ShardedIndex(
        vectors=vec, nbr=nbr, labels=lab, norms=nrm, U_X=UX, U_Y=UY,
        num_y=num_y, entry_node=ent, entry_y_rank=enty, relation=relation,
        n_local=n_l, planners=planners,
    )


def segments_to_sharded_index(segidx) -> tuple:
    """Stack a ``repro.scale.SegmentedIndex`` into the shard_map serving
    layout — segments sharded across hosts. Returns ``(sharded, id_map)``.

    The segments already share one ``node_capacity``/``edge_capacity``/
    label layout (the segmented build's uniform-export contract), so the
    stack needs no per-shard re-padding beyond the canonical grids. Two
    deltas vs ``build_sharded_index``'s round-robin partition:

    * membership is dominance-driven, not ``id % S``, so the serving
      step's synthetic global ids (``shard · n_l + local``) do not equal
      object ids — ``id_map [S, n_l] int64`` (-1 on padding rows) plus
      :func:`remap_shard_ids` recover them;
    * int8-resident segments stack their *float32* rows (``ShardedIndex``
      carries no scales), with norms recomputed from those rows so the
      fused scorer sees matching vector/norm pairs — the rerank-exact
      contract of the segmented tier, applied fleet-wide.
    """
    dgs = [seg.dg for seg in segidx.segments]
    S = len(dgs)
    n_l = int(segidx.node_capacity)
    E = max(dg.max_degree for dg in dgs)
    ux = max(dg.U_X.shape[0] for dg in dgs)
    uy = max(dg.U_Y.shape[0] for dg in dgs)

    def padE(a, e, fill):
        out = np.full(a.shape[:1] + (e,) + a.shape[2:], fill, dtype=a.dtype)
        out[:, : a.shape[1]] = a
        return out

    vec = np.stack([np.asarray(dg.vectors, np.float32) for dg in dgs])
    nbr = np.stack([padE(dg.nbr, E, -1) for dg in dgs])
    if all(dg.plabels is not None for dg in dgs):
        lab = np.stack([padE(dg.plabels, E, 0) for dg in dgs])
    else:
        lab = np.stack([padE(dg.labels_i32(), E, 0) for dg in dgs])
    nrm = np.einsum("sij,sij->si", vec, vec).astype(np.float32)
    UX = np.full((S, ux), np.inf, np.float32)
    UY = np.full((S, uy), np.inf, np.float32)
    ent = np.full((S, ux), -1, np.int32)
    enty = np.full((S, ux), np.iinfo(np.int32).max, np.int32)
    num_y = np.zeros(S, np.int32)
    id_map = np.full((S, n_l), -1, np.int64)
    for i, dg in enumerate(dgs):
        kx = dg.U_X.shape[0]
        UX[i, :kx] = dg.U_X.astype(np.float32)
        UY[i, : dg.U_Y.shape[0]] = dg.U_Y.astype(np.float32)
        num_y[i] = dg.U_Y.shape[0]
        ent[i, :kx] = dg.entry_node
        enty[i, :kx] = dg.entry_y_rank
        seg = segidx.segments[i]
        id_map[i, : seg.ids.shape[0]] = seg.ids
    planners = [dg.planner for dg in dgs]
    # quarantined segments serve as provably-empty shards: no entry points
    # (graph walks cannot start), an n=0 estimator (every count bound is 0,
    # so the planner routes BRUTE over an empty id list) and a -1 id_map
    # row (any stray synthetic id remaps to the drop sentinel). The shard
    # axis keeps its full extent — same mesh, same compiled step.
    for si in sorted(getattr(segidx, "quarantined", ())):
        ent[si, :] = -1
        enty[si, :] = np.iinfo(np.int32).max
        id_map[si, :] = -1
        p = planners[si]
        if p is not None:
            planners[si] = SelectivityEstimator(
                np.empty(0, np.int64), np.empty(0, np.int64),
                p.num_x, p.num_y, buckets=p.buckets,
            )
    sharded = ShardedIndex(
        vectors=vec, nbr=nbr, labels=lab, norms=nrm, U_X=UX, U_Y=UY,
        num_y=num_y, entry_node=ent, entry_y_rank=enty,
        relation=segidx.relation.name, n_local=n_l,
        planners=planners,
    )
    _prime_device_from_stack(sharded, segidx, E=E)
    return sharded, id_map


def _prime_device_from_stack(sharded: ShardedIndex, segidx, *, E):
    """Pre-stage the sharded device bundle from the segmented tier's
    flat ``SegmentStack`` — the graph topology and label table (the two
    largest components) are DERIVED from the scheduler's stacked buffers
    on device (un-offsetting the flat adjacency, reshaping the label rows)
    instead of re-staging independent host copies. Vectors and norms still
    stage from the host stack: the sharded contract is f32 rows + f32-row
    norms, which an int8-resident stack does not carry. Skipped when the
    stack's layout diverges from the stacked host arrays (never the case
    for a uniform segmented export — belt and braces)."""
    try:
        stack = segidx.device_stack()
    except (AttributeError, ValueError):
        return
    S = stack.num_segments
    ncap = stack.node_capacity
    if (stack.edge_capacity != E or S != sharded.num_shards
            or ncap != sharded.n_local):
        return
    flat_lab = stack.flat("labels")
    if is_int32_rects(flat_lab) != is_int32_rects(sharded.labels):
        return
    flat_nbr = stack.flat("nbr")
    base = (jnp.arange(S, dtype=jnp.int32) * ncap)[:, None, None]
    nbr_dev = flat_nbr.reshape(S, ncap, E)
    nbr_dev = jnp.where(nbr_dev >= 0, nbr_dev - base, jnp.int32(-1))
    sharded._cache = {"staged": {
        "nbr": nbr_dev,
        "labels": flat_lab.reshape((S, ncap) + flat_lab.shape[1:]),
    }}


def remap_shard_ids(id_map: np.ndarray, gids: np.ndarray) -> np.ndarray:
    """Translate serving-step synthetic ids (``shard · n_l + local``) back
    to true object ids via the ``id_map`` from
    :func:`segments_to_sharded_index`; -1 passes through."""
    S, n_l = id_map.shape
    g = np.asarray(gids, dtype=np.int64)
    safe = np.clip(g, 0, S * n_l - 1)
    out = id_map.reshape(-1)[safe]
    return np.where(g >= 0, out, np.int64(-1))


def _canonicalize_local(UX, UY, num_y, ent, enty, xq, yq):
    """Device-side Lemma 1 snap onto shard-local canonical grids.

    Both grids are padded with trailing +inf, which keeps each row sorted so
    ``searchsorted`` is exact, and guarantees ``c <= num_y - 1`` for finite
    queries (the clamp is a belt-and-braces no-op). The historical -inf
    Y-padding broke sortedness: binary search could land in the pad region
    and the old ``c >= num_y -> invalid`` guard then silently dropped the
    whole shard from perfectly valid (often broad) queries.
    """
    a = jnp.searchsorted(UX, xq, side="left").astype(jnp.int32)
    c = (jnp.searchsorted(UY, yq, side="right") - 1).astype(jnp.int32)
    num_x = UX.shape[0]
    c = jnp.minimum(c, num_y - 1)
    invalid = (a >= num_x) | (c < 0)
    a_cl = jnp.clip(a, 0, num_x - 1)
    ep = ent[a_cl]
    ep = jnp.where(invalid | (ep < 0) | (enty[a_cl] > c), -1, ep)
    return jnp.stack([a_cl, jnp.maximum(c, 0)], axis=1), ep


def plan_sharded_batch(
    idx: ShardedIndex,
    xq: np.ndarray,
    yq: np.ndarray,
    *,
    config: PlannerConfig,
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side per-shard planning for one query batch.

    Mirrors ``_canonicalize_local`` (f32 grids, +inf padding) so the rank
    states the planner counts with are exactly the states the device search
    will run with, then consults each shard's rank-space histogram.
    Returns (plans [S, B] int32, bf_ids [S, B, V] int32 — *shard-local*
    brute-path valid ids, -1 padded).
    """
    if idx.planners is None:
        raise ValueError("ShardedIndex has no planner state (planners=None)")
    S = idx.num_shards
    xq = np.asarray(xq, np.float32)
    yq = np.asarray(yq, np.float32)
    B = xq.shape[0]
    plans = np.full((S, B), int(QueryPlan.GRAPH), dtype=np.int32)
    bf_ids = np.full((S, B, config.brute_max_valid), -1, dtype=np.int32)
    for sh in range(S):
        est = idx.planners[sh]
        a = np.searchsorted(idx.U_X[sh], xq, side="left")
        c = np.searchsorted(idx.U_Y[sh], yq, side="right") - 1
        c = np.minimum(c, int(idx.num_y[sh]) - 1)
        invalid = (a >= est.num_x) | (c < 0)
        states = np.stack(
            [np.clip(a, 0, est.num_x - 1), np.maximum(c, 0)], axis=1
        ).astype(np.int32)
        pb = plan_queries(est, states, invalid, config=config)
        plans[sh] = pb.plans
        bf_ids[sh] = pb.bf_ids
    return plans, bf_ids


def _merge_across_shards(mesh, gids, d_l, *, k: int, merge: str):
    """Cross-shard top-k merge over the ``model`` axis (inside shard_map)."""
    if merge == "tournament":
        # log-step pairwise merge: each hop exchanges only k entries
        num_shards = mesh.shape["model"]
        step = 1
        while step < num_shards:
            perm = [(i, i ^ step) for i in range(num_shards)]
            o_ids = jax.lax.ppermute(gids, "model", perm)
            o_d = jax.lax.ppermute(d_l, "model", perm)
            cat_d = jnp.concatenate([d_l, o_d], axis=1)
            cat_i = jnp.concatenate([gids, o_ids], axis=1)
            nd, ni = jax.lax.sort((cat_d, cat_i), dimension=1, num_keys=1)
            d_l, gids = nd[:, :k], ni[:, :k]
            step *= 2
        return gids, d_l
    all_i = jax.lax.all_gather(gids, "model", axis=1)   # [B, S, k]
    all_d = jax.lax.all_gather(d_l, "model", axis=1)
    B = all_i.shape[0]
    cat_d = all_d.reshape(B, -1)
    cat_i = all_i.reshape(B, -1)
    nd, ni = jax.lax.sort((cat_d, cat_i), dimension=1, num_keys=1)
    return ni[:, :k], nd[:, :k]


def make_serving_step(
    mesh,
    relation: str,
    *,
    k: int = 10,
    beam: int = 64,
    max_iters: int | None = None,
    merge: str = "all_gather",     # all_gather | tournament
    use_ref_kernel: bool | None = None,
    unroll_iters: int = 0,
    int8_vectors: bool = False,
    fused: bool = True,
    expand: int = 1,
    stats: bool = False,
):
    """Build the jitted shard_map serving step for ``mesh``.

    Signature of the returned fn:
      (vectors, nbr, labels, norms, U_X, U_Y, num_y, entry_node,
       entry_y_rank, q, xq, yq[, scales]) -> (global_ids [B, k], dists [B, k])
    with the database arrays carrying the leading shard dim. With
    ``int8_vectors`` the database is int8 + per-vector f32 scales (4x less
    HBM traffic on beam-expansion gathers — EXPERIMENTS.md §Perf U3).
    ``fused`` selects the gather-fused beam expansion (in-kernel HBM gather
    off the cached ``norms``, bit-packed visited); ``expand`` widens each
    iteration to the best M unexpanded beam entries.

    ``stats=True`` appends a third output: {field: [B] int32} per-query
    traversal counters (the ``SearchStats`` [B]-shaped fields) psum'd over
    the ``model`` axis, i.e. fleet-wide totals per query
    (``hit_max_iters`` becomes the *count of shards* that hit the cap).
    """
    max_iters = max_iters if max_iters is not None else 2 * beam
    batch_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)

    def shard_fn(vec, nbr, lab, nrm, UX, UY, num_y, ent, enty, q, xq, yq,
                 scales=None):
        # leading shard dim is 1 on-device
        vec, nbr, lab, nrm = (
            vec[0], nbr[0], _oracle_labels(lab[0], nbr[0], fused), nrm[0])
        UX, UY, ent, enty = UX[0], UY[0], ent[0], enty[0]
        states, ep = _canonicalize_local(UX, UY, num_y[0], ent, enty, xq, yq)
        # cached norms must match the rows the kernel scores: ShardedIndex
        # stacks f32-row norms, so on the int8 path they are dropped and the
        # core recomputes sum(c_q^2)*scale^2 (dequantized norms) per batch
        out = _batched_search_core(
            vec, nbr, lab, q, states, ep,
            k=k, beam=beam, max_iters=max_iters, use_ref=use_ref_kernel,
            fused=fused, expand=expand,
            unroll_iters=unroll_iters,
            scales=scales[0] if scales is not None else None,
            norms=None if int8_vectors else nrm,
            stats=stats,
        )
        ids_l, d_l = out[0], out[1]
        shard_id = jax.lax.axis_index("model")
        n_l = vec.shape[0]
        gids = jnp.where(ids_l >= 0, ids_l * 1 + shard_id * n_l, -1)
        d_l = jnp.where(ids_l >= 0, d_l, jnp.inf)
        merged = _merge_across_shards(mesh, gids, d_l, k=k, merge=merge)
        if stats:
            pq = {
                name: jax.lax.psum(v, "model")
                for name, v in per_query_dict(out[3]).items()
            }
            return merged + (pq,)
        return merged

    shard_spec = P("model")
    qspec = P(batch_axes)
    in_specs = (shard_spec,) * 9 + (qspec, qspec, qspec)
    if int8_vectors:
        in_specs = in_specs + (shard_spec,)
    out_specs = (qspec, qspec)
    if stats:
        out_specs = out_specs + (
            {name: qspec for name in SearchStats._fields},
        )
    fn = _shard_map(shard_fn, mesh, in_specs, out_specs)
    return jax.jit(fn)


def make_planned_serving_step(
    mesh,
    relation: str,
    *,
    k: int = 10,
    beam: int = 64,
    max_iters: int | None = None,
    merge: str = "all_gather",     # all_gather | tournament
    use_ref_kernel: bool | None = None,
    fused: bool = True,
    expand: int = 1,
    config: PlannerConfig | None = None,
):
    """Planner-routed variant of :func:`make_serving_step`.

    Two extra query-sharded inputs carry the host planning result
    (``plan_sharded_batch``): per-shard plans ``[S, B]`` and shard-local
    brute-path valid ids ``[S, B, V]``. Each shard runs the three-way
    padding-dispatched executor (``repro.exec``) and the usual cross-shard
    top-k merge. All shapes are fixed by capacities and the planner config,
    so one compiled program serves every plan mix.

    Signature of the returned fn:
      (vectors, nbr, labels, norms, U_X, U_Y, num_y, entry_node,
       entry_y_rank, q, xq, yq, plans, bf_ids) -> (global_ids, dists)
    """
    config = config or default_planner_config()
    max_iters = max_iters if max_iters is not None else 2 * beam
    wide_beam = max(beam * config.wide_beam_scale, beam)
    wide_expand = config.wide_expand if fused else 1
    batch_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)

    def shard_fn(vec, nbr, lab, nrm, UX, UY, num_y, ent, enty, q, xq, yq,
                 plans, bf_ids):
        vec, nbr, lab, nrm = (
            vec[0], nbr[0], _oracle_labels(lab[0], nbr[0], fused), nrm[0])
        UX, UY, ent, enty = UX[0], UY[0], ent[0], enty[0]
        plans, bf_ids = plans[0], bf_ids[0]
        states, ep = _canonicalize_local(UX, UY, num_y[0], ent, enty, xq, yq)
        ep_graph = jnp.where(plans == int(QueryPlan.GRAPH), ep, -1)
        ep_wide = jnp.where(plans == int(QueryPlan.GRAPH_WIDE), ep, -1)
        ids_l, d_l, _ = planned_exec_core(
            vec, nbr, lab, q.astype(jnp.float32), states,
            ep_graph, ep_wide, bf_ids, plans,
            k=k, beam=beam, wide_beam=wide_beam,
            max_iters=max_iters,
            wide_max_iters=max_iters * config.wide_beam_scale,
            use_ref=use_ref_kernel, fused=fused, expand=expand,
            wide_expand=wide_expand, norms=nrm,
        )
        shard_id = jax.lax.axis_index("model")
        n_l = vec.shape[0]
        gids = jnp.where(ids_l >= 0, ids_l + shard_id * n_l, -1)
        d_l = jnp.where(ids_l >= 0, d_l, jnp.inf)
        return _merge_across_shards(mesh, gids, d_l, k=k, merge=merge)

    shard_spec = P("model")
    qspec = P(batch_axes)
    # plans/bf_ids carry a leading shard dim (per-shard planning results)
    # AND a query-batch dim sharded like q itself
    pspec = P("model", batch_axes)
    in_specs = (shard_spec,) * 9 + (qspec, qspec, qspec) + (pspec, pspec)
    fn = _shard_map(shard_fn, mesh, in_specs, (qspec, qspec))
    return jax.jit(fn)


# serve_batch memoizes its jitted shard_map steps here: jax.jit caches by
# function identity, so rebuilding the closure per call would re-trace and
# recompile every batch. Keyed by mesh identity + static step parameters
# (PlannerConfig is frozen, hence hashable). Bounded FIFO: each entry pins
# its mesh alive through the closure (which also keeps the id(mesh) key
# valid), so eviction caps both compiled-program and mesh retention for
# long-lived processes sweeping configurations.
_STEP_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_STEP_CACHE_MAX = 16


def _cached_step(key, make):
    step = _STEP_CACHE.get(key)
    if step is None:
        step = _STEP_CACHE.setdefault(key, make())
        while len(_STEP_CACHE) > _STEP_CACHE_MAX:
            _STEP_CACHE.popitem(last=False)
    return step


def serve_batch(
    idx: ShardedIndex,
    mesh,
    q: np.ndarray,
    s_q: np.ndarray,
    t_q: np.ndarray,
    *,
    k: int = 10,
    beam: int = 64,
    merge: str = "all_gather",
    plan: str = "auto",
    planner_config: PlannerConfig | None = None,
    id_map: np.ndarray | None = None,
    missing_shards: Sequence[int] | None = None,
    return_partial: bool = False,
):
    """Host entry point: run one distributed batch end-to-end.

    ``plan="auto"`` plans each (query, shard) pair from the shard's
    rank-space histogram and serves through the planned step; ``"graph"``
    is the pre-planner single-strategy path (parity oracle; also the
    fallback for indexes without planner state). Returned ids are
    ROUND-ROBIN global: original_id = local_id*shards+shard is inverted
    here so callers see dataset ids — unless ``id_map`` is given (a
    segment-stacked index from :func:`segments_to_sharded_index`, whose
    membership is dominance-driven, not round-robin), in which case ids
    are translated through :func:`remap_shard_ids` instead.

    ``return_partial=True`` wraps the answer in a :class:`PartialResult`
    whose ``missing_shards`` comes from the caller (typically the
    segmented tier's quarantine list — the shards masked out of this
    index by :func:`segments_to_sharded_index`) so clients see a correct
    top-k over the surviving shards explicitly flagged as degraded."""
    if plan not in ("auto", "graph"):
        raise ValueError(f"plan={plan!r} not in ('auto', 'graph')")
    # boundary hardening: a NaN/Inf anywhere in the batch silently poisons
    # the shared distance computations, so reject before touching devices.
    # Sentinel padding rows (s > t = empty valid set) are legitimate here.
    q = validate_query(
        q, s_q, t_q, what="serve_batch", require_ordered=False,
    )
    rel = get_relation(idx.relation)
    xq, yq = rel.query_map(
        np.asarray(s_q, np.float64), np.asarray(t_q, np.float64)
    )
    if plan == "auto" and idx.planners is not None:
        config = planner_config or default_planner_config()
        plans, bf_ids = plan_sharded_batch(
            idx, np.asarray(xq, np.float32), np.asarray(yq, np.float32),
            config=config,
        )
        step = _cached_step(
            ("planned", id(mesh), idx.relation, k, beam, merge, config),
            lambda: make_planned_serving_step(
                mesh, idx.relation, k=k, beam=beam, merge=merge, config=config
            ),
        )
        dev = idx.device(mesh)
        gids, d = step(
            dev["vectors"], dev["nbr"], dev["labels"], dev["norms"],
            dev["U_X"], dev["U_Y"], dev["num_y"], dev["entry_node"],
            dev["entry_y_rank"],
            np.asarray(q, np.float32),
            np.asarray(xq, np.float32),
            np.asarray(yq, np.float32),
            plans, bf_ids,
        )
    else:
        step = _cached_step(
            ("graph", id(mesh), idx.relation, k, beam, merge),
            lambda: make_serving_step(
                mesh, idx.relation, k=k, beam=beam, merge=merge
            ),
        )
        dev = idx.device(mesh)
        gids, d = step(
            dev["vectors"], dev["nbr"], dev["labels"], dev["norms"],
            dev["U_X"], dev["U_Y"], dev["num_y"], dev["entry_node"],
            dev["entry_y_rank"],
            np.asarray(q, np.float32),
            np.asarray(xq, np.float32),
            np.asarray(yq, np.float32),
        )
    gids = np.asarray(gids)
    d = np.asarray(d)
    if id_map is not None:
        ids = remap_shard_ids(id_map, gids)
    else:
        shard = gids // idx.n_local
        local = gids % idx.n_local
        ids = np.where(gids >= 0, local * idx.num_shards + shard, -1)
    if return_partial:
        missing = sorted(int(s) for s in (missing_shards or ()))
        d = np.where(ids >= 0, d, np.inf).astype(np.float32)
        return PartialResult(
            ids=ids, dists=d, degraded=bool(missing),
            missing_shards=missing,
        )
    return ids, d


# --- partial-result merge (degraded responses under shard loss) ----------------


@dataclasses.dataclass
class PartialResult:
    """Merged top-k over the shards that answered. ``degraded=True`` (one
    or more shards contributed nothing — both the primary and its
    speculative replica missed the deadline or raised) means the result is
    a correct top-k over a *subset* of the database; ``missing_shards``
    names the gaps so callers can retry or annotate."""

    ids: np.ndarray        # [B, k] global ids, -1 padded
    dists: np.ndarray      # [B, k] squared distances, +inf padded
    degraded: bool
    missing_shards: List[int]


def merge_partial_results(
    per_shard: Sequence[Optional[Tuple[np.ndarray, np.ndarray]]],
    *,
    k: int,
) -> PartialResult:
    """Host-side top-k merge across shard responses where some entries may
    be ``None`` (shard + replica both missed — the output of
    ``SpeculativeDispatcher.call_all_partial``).

    Top-k over a union is the merge of per-shard top-k, so dropping a
    shard degrades coverage, never correctness of the surviving
    candidates: every returned (id, dist) pair is exact. An all-``None``
    input yields the fully-padded empty result rather than raising —
    total shard loss is an operational event the caller flags, not a
    crash."""
    missing = [i for i, r in enumerate(per_shard) if r is None]
    avail = [r for r in per_shard if r is not None]
    if not avail:
        return PartialResult(
            ids=np.full((0, k), -1, np.int32),
            dists=np.full((0, k), np.inf, np.float32),
            degraded=True, missing_shards=missing,
        )
    ids = np.concatenate([np.asarray(r[0]) for r in avail], axis=1)
    dists = np.concatenate(
        [np.asarray(r[1], np.float32) for r in avail], axis=1
    )
    # -1 padding rows carry +inf so they sort last regardless of the
    # distance the shard reported for them
    dists = np.where(ids >= 0, dists, np.inf)
    order = np.argsort(dists, axis=1, kind="stable")[:, :k]
    return PartialResult(
        ids=np.take_along_axis(ids, order, axis=1),
        dists=np.take_along_axis(dists, order, axis=1),
        degraded=bool(missing), missing_shards=missing,
    )


# --- streaming (online mutations + per-shard epoch swap) -----------------------


class ShardedStreamingIndex:
    """One ``StreamingIndex`` per shard with round-robin insert routing.

    External ids are globally unique (shard s uses ids ≡ s mod S), so
    ``delete`` and result merging need no translation tables. Compaction is
    *per shard*: ``maybe_compact_shards`` rebuilds at most one shard per
    call, so at any instant at most one shard is paused in its (sub-ms)
    epoch swap while the rest keep serving — the distributed analogue of the
    single-host epoch swap.

    Every shard shares one static serving shape (same capacities), so the
    jitted streaming step — single-host or the ``make_streaming_serving_step``
    mesh version below — is compiled once for the whole fleet and survives
    every per-shard swap.
    """

    def __init__(
        self,
        dim: int,
        relation: str,
        num_shards: int,
        **kwargs,
    ):
        from repro.stream import StreamingIndex

        self.dim = dim
        self.relation = relation
        self.num_shards = num_shards
        self.shards = [
            StreamingIndex(
                dim, relation, id_start=sh, id_stride=num_shards, **kwargs
            )
            for sh in range(num_shards)
        ]
        self._rr = 0

    # --- mutations ------------------------------------------------------------

    def insert(self, vec: np.ndarray, s: float, t: float) -> int:
        sh = self._rr
        self._rr = (self._rr + 1) % self.num_shards
        return self.shards[sh].insert(vec, s, t)

    def insert_batch(self, vecs, s, t) -> np.ndarray:
        return np.array(
            [self.insert(vecs[i], s[i], t[i]) for i in range(len(vecs))],
            dtype=np.int64,
        )

    def delete(self, ext_id: int) -> bool:
        return self.shards[int(ext_id) % self.num_shards].delete(ext_id)

    @property
    def live_count(self) -> int:
        return sum(sh.live_count for sh in self.shards)

    def maybe_compact_shards(self) -> int:
        """Compact the single most-mutated shard over threshold (staggered
        swaps). Returns the shard index, or -1 if none qualified."""
        cand = [
            (sh.delta_fraction, i)
            for i, sh in enumerate(self.shards)
            if sh.should_compact()
        ]
        if not cand:
            return -1
        _, i = max(cand)
        self.shards[i].compact()
        return i

    # --- host-merge query path ------------------------------------------------

    def search(
        self, q, s_q, t_q, *, k: int = 10, beam: int = 64,
        use_ref: bool | None = None, fused: bool = True, plan: str = "auto",
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Query every shard (one shared jit trace) and merge per-shard
        top-k by distance. Top-k over a union = merge of per-shard top-k.
        Each shard plans its own queries (selectivity differs per shard);
        ``plan="graph"`` forces the pre-planner path everywhere."""
        per = [
            sh.search(q, s_q, t_q, k=k, beam=beam, use_ref=use_ref,
                      fused=fused, plan=plan)
            for sh in self.shards
        ]
        all_ids = np.concatenate([p[0] for p in per], axis=1)
        all_d = np.concatenate([p[1] for p in per], axis=1)
        all_d = np.where(all_ids >= 0, all_d, np.inf)
        order = np.argsort(all_d, axis=1, kind="stable")[:, :k]
        return (
            np.take_along_axis(all_ids, order, 1),
            np.take_along_axis(all_d, order, 1),
        )

    # --- mesh (shard_map) query path ------------------------------------------

    def stacked_arrays(self) -> dict:
        """Stack every shard's epoch + delta arrays on a leading shard dim.

        All dims are capacity-static: refreshing a shard after its epoch
        swap (``refresh_shard``) republishes one slice copy-on-write and the
        jitted mesh step keeps its single compiled program.
        """
        S = self.num_shards
        sh0 = self.shards[0]
        ncap, dcap = sh0.node_capacity, sh0.delta_capacity
        ecap, dim = sh0.edge_capacity, sh0.dim
        # every shard shares one construction-time label layout (see
        # StreamingIndex._packed_labels), so the stack — and the jitted
        # mesh step's label shape — is fixed for the fleet's lifetime
        if sh0._packed_labels:
            lab_stack = np.zeros((S, ncap, ecap, 2), np.uint32)
        else:
            lab_stack = np.zeros((S, ncap, ecap, 4), np.int32)
        out = {
            "vectors": np.zeros((S, ncap, dim), np.float32),
            "nbr": np.full((S, ncap, ecap), -1, np.int32),
            "labels": lab_stack,
            "norms": np.zeros((S, ncap), np.float32),
            "live": np.zeros((S, ncap), bool),
            "ext": np.full((S, ncap), -1, np.int32),
            "dvec": np.zeros((S, dcap, dim), np.float32),
            "dlab": np.zeros((S, dcap, 4), np.int32),
            "dids": np.full((S, dcap), -1, np.int32),
            "dext": np.full((S, dcap), -1, np.int32),
            "U_X": np.full((S, ncap), np.inf, np.float32),
            "U_Y": np.full((S, ncap), np.inf, np.float32),
            "num_y": np.zeros(S, np.int32),
            "entry_node": np.full((S, ncap), -1, np.int32),
            "entry_y_rank": np.full((S, ncap), np.iinfo(np.int32).max, np.int32),
        }
        for i in range(S):
            self._write_shard(out, i)
        return out

    def refresh_shard(self, stacked: dict, i: int) -> dict:
        """Per-shard epoch swap in the distributed path: republish shard i's
        current epoch (a consistent snapshot taken under the shard's lock).

        Copy-on-write: returns a NEW dict with fresh arrays; the caller
        swaps its reference atomically, so a serving thread holding the old
        dict keeps a complete epoch-N view and can never observe a torn
        (half-rewritten) shard."""
        fresh = {key: a.copy() for key, a in stacked.items()}
        self._write_shard(fresh, i)
        return fresh

    def _write_shard(self, stacked: dict, i: int) -> None:
        sh = self.shards[i]
        with sh._lock:
            dg = sh._dg
            live = sh._graph_live.copy()
            ext = np.where(live, sh._graph_ext, -1).astype(np.int32)
            seg = sh._delta.device_segment()
        stacked["vectors"][i] = dg.vectors
        stacked["nbr"][i] = dg.nbr
        stacked["labels"][i] = (
            dg.plabels if stacked["labels"].dtype == np.uint32
            else dg.labels_i32()
        )
        stacked["norms"][i] = dg.norms
        stacked["live"][i] = live
        stacked["ext"][i] = ext
        stacked["dvec"][i] = seg.vectors
        stacked["dlab"][i] = seg.labels
        stacked["dids"][i] = seg.slot_ids
        stacked["dext"][i] = seg.ext_ids
        kx, ky = dg.U_X.shape[0], dg.U_Y.shape[0]
        stacked["U_X"][i] = np.inf
        stacked["U_X"][i, :kx] = dg.U_X.astype(np.float32)
        stacked["U_Y"][i] = np.inf
        stacked["U_Y"][i, :ky] = dg.U_Y.astype(np.float32)
        stacked["num_y"][i] = ky
        stacked["entry_node"][i] = -1
        stacked["entry_node"][i, :kx] = dg.entry_node
        stacked["entry_y_rank"][i] = np.iinfo(np.int32).max
        stacked["entry_y_rank"][i, :kx] = dg.entry_y_rank


def make_streaming_serving_step(
    mesh,
    *,
    k: int = 10,
    beam: int = 64,
    max_iters: int | None = None,
    use_ref_kernel: bool | None = None,
    fused: bool = True,
    expand: int = 1,
    stats: bool = False,
):
    """Jitted shard_map step for streaming serving: two-tier search per
    shard (tombstone-masked gather-fused graph beam + gather-fused delta
    scan) then cross-shard top-k merge. Results are *external* ids, so no
    round-robin inversion. All shapes are capacity-fixed, so per-shard
    epoch swaps keep hitting this one compiled program.

    Signature of the returned fn (leading shard dim on database arrays):
      (vectors, nbr, labels, norms, live, ext, dvec, dlab, dids, dext,
       U_X, U_Y, num_y, entry_node, entry_y_rank,
       q, xq, yq, dstate) -> (ext_ids [B, k], dists [B, k])

    ``stats=True`` appends a third output: {field: [B] int32} per-query
    counters psum'd over ``model`` — graph-tier traversal totals plus
    ``delta_valid`` (delta-tier candidates passing the filter, all shards).
    """
    from repro.stream.search import two_tier_merge

    max_iters = max_iters if max_iters is not None else 2 * beam
    batch_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)

    def shard_fn(vec, nbr, lab, nrm, live, ext, dvec, dlab, dids, dext,
                 UX, UY, num_y, ent, enty, q, xq, yq, dstate):
        vec, nbr, lab, nrm = (
            vec[0], nbr[0], _oracle_labels(lab[0], nbr[0], fused), nrm[0])
        live, ext = live[0], ext[0]
        dvec, dlab, dids, dext = dvec[0], dlab[0], dids[0], dext[0]
        UX, UY, ent, enty = UX[0], UY[0], ent[0], enty[0]
        states, ep = _canonicalize_local(UX, UY, num_y[0], ent, enty, xq, yq)
        q32 = q.astype(jnp.float32)
        core = _batched_search_core(
            vec, nbr, lab, q32, states, ep,
            k=beam, beam=beam, max_iters=max_iters, use_ref=use_ref_kernel,
            fused=fused, expand=expand, norms=nrm, stats=stats,
        )
        ids_l, d_l = core[0], core[1]
        merged = two_tier_merge(
            ids_l, d_l, live, ext, q32, dvec, dlab, dids, dext, dstate,
            k=k, use_ref=use_ref_kernel, fused=fused,
            st=core[3] if stats else None,
        )
        i_k, d_k = merged[0], merged[1]
        B = q.shape[0]
        all_i = jax.lax.all_gather(i_k, "model", axis=1)    # [B, S, k]
        all_d = jax.lax.all_gather(d_k, "model", axis=1)
        cat_d = all_d.reshape(B, -1)
        cat_i = all_i.reshape(B, -1)
        nd, ni = jax.lax.sort((cat_d, cat_i), dimension=1, num_keys=1)
        if stats:
            pq = {
                name: jax.lax.psum(v, "model")
                for name, v in per_query_dict(merged[2]).items()
            }
            return ni[:, :k], nd[:, :k], pq
        return ni[:, :k], nd[:, :k]

    shard_spec = P("model")
    qspec = P(batch_axes)
    in_specs = (shard_spec,) * 15 + (qspec,) * 4
    out_specs = (qspec, qspec)
    if stats:
        out_specs = out_specs + (
            {name: qspec for name in SearchStats._fields},
        )
    fn = _shard_map(shard_fn, mesh, in_specs, out_specs)
    return jax.jit(fn)


def serve_streaming_batch(
    stacked: dict,
    mesh,
    relation: str,
    q: np.ndarray,
    s_q: np.ndarray,
    t_q: np.ndarray,
    *,
    step=None,
    k: int = 10,
    beam: int = 64,
) -> Tuple[np.ndarray, np.ndarray]:
    """Host entry point for the mesh streaming path. Pass a prebuilt ``step``
    (from ``make_streaming_serving_step``) to reuse its compiled program
    across epoch swaps."""
    from repro.stream.delta import query_key_state

    rel = get_relation(relation)
    s_q = np.asarray(s_q, np.float64)
    t_q = np.asarray(t_q, np.float64)
    xq, yq = rel.query_map(s_q, t_q)
    dstate = query_key_state(rel, s_q, t_q)
    if step is None:
        step = make_streaming_serving_step(mesh, k=k, beam=beam)
    out = step(
        stacked["vectors"], stacked["nbr"], stacked["labels"],
        stacked["norms"], stacked["live"], stacked["ext"],
        stacked["dvec"], stacked["dlab"], stacked["dids"], stacked["dext"],
        stacked["U_X"], stacked["U_Y"], stacked["num_y"],
        stacked["entry_node"], stacked["entry_y_rank"],
        np.asarray(q, np.float32),
        np.asarray(xq, np.float32),
        np.asarray(yq, np.float32),
        dstate,
    )
    if len(out) == 3:   # a step built with stats=True: per-query counters
        return (np.asarray(out[0]), np.asarray(out[1]),
                {name: np.asarray(v) for name, v in out[2].items()})
    return np.asarray(out[0]), np.asarray(out[1])
