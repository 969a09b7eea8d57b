"""Segmented scale-out index (repro.scale): router completeness, int8+rerank
parity, no-recompile across segment mixes, byte accounting, determinism,
segment-local streaming compaction, and segment-sharded serving.

The load-bearing invariant (property-tested below across all five
relations) is **router completeness**: for every query whose canonical
state exists, every object satisfying ``DominanceSpace.valid_mask_state``
lives in a routed cell. Over-selection is fine; a dropped valid object is
a recall bug. The value-space router (`route_values`, the streaming twin)
is pinned under the same invariant.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import get_relation
from repro.core.build_batched import build_udg_batched
from repro.core.predicates import RELATIONS, DominanceSpace
from repro.data import (
    generate_queries,
    ground_truth,
    make_dataset,
    make_queries_vectors,
    make_vectors,
    recall_at_k,
)
from repro.exec import (
    execute_batch,
    planned_exec_cache_size,
    worklist_exec_cache_size,
)
from repro.scale import (
    SegmentGrid,
    SegmentedIndex,
    SegmentedStreamingIndex,
    build_segmented_index,
    canonicalize_batch,
    dispatch_count,
    merge_fold_cache_size,
    worklist_capacity,
)
from repro.search import export_device_graph
from repro.stream.index import CompactionPolicy

RELATION_NAMES = sorted(RELATIONS)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _intervals(rng, n, T=100.0):
    s = rng.uniform(0, T, n)
    return s, s + rng.uniform(0, 0.3 * T, n)


def _check_router_complete(relname, seed, cells_per_axis, nq=16, n=160):
    """Core completeness check shared by the seeded sweep and the
    hypothesis property test."""
    rng = np.random.default_rng(seed)
    rel = get_relation(relname)
    s, t = _intervals(rng, n)
    X, Y = rel.transform_data(s, t)
    space = DominanceSpace.build(X, Y)
    grid = SegmentGrid.from_space(space, cells_per_axis)
    xr, yr = space.ranks()
    cell = grid.assign_ranks(xr, yr)
    # value-space assignment must agree with rank-space on on-grid points
    np.testing.assert_array_equal(grid.assign_values(X, Y), cell)

    sq, tq = _intervals(rng, nq)
    x_q, y_q = rel.query_map(sq, tq)
    a, c, valid = canonicalize_batch(space, x_q, y_q)
    route_r = grid.route_ranks(a, c, valid)
    route_v = grid.route_values(x_q, y_q, valid)
    for b in range(nq):
        m = np.asarray(rel.valid_mask(s, t, sq[b], tq[b]))
        vids = np.flatnonzero(m)
        if not valid[b]:
            # canonical state missing => valid set provably empty
            assert vids.size == 0, (relname, seed, b)
            assert not route_r[b].any() and not route_v[b].any()
            continue
        for i in vids:
            assert route_r[b, cell[i]], (
                f"{relname} seed={seed} q={b}: valid object {i} "
                f"(cell {cell[i]}) not rank-routed")
            assert route_v[b, cell[i]], (
                f"{relname} seed={seed} q={b}: valid object {i} "
                f"(cell {cell[i]}) not value-routed")


# --- satellite: router completeness (seeded sweep, runs everywhere) -----------


@pytest.mark.parametrize("relname", RELATION_NAMES)
def test_router_completeness_all_relations_seeded(relname):
    for seed in range(4):
        for g in (2, 3, 5):
            _check_router_complete(relname, seed, g)


def test_router_rejects_invalid_rows():
    rng = np.random.default_rng(0)
    rel = get_relation("containment")
    s, t = _intervals(rng, 50)
    space = DominanceSpace.from_intervals(rel, s, t)
    grid = SegmentGrid.from_space(space, 3)
    # query interval far past every datum => canonicalization fails
    x_q, y_q = rel.query_map(np.asarray([1e9]), np.asarray([2e9]))
    a, c, valid = canonicalize_batch(space, x_q, y_q)
    assert not valid[0]
    assert not grid.route_ranks(a, c, valid).any()
    assert not grid.route_values(x_q, y_q, valid).any()


# --- satellite: router completeness (hypothesis property sweep) ---------------


try:
    import hypothesis  # noqa: F401

    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False


if _HAVE_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(
        relname=st.sampled_from(RELATION_NAMES),
        seed=st.integers(0, 10_000),
        g=st.integers(2, 6),
    )
    def test_router_completeness_property(relname, seed, g):
        _check_router_complete(relname, seed, g, nq=8, n=80)

else:

    def test_router_completeness_property():
        pytest.skip("hypothesis not installed")


# --- shared segmented index (module scope amortizes the build) ----------------


@pytest.fixture(scope="module")
def seg_env():
    n, d = 1500, 8
    vecs, s, t = make_dataset(n, d, seed=7)
    idx = build_segmented_index(
        vecs, s, t, "overlap", cells_per_axis=3, M=8, Z=32, K_p=4,
        quantize_int8=True,
    )
    qv = make_queries_vectors(24, d, seed=11)
    qs = ground_truth(
        generate_queries(qv, s, t, "overlap", 0.08, k=10, seed=3), vecs, s, t)
    return dict(vecs=vecs, s=s, t=t, idx=idx, qs=qs)


def test_segmented_builds_real_segments(seg_env):
    idx = seg_env["idx"]
    assert idx.num_segments >= 2
    sizes = idx.segment_sizes()
    assert int(sizes.sum()) == idx.n
    # disjoint, exhaustive membership
    allids = np.concatenate([seg.ids for seg in idx.segments])
    np.testing.assert_array_equal(np.sort(allids), np.arange(idx.n))
    assert idx.quantized and all(seg.dg.vec_q is not None
                                 for seg in idx.segments)


def test_refined_route_keeps_every_valid_objects_segment(seg_env):
    """The hi>0 histogram refinement must stay recall-safe end to end."""
    idx, qs = seg_env["idx"], seg_env["qs"]
    s, t = seg_env["s"], seg_env["t"]
    rel = idx.relation
    cell_of = {int(g): si for si, seg in enumerate(idx.segments)
               for g in seg.ids}
    _, _, route = idx.search(qs.vectors, qs.s_q, qs.t_q, k=10,
                             return_route=True)
    seg_of = np.empty(idx.n, dtype=np.int64)
    for si, seg in enumerate(idx.segments):
        seg_of[seg.ids] = si
    for b in range(qs.nq):
        m = np.asarray(rel.valid_mask(s, t, qs.s_q[b], qs.t_q[b]))
        for i in np.flatnonzero(m):
            assert route[b, seg_of[i]], (b, i)
    assert cell_of  # sanity: membership map non-trivial


def test_segmented_recall_matches_monolithic(seg_env):
    """The n=100k benchmark gate in miniature: segmented recall within
    0.5 pt of the monolithic index at the same beam."""
    vecs, s, t = seg_env["vecs"], seg_env["s"], seg_env["t"]
    idx, qs = seg_env["idx"], seg_env["qs"]
    ids, d = idx.search(qs.vectors, qs.s_q, qs.t_q, k=10, beam=64)
    seg_recall = recall_at_k(ids, qs)

    g, _ = build_udg_batched(vecs, s, t, "overlap", M=8, Z=32, K_p=4)
    dg = export_device_graph(g)
    mono_ids, _ = execute_batch(dg, qs.vectors, qs.s_q, qs.t_q,
                                k=10, beam=64)
    mono_recall = recall_at_k(np.asarray(mono_ids), qs)
    assert seg_recall >= mono_recall - 0.005, (seg_recall, mono_recall)
    assert seg_recall >= 0.9

    # every returned id must satisfy the predicate
    rel = idx.relation
    for b in range(qs.nq):
        m = np.asarray(rel.valid_mask(s, t, qs.s_q[b], qs.t_q[b]))
        assert all(m[j] for j in ids[b] if j >= 0), b

    # rerank distances are exact f32 distances
    for b in range(qs.nq):
        for col, j in enumerate(ids[b]):
            if j < 0:
                continue
            ref = np.float32(np.sum(
                (vecs[j] - qs.vectors[b]) ** 2, dtype=np.float32))
            assert np.isclose(d[b, col], ref, rtol=1e-5), (b, col)


# --- satellite: int8 + rerank parity across all five relations ----------------


@pytest.mark.parametrize("relname", RELATION_NAMES)
def test_int8_rerank_parity_per_relation(relname):
    n, d = 700, 8
    vecs = make_vectors(n, d, seed=13)
    # wide intervals keep every relation feasible (query_within_data needs
    # data intervals long enough to contain a query interval)
    s, t = _intervals(np.random.default_rng(13), n)
    idx = build_segmented_index(
        vecs, s, t, relname, cells_per_axis=2, M=8, Z=32, K_p=4,
        quantize_int8=True,
    )
    qv = make_queries_vectors(12, d, seed=5)
    qs = ground_truth(
        generate_queries(qv, s, t, relname, 0.1, k=10, seed=9), vecs, s, t)
    ids, _ = idx.search(qs.vectors, qs.s_q, qs.t_q, k=10, beam=64)
    r = recall_at_k(ids, qs)
    assert r >= 0.9, (relname, r)


def test_int8_rerank_tie_rule_duplicate_vectors():
    """Duplicate vectors => equal exact distances => ties break toward the
    smaller id (the ground-truth ``np.lexsort((ids, d))`` rule)."""
    rng = np.random.default_rng(21)
    n, d = 400, 6
    vecs = make_vectors(n, d, seed=2)
    # plant 4 exact duplicates of one row
    dup = [37, 120, 233, 301]
    vecs[dup] = vecs[17]
    s, t = _intervals(rng, n)
    # give the planted rows wide intervals so they are valid for the query
    s[[17] + dup] = 10.0
    t[[17] + dup] = 90.0
    idx = build_segmented_index(vecs, s, t, "overlap", cells_per_axis=2,
                                M=8, Z=32, K_p=4, quantize_int8=True)
    q = vecs[17].copy()
    ids, dist = idx.search(q[None], np.asarray([20.0]), np.asarray([80.0]),
                           k=8, beam=96, fetch_k=32)
    zero = ids[0][np.asarray(dist[0]) == 0.0]
    expect = np.sort(np.asarray([17] + dup))
    np.testing.assert_array_equal(zero, expect)
    # equal-distance block is sorted ascending by id (lexsort tie rule)
    assert np.all(np.diff(zero) > 0)


def test_all_invalid_query_returns_empty(seg_env):
    idx = seg_env["idx"]
    q = make_queries_vectors(3, seg_env["vecs"].shape[1], seed=99)
    # intervals past every datum: no valid object for any relation state
    sq = np.full(3, 1e9)
    tq = np.full(3, 2e9)
    ids, d, route = idx.search(q, sq, tq, k=5, return_route=True)
    assert not route.any()
    assert np.all(ids == -1)
    assert np.all(np.isinf(d))


# --- satellite: one compiled program across mixed segment counts --------------


def test_no_recompile_across_segment_mixes(seg_env):
    """Mixed routed-segment counts must reuse the SAME compiled executor and
    merge-fold programs (jit-cache idiom from test_planner.py). Distinct
    k/beam from every other test so the first search compiles exactly one
    new variant of each. ``scheduler=False`` pins the legacy per-segment
    loop — the parity oracle keeps its own no-recompile guarantee."""
    idx, qs = seg_env["idx"], seg_env["qs"]
    B = 8
    qv = qs.vectors[:B]

    exec0 = planned_exec_cache_size()
    fold0 = merge_fold_cache_size()
    # mix 1: normal queries (route to several segments each)
    idx.search(qv, qs.s_q[:B], qs.t_q[:B], k=7, beam=48, scheduler=False)
    exec1 = planned_exec_cache_size()
    fold1 = merge_fold_cache_size()
    assert exec1 - exec0 == 1, (exec0, exec1)
    assert fold1 - fold0 == 1, (fold0, fold1)

    # mix 2: narrow queries (tiny dominance rectangle -> few segments);
    # mix 3: maximal queries (route everywhere). Same shapes, no recompile.
    s, t = seg_env["s"], seg_env["t"]
    narrow_s = np.full(B, float(np.median(s)))
    narrow_t = narrow_s + 0.5
    wide_s = np.full(B, float(s.min()))
    wide_t = np.full(B, float(t.max()))
    _, _, r_narrow = idx.search(qv, narrow_s, narrow_t, k=7, beam=48,
                                return_route=True, scheduler=False)
    _, _, r_wide = idx.search(qv, wide_s, wide_t, k=7, beam=48,
                              return_route=True, scheduler=False)
    # the wide mix routes every (query, segment) pair; the narrow mix is a
    # (possibly strict) subset — both reuse the warm programs
    assert r_wide.all()
    assert r_wide.sum() >= r_narrow.sum()
    assert planned_exec_cache_size() == exec1
    assert merge_fold_cache_size() == fold1


# --- tentpole: worklist scheduler — one dispatch, bit-identical results -------


def test_worklist_single_dispatch_bit_parity(seg_env):
    """The scheduler must return byte-for-byte what the per-segment loop
    returns (ids AND distances, with and without the rerank tail) while
    issuing ONE device dispatch for the whole routed mix instead of one
    per routed segment."""
    idx, qs = seg_env["idx"], seg_env["qs"]
    for rerank in (False, True):
        d0 = dispatch_count()
        out_s = idx.search(qs.vectors, qs.s_q, qs.t_q, k=10, beam=64,
                           rerank=rerank, return_route=True, scheduler=True)
        d1 = dispatch_count()
        out_l = idx.search(qs.vectors, qs.s_q, qs.t_q, k=10, beam=64,
                           rerank=rerank, return_route=True, scheduler=False)
        d2 = dispatch_count()
        np.testing.assert_array_equal(out_s[0], out_l[0])
        np.testing.assert_array_equal(out_s[1], out_l[1])
        np.testing.assert_array_equal(out_s[2], out_l[2])
        route = out_s[2]
        n_routed = int(route.any(axis=0).sum())
        assert n_routed >= 2  # the mix is non-trivial
        assert d1 - d0 == 1, (d0, d1)
        assert d2 - d1 == n_routed, (d1, d2, n_routed)


@pytest.mark.parametrize("relname", RELATION_NAMES)
def test_worklist_bit_parity_all_relations(relname):
    """Scheduler vs loop parity under every relation mapping (distinct
    dominance-space shapes route distinct segment mixes)."""
    n, d = 700, 8
    vecs = make_vectors(n, d, seed=13)
    s, t = _intervals(np.random.default_rng(13), n)
    idx = build_segmented_index(
        vecs, s, t, relname, cells_per_axis=2, M=8, Z=32, K_p=4,
        quantize_int8=True,
    )
    qv = make_queries_vectors(12, d, seed=5)
    qs = ground_truth(
        generate_queries(qv, s, t, relname, 0.1, k=10, seed=9), vecs, s, t)
    a = idx.search(qs.vectors, qs.s_q, qs.t_q, k=10, beam=64,
                   rerank=False, scheduler=True)
    b = idx.search(qs.vectors, qs.s_q, qs.t_q, k=10, beam=64,
                   rerank=False, scheduler=False)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


@pytest.mark.parametrize("plan", ["graph", "wide", "brute"])
@pytest.mark.parametrize("fused", [True, False])
def test_worklist_plan_mode_parity(seg_env, plan, fused):
    """Forced plan modes (incl. the ragged brute-list path, which the
    scheduler pads to ONE worklist-wide power-of-two capacity) and both
    label layouts stay bit-identical to the loop."""
    idx, qs = seg_env["idx"], seg_env["qs"]
    a = idx.search(qs.vectors, qs.s_q, qs.t_q, k=6, beam=32, plan=plan,
                   fused=fused, rerank=False, scheduler=True)
    b = idx.search(qs.vectors, qs.s_q, qs.t_q, k=6, beam=32, plan=plan,
                   fused=fused, rerank=False, scheduler=False)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


def test_worklist_bucket_no_recompile(seg_env):
    """Routed-mix changes land in a small closed set of quarter-octave
    worklist buckets: after warming each mix's bucket once, re-running
    EVERY mix adds zero compiled variants of ``worklist_exec_core``."""
    idx, qs = seg_env["idx"], seg_env["qs"]
    s, t = seg_env["s"], seg_env["t"]
    B = 8
    qv = qs.vectors[:B]
    narrow_s = np.full(B, float(np.median(s)))
    narrow_t = narrow_s + 0.5
    wide_s = np.full(B, float(s.min()))
    wide_t = np.full(B, float(t.max()))
    mixes = [
        (qs.s_q[:B], qs.t_q[:B]),   # normal: several segments per query
        (narrow_s, narrow_t),       # narrow: few (query, segment) pairs
        (wide_s, wide_t),           # maximal: every pair routed
    ]
    for sq, tq in mixes:            # warm each mix's bucket
        idx.search(qv, sq, tq, k=9, beam=40)
    warm = worklist_exec_cache_size()
    for sq, tq in mixes:
        idx.search(qv, sq, tq, k=9, beam=40)
    assert worklist_exec_cache_size() == warm


def test_worklist_capacity_buckets():
    # quarter-octave ladder: pow2 plus the 1.25/1.5/1.75 steps
    assert [worklist_capacity(w) for w in (0, 1, 7, 8, 9, 11, 39, 64, 65)] \
        == [8, 8, 8, 8, 10, 12, 40, 64, 80]
    for w in (1, 5, 8, 13, 39, 100, 1000):
        cap = worklist_capacity(w)
        assert cap >= max(w, 8)
        assert cap < 2 * max(w, 8)      # waste strictly under 2x
        assert cap <= 1.25 * max(w, 8) or cap == 8  # <= 25% padding
        # cap is pow2 or pow2 * {1.25, 1.5, 1.75}
        base = 1 << (cap.bit_length() - 1)
        assert cap * 4 % base == 0


def test_empty_worklist_no_dispatch(seg_env):
    """An all-invalid batch produces an empty worklist: the scheduler must
    return the padded empty result WITHOUT touching the device."""
    idx = seg_env["idx"]
    q = make_queries_vectors(4, seg_env["vecs"].shape[1], seed=77)
    sq = np.full(4, 1e9)
    tq = np.full(4, 2e9)
    d0 = dispatch_count()
    ids, d, st = idx.search(q, sq, tq, k=5, scheduler=True, stats=True)
    assert dispatch_count() == d0
    assert np.all(ids == -1)
    assert np.all(np.isinf(d))
    # zero stats, field-identical to the loop path's empty case
    _, _, st_l = idx.search(q, sq, tq, k=5, scheduler=False, stats=True)
    for name in st._fields:
        np.testing.assert_array_equal(
            getattr(st, name), getattr(st_l, name), err_msg=name)


def test_worklist_stats_parity(seg_env):
    """SearchStats out of the scheduler's one dispatch (scatter-added over
    the worklist) must equal the loop's ``combine_stats`` fold field by
    field — the counters are per-query trajectory sums, and the
    trajectory sets are identical."""
    idx, qs = seg_env["idx"], seg_env["qs"]
    # plan="graph" guarantees every routed pair actually traverses (the
    # auto planner may legally brute the whole batch, where counters are
    # all-zero by contract — that case is still compared, via "auto")
    for plan, check_nonzero in (("graph", True), ("auto", False)):
        *_, st_s = idx.search(qs.vectors, qs.s_q, qs.t_q, k=10, beam=64,
                              plan=plan, scheduler=True, stats=True)
        *_, st_l = idx.search(qs.vectors, qs.s_q, qs.t_q, k=10, beam=64,
                              plan=plan, scheduler=False, stats=True)
        if check_nonzero:
            assert int(np.sum(st_s.cand_total)) > 0
        for name in st_s._fields:
            np.testing.assert_array_equal(
                getattr(st_s, name), getattr(st_l, name), err_msg=name)


# --- satellite: byte accounting -----------------------------------------------


def test_nbytes_accounting_monolithic_and_segmented(seg_env):
    idx = seg_env["idx"]
    comp = idx.nbytes_by_component()
    assert sum(comp.values()) == idx.nbytes()
    assert comp["router"] == idx.grid.nbytes() > 0

    # packed labels: exactly 8 bytes/edge slot in every segment
    assert idx.packed
    for seg in idx.segments:
        dg = seg.dg
        assert dg.plabels is not None
        assert dg.plabels.nbytes == idx.node_capacity * idx.edge_capacity * 8

    # int8 residency: 1 byte/dim resident rows, f32 copies 4x larger
    assert comp["vec_q"] * 4 == comp["vectors"]
    assert comp["scales"] == comp["norms"]

    # monolithic DeviceGraph obeys the same sum rule
    vecs, s, t = seg_env["vecs"], seg_env["s"], seg_env["t"]
    g, _ = build_udg_batched(vecs[:300], s[:300], t[:300], "overlap",
                             M=8, Z=32, K_p=4)
    dg = export_device_graph(g, quantize_int8=True)
    assert sum(dg.nbytes_by_component().values()) == dg.nbytes()


# --- satellite: seed-sweep determinism ----------------------------------------


def test_segmented_build_and_search_deterministic():
    n, d = 800, 8
    vecs, s, t = make_dataset(n, d, seed=31)
    qv = make_queries_vectors(8, d, seed=4)
    sq, tq = _intervals(np.random.default_rng(6), 8)

    runs = []
    for _ in range(2):
        idx = build_segmented_index(vecs, s, t, "overlap",
                                    cells_per_axis=3, M=8, Z=32, K_p=4)
        ids, dist = idx.search(qv, sq, tq, k=10, beam=48)
        runs.append((idx, ids, dist))
    a, b = runs
    np.testing.assert_array_equal(a[1], b[1])
    np.testing.assert_array_equal(a[2], b[2])
    assert a[0].num_segments == b[0].num_segments
    for sa, sb in zip(a[0].segments, b[0].segments):
        np.testing.assert_array_equal(sa.ids, sb.ids)
        np.testing.assert_array_equal(np.asarray(sa.dg.nbr),
                                      np.asarray(sb.dg.nbr))
        np.testing.assert_array_equal(np.asarray(sa.dg.vec_q),
                                      np.asarray(sb.dg.vec_q))


# --- satellite: streaming segment-local epoch swap ----------------------------


def test_streaming_segment_local_epoch_swap():
    rng = np.random.default_rng(44)
    d = 6
    # construction-time space just seeds the grid; inserts may be off-grid
    s0, t0 = _intervals(rng, 300)
    rel = get_relation("overlap")
    space = DominanceSpace.from_intervals(rel, s0, t0)
    grid = SegmentGrid.from_space(space, 2)
    idx = SegmentedStreamingIndex(
        d, "overlap", grid,
        node_capacity=512, delta_capacity=128, edge_capacity=64,
        M=6, Z=24, K_p=4,
        policy=CompactionPolicy(max_delta_fraction=0.05, min_mutations=16),
        build_kwargs=dict(M=6, Z=24, K_p=4),
    )
    vecs = make_vectors(300, d, seed=8)
    idx.insert_batch(vecs, s0, t0)
    assert idx.live_count == 300
    # hot cells overflowed their delta and flush-compacted DURING insert;
    # cold cells must still be at epoch 0 — swaps are segment-local
    flushed = idx.epochs()
    assert any(e >= 1 for e in flushed)
    assert any(e == 0 for e in flushed)
    assert idx.swap_counts == flushed

    # now trip the policy in exactly one hot segment via deletes
    hot = int(np.argmax(flushed))
    victims = idx.subs[hot].live_ids()[:24]
    for e in victims:
        assert idx.delete(int(e))
    before = idx.epochs()
    reports = idx.maybe_compact()
    after = idx.epochs()
    assert hot in reports, (reports, before)
    for ci in range(idx.num_segments):
        if ci in reports:
            assert after[ci] == before[ci] + 1, ci
        else:
            # segment-local: untouched segments keep their epoch
            assert after[ci] == before[ci], ci
    assert idx.swap_counts == after

    # search parity vs brute oracle over live objects
    qv = make_queries_vectors(6, d, seed=12)
    sq, tq = _intervals(rng, 6)
    ids, dist = idx.search(qv, sq, tq, k=5, beam=48)
    # external id -> insertion order: ids were handed out round-robin per
    # cell, so recover (vec, s, t) via the per-sub id namespace
    ext_meta = {}
    cell = grid.assign_values(*rel.transform_data(s0, t0))
    counters = [0] * idx.num_segments
    for i in range(300):
        ci = int(cell[i])
        ext = ci + counters[ci] * idx.num_segments
        counters[ci] += 1
        ext_meta[ext] = i
    dead = {ext_meta[int(e)] for e in victims}
    for b in range(6):
        m = np.asarray(rel.valid_mask(s0, t0, sq[b], tq[b]))
        vids = np.array([i for i in np.flatnonzero(m) if i not in dead])
        for e in ids[b]:
            if e >= 0:
                assert ext_meta[int(e)] in vids, (b, e)
        if vids.size:
            dd = np.sum((vecs[vids] - qv[b]) ** 2, axis=1)
            best = vids[np.argmin(dd)]
            got = {ext_meta[int(e)] for e in ids[b] if e >= 0}
            assert best in got, b


# --- satellite: segment-local stack patch on streaming epoch swap -------------


def test_streaming_stack_patch_is_segment_local():
    """``on_epoch_swap`` must restage ONLY the swapped segment's slice of
    the flat device stack: every other part keeps the very same device
    buffers (object identity), and the flat concat is invalidated so the
    next read sees the new epoch."""
    rng = np.random.default_rng(44)
    d = 6
    s0, t0 = _intervals(rng, 300)
    rel = get_relation("overlap")
    space = DominanceSpace.from_intervals(rel, s0, t0)
    grid = SegmentGrid.from_space(space, 2)
    idx = SegmentedStreamingIndex(
        d, "overlap", grid,
        node_capacity=512, delta_capacity=128, edge_capacity=64,
        M=6, Z=24, K_p=4,
        policy=CompactionPolicy(max_delta_fraction=0.05, min_mutations=16),
        build_kwargs=dict(M=6, Z=24, K_p=4),
    )
    vecs = make_vectors(300, d, seed=8)
    idx.insert_batch(vecs, s0, t0)

    stack = idx.device_stack()
    assert stack.num_segments == idx.num_segments
    before = [stack.part(ci) for ci in range(stack.num_segments)]
    flat0 = stack.flat("nbr")  # materialize the concat cache

    # trip the policy in exactly one hot segment via deletes
    hot = int(np.argmax(idx.epochs()))
    for e in idx.subs[hot].live_ids()[:24]:
        assert idx.delete(int(e))
    reports = idx.maybe_compact()
    assert hot in reports

    after = [stack.part(ci) for ci in range(stack.num_segments)]
    for ci in range(stack.num_segments):
        for key in ("table", "nbr", "labels", "gids"):
            same = after[ci][key] is before[ci][key]
            if ci in reports:
                assert not same, (ci, key)
            else:
                assert same, (ci, key)
    # the flat concat restaged and reflects the swapped segment's new
    # live-id table (the deleted rows left the gids slice)
    flat1 = stack.flat("nbr")
    assert flat1 is not flat0
    ncap = stack.node_capacity
    gids = np.asarray(stack.flat("gids"))
    live = set(idx.subs[hot].live_ids().tolist())
    seg_gids = gids[hot * ncap : (hot + 1) * ncap]
    assert set(seg_gids[seg_gids >= 0].tolist()) == live


# --- satellite: sharded serving device bundle derives from the stack ----------


def test_sharded_device_bundle_reuses_segment_stack():
    """``segments_to_sharded_index`` primes the sharded device cache from
    the scheduler's flat ``SegmentStack`` (un-offsetting the adjacency on
    device) — the derived bundle must equal the stacked host arrays
    exactly."""
    vecs, s, t = make_dataset(600, 8, seed=17)
    idx = build_segmented_index(
        vecs, s, t, "overlap", cells_per_axis=2, M=8, Z=32, K_p=4,
        quantize_int8=False,
    )
    from repro.serve.distributed import segments_to_sharded_index

    sharded, id_map = segments_to_sharded_index(idx)
    assert sharded._cache is not None  # primed at build, not first use
    from repro.launch.mesh import make_host_mesh

    dev = sharded.device(make_host_mesh(1))
    np.testing.assert_array_equal(np.asarray(dev["nbr"]), sharded.nbr)
    np.testing.assert_array_equal(np.asarray(dev["labels"]),
                                  sharded.kernel_layout("labels"))
    np.testing.assert_array_equal(np.asarray(dev["vectors"]),
                                  sharded.kernel_layout("vectors"))
    # id_map agrees with the stack's device-resident global-id table
    gids = np.asarray(idx.device_stack().flat("gids")).reshape(
        sharded.num_shards, sharded.n_local)
    np.testing.assert_array_equal(gids, id_map.astype(np.int32))


# --- satellite: segment-sharded serving (multi-host-device, subprocess) -------


def _run(code: str, devices: int = 8, timeout: int = 600) -> str:
    env = dict(
        os.environ,
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        PYTHONPATH=os.path.join(REPO, "src"),
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=timeout,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


@pytest.mark.slow
def test_segments_sharded_across_devices():
    out = _run(
        """
import numpy as np
from repro.core import get_relation
from repro.data import make_dataset, make_queries_vectors, generate_queries, ground_truth, recall_at_k
from repro.launch.mesh import make_host_mesh
from repro.scale import build_segmented_index
from repro.serve.distributed import segments_to_sharded_index, serve_batch

vecs, s, t = make_dataset(1024, 8, seed=0)
idx = build_segmented_index(vecs, s, t, "overlap", cells_per_axis=2, M=8, Z=32, K_p=4, quantize_int8=False)
sh, id_map = segments_to_sharded_index(idx)
assert sh.num_shards == idx.num_segments == 4, sh.num_shards
mesh = make_host_mesh(model_parallel=sh.num_shards)
qv = make_queries_vectors(12, 8, seed=1)
qs = ground_truth(generate_queries(qv, s, t, "overlap", 0.08, k=10, seed=2), vecs, s, t)
ids, d = serve_batch(sh, mesh, qs.vectors, qs.s_q, qs.t_q, k=10, beam=64, id_map=id_map)
rel = get_relation("overlap")
for i in range(qs.nq):
    m = rel.valid_mask(s, t, qs.s_q[i], qs.t_q[i])
    assert all(m[j] for j in ids[i] if j >= 0), i
r = recall_at_k(np.asarray(ids), qs)
assert r >= 0.9, r
print("segment-sharded recall", round(r, 3))
""")
    assert "segment-sharded recall" in out
