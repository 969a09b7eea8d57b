"""Streaming subsystem: LSM-style online mutations over the UDG.

Covers the ISSUE-1 acceptance criteria:
  * after interleaved inserts/deletes (spanning several compactions), query
    recall on the streamed index is within 1% of a from-scratch UDG rebuilt
    on the same live set — for containment and overlap;
  * deletes never resurface: not from the delta tier, not from graph
    tombstones, and not across a compaction that races the delete;
  * epoch swap under concurrent queries: every query sees one consistent
    epoch (never a deleted id, never an unknown id) and the swap does not
    recompile the jitted serving step.
"""
import threading

import numpy as np
import pytest

from repro.core import EntryTable, build_udg, get_relation
from repro.data import make_dataset, make_queries_vectors
from repro.search import batched_udg_search, export_device_graph
from repro.serve import ShardedStreamingIndex, StreamingServer
from repro.stream import (
    CompactionPolicy,
    StreamingIndex,
    sort_key,
    streaming_search_cache_size,
)

DIM = 16
K = 10
BEAM = 48


def _workload(n=420, seed=0):
    return make_dataset(n, DIM, seed=seed)


def _queries(s, t, nq=16, seed=1):
    """Query vectors + interval predicates spanning narrow to broad."""
    rng = np.random.default_rng(seed)
    qv = make_queries_vectors(nq, DIM, seed=seed)
    lo = rng.uniform(s.min(), s.max(), size=nq)
    width = rng.uniform(0.05, 1.0, size=nq) * (t.max() - s.min())
    return qv, lo, np.minimum(lo + width, t.max() + 1.0)


def _brute_topk(qv, s_q, t_q, vecs, s, t, ext, relation, k=K):
    """Exact top-k external ids over a live set under the predicate."""
    rel = get_relation(relation)
    m = rel.valid_mask(s, t, s_q, t_q)
    if not m.any():
        return set()
    d = ((vecs[m] - qv) ** 2).sum(axis=1)
    return set(int(x) for x in ext[m][np.argsort(d)][:k])


def _recall(results, gts):
    hits = sum(len(set(map(int, r[r >= 0])) & gt) for r, gt in zip(results, gts))
    total = sum(len(gt) for gt in gts)
    return hits / max(total, 1)


def test_sort_key_is_monotone():
    rng = np.random.default_rng(0)
    v = np.concatenate(
        [rng.normal(scale=100.0, size=500), [0.0, -0.0, 1e-30, -1e-30, 1e30, -1e30]]
    ).astype(np.float32)
    k = sort_key(v)
    order_v = np.argsort(v, kind="stable")
    assert np.all(np.diff(k[order_v]) >= 0)
    assert sort_key(-0.0) == sort_key(0.0)


@pytest.mark.parametrize("relation", ["containment", "overlap"])
def test_streamed_recall_matches_rebuild_oracle(relation):
    vecs, s, t = _workload()
    n = vecs.shape[0]
    idx = StreamingIndex(
        DIM, relation, node_capacity=512, delta_capacity=96, edge_capacity=96,
        M=8, Z=32, policy=CompactionPolicy(max_delta_fraction=0.2, min_mutations=24),
    )
    # interleave: insert in chunks, delete stragglers, let the policy compact
    ext_of_row = {}
    deleted = set()
    rng = np.random.default_rng(7)
    for lo in range(0, n, 60):
        hi = min(lo + 60, n)
        for i in range(lo, hi):
            ext_of_row[i] = idx.insert(vecs[i], s[i], t[i])
        alive = [i for i in ext_of_row if i not in deleted]
        for i in rng.choice(alive, size=6, replace=False):
            assert idx.delete(ext_of_row[i])
            deleted.add(i)
        idx.maybe_compact()
    assert idx.epoch >= 1  # at least one compaction actually happened
    assert idx.live_count == n - len(deleted)

    live_rows = np.array(sorted(set(range(n)) - deleted))
    lv, ls, lt = vecs[live_rows], s[live_rows], t[live_rows]
    lext = np.array([ext_of_row[i] for i in live_rows])

    qv, s_q, t_q = _queries(s, t)
    gts = [
        _brute_topk(qv[i], s_q[i], t_q[i], lv, ls, lt, lext, relation)
        for i in range(len(qv))
    ]
    ids, _ = idx.search(qv, s_q, t_q, k=K, beam=BEAM)
    r_stream = _recall(ids, gts)

    # from-scratch oracle: one static UDG over exactly the live set
    g, _ = build_udg(lv, ls, lt, relation, M=8, Z=32)
    dg = export_device_graph(g, EntryTable(g))
    oid, _ = batched_udg_search(dg, qv, s_q, t_q, k=K, beam=BEAM, use_ref=True)
    gts_local = [
        _brute_topk(qv[i], s_q[i], t_q[i], lv, ls, lt, np.arange(len(live_rows)),
                    relation)
        for i in range(len(qv))
    ]
    r_rebuild = _recall(oid, gts_local)

    assert r_stream >= r_rebuild - 0.01, (r_stream, r_rebuild)


def test_bulk_load_is_one_rebuild():
    """``bulk_load`` puts a batch past the delta capacity into ONE new epoch
    (the delta tier stays empty), keeps what was live before, and hands
    out ids that the index then serves and deletes like any other."""
    vecs, s, t = _workload(n=300, seed=4)
    idx = StreamingIndex(
        DIM, "containment", node_capacity=512, delta_capacity=32,
        edge_capacity=96, M=8, Z=32,
    )
    early = idx.insert_batch(vecs[:20], s[:20], t[:20])
    ext = idx.bulk_load(vecs[20:], s[20:], t[20:])
    assert idx.epoch == 1 and idx.live_count == 300
    assert idx.delta_fraction == 0.0
    assert len(set(ext) | set(early)) == 300
    qv = vecs[20:24]
    broad = np.full(4, float(s.min()) - 1.0), np.full(4, float(t.max()) + 1.0)
    ids, _ = idx.search(qv, *broad, k=1, beam=BEAM)
    np.testing.assert_array_equal(ids[:, 0], ext[:4])   # each finds itself
    assert idx.delete(int(ext[0]))
    ids, _ = idx.search(qv, *broad, k=K, beam=BEAM)
    assert int(ext[0]) not in set(ids.ravel().tolist())


def test_bulk_load_refuses_a_wal(tmp_path):
    from repro.stream.wal import WriteAheadLog

    vecs, s, t = _workload(n=40, seed=5)
    idx = StreamingIndex(DIM, "containment", node_capacity=64,
                         delta_capacity=16, edge_capacity=32, M=8, Z=32,
                         wal=WriteAheadLog(str(tmp_path / "wal")))
    with pytest.raises(RuntimeError, match="not logged"):
        idx.bulk_load(vecs, s, t)
    assert idx.live_count == 0 and idx.epoch == 0


def test_deletes_never_resurface_across_compaction():
    vecs, s, t = _workload(n=300, seed=2)
    idx = StreamingIndex(
        DIM, "containment", node_capacity=512, delta_capacity=128,
        edge_capacity=96, M=8, Z=32,
    )
    ext = idx.insert_batch(vecs[:200], s[:200], t[:200])
    qv = make_queries_vectors(4, DIM, seed=3)
    broad = (float(s.min()) - 1.0, float(t.max()) + 1.0)  # everything valid

    def returned_ids():
        ids, _ = idx.search(
            qv, np.full(4, broad[0]), np.full(4, broad[1]), k=K, beam=BEAM
        )
        return set(int(x) for x in ids.ravel() if x >= 0)

    # 1. delete straight from the delta tier
    dead = set(int(e) for e in ext[:30])
    for e in sorted(dead):
        assert idx.delete(e)
    assert not (returned_ids() & dead)
    # 2. compact: tombstoned objects must not be rebuilt into the new epoch
    idx.compact()
    assert not (returned_ids() & dead)
    # 3. delete from the compacted graph tier (soft delete)
    dead2 = set(int(e) for e in ext[30:60])
    for e in sorted(dead2):
        assert idx.delete(e)
    assert not (returned_ids() & (dead | dead2))
    # 4. a delete racing an in-flight compaction is replayed at swap
    job = idx.begin_compaction()
    racing = set(int(e) for e in ext[60:80])
    for e in sorted(racing):
        assert idx.delete(e)
    late = idx.insert_batch(vecs[200:220], s[200:220], t[200:220])
    idx.build_epoch(job)
    idx.finish_compaction(job)
    got = returned_ids()
    assert not (got & (dead | dead2 | racing))
    # post-snapshot inserts survived the swap: still live, and querying an
    # object's own vector under the broad predicate returns it at ~distance 0
    # (the gather-fused path scores via cached norms, ‖c‖²−2q·c+‖q‖², which
    # leaves float-rounding residue where the diff-square form gave exact 0)
    live = set(int(e) for e in idx.live_ids())
    assert set(int(e) for e in late) <= live
    for j in (0, 7, 19):
        ids, d = idx.search(vecs[200 + j], broad[0], broad[1], k=K, beam=BEAM)
        assert int(ids[0]) == int(late[j]) and d[0] <= 1e-4
    # 5. double delete reports False, unknown id reports False
    assert not idx.delete(int(ext[0]))
    assert not idx.delete(10**9)


def test_epoch_swap_under_concurrent_queries_no_recompile():
    vecs, s, t = _workload(n=360, seed=4)
    idx = StreamingIndex(
        DIM, "overlap", node_capacity=512, delta_capacity=128, edge_capacity=96,
        M=8, Z=32, policy=CompactionPolicy(max_delta_fraction=0.05, min_mutations=8),
    )
    srv = StreamingServer(idx, batch_size=4, k=K, beam=BEAM)
    ext = idx.insert_batch(vecs[:240], s[:240], t[:240])
    idx.compact()
    deleted = set(int(e) for e in ext[:40])
    for e in sorted(deleted):
        idx.delete(e)
    for i in range(240, 300):
        idx.insert(vecs[i], s[i], t[i])

    qv = make_queries_vectors(4, DIM, seed=5)
    broad_s = np.full(4, float(s.min()) - 1.0)
    broad_t = np.full(4, float(t.max()) + 1.0)
    cache_before = streaming_search_cache_size()
    epoch_before = idx.epoch

    errors: list = []
    results: list = []
    stop = threading.Event()

    def query_loop():
        try:
            while not stop.is_set():
                ids, _ = idx.search(qv, broad_s, broad_t, k=K, beam=BEAM)
                results.append(ids.copy())
        except BaseException as exc:  # surfaced below
            errors.append(exc)

    qt = threading.Thread(target=query_loop)
    qt.start()
    try:
        assert srv.maybe_compact_async()  # policy fires: 60 delta + 40 dead
        srv.join_compaction()
        # a few more queries strictly after the swap
        for _ in range(3):
            ids, _ = idx.search(qv, broad_s, broad_t, k=K, beam=BEAM)
            results.append(ids.copy())
    finally:
        stop.set()
        qt.join()
    assert not errors, errors
    assert idx.epoch == epoch_before + 1
    # one static shape across the swap: zero new compilations
    assert streaming_search_cache_size() == cache_before
    # every concurrently-issued query saw one consistent epoch: deleted ids
    # never appear, and all ids belong to the (unchanged) live set
    live = set(int(e) for e in idx.live_ids())
    for ids in results:
        got = set(int(x) for x in ids.ravel() if x >= 0)
        assert not (got & deleted)
        assert got <= live


def test_sharded_streaming_round_trip():
    vecs, s, t = _workload(n=240, seed=6)
    sidx = ShardedStreamingIndex(
        DIM, "containment", 2, node_capacity=256, delta_capacity=64,
        edge_capacity=96, M=8, Z=32,
    )
    ext = sidx.insert_batch(vecs, s, t)
    assert len(set(map(int, ext))) == len(ext)  # globally unique ids
    deleted = set(int(e) for e in ext[::5])
    for e in sorted(deleted):
        assert sidx.delete(e)
    while sidx.maybe_compact_shards() >= 0:
        pass
    live_rows = np.array([i for i in range(len(ext)) if int(ext[i]) not in deleted])
    lext = np.array([int(ext[i]) for i in live_rows])
    qv, s_q, t_q = _queries(s, t, nq=8, seed=7)
    ids, d = sidx.search(qv, s_q, t_q, k=K, beam=BEAM)
    gts = [
        _brute_topk(qv[i], s_q[i], t_q[i], vecs[live_rows], s[live_rows],
                    t[live_rows], lext, "containment")
        for i in range(len(qv))
    ]
    assert _recall(ids, gts) >= 0.95
    for row in ids:
        got = set(int(x) for x in row if x >= 0)
        assert not (got & deleted)
