"""The benchmark's copies of the generators and its reference, against the
program's originals, on the CPU at a small size."""
import numpy as np
import pytest

from benchmarks.chip import data, reference
from repro.data import synthetic, workloads


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_vectors_are_the_originals(seed):
    x, q = data.make_vectors_and_queries(500, 64, 24, clusters=16,
                                         spread=0.35, seed=seed)
    np.testing.assert_array_equal(x, synthetic.make_vectors(500, 24,
                                                            seed=seed))
    assert q.shape == (64, 24) and q.dtype == np.float32


def test_queries_come_from_the_data_centres():
    x, q = data.make_vectors_and_queries(4000, 400, 64, clusters=16,
                                         spread=0.35, seed=3)
    centers = np.random.default_rng(3).normal(size=(16, 64))
    nearest = ((q[:, None] - centers[None]) ** 2).sum(-1).min(1)
    assert np.max(nearest) < 64 * 0.35 ** 2 * 2


@pytest.mark.parametrize("law", ["uniform", "uncapped"])
def test_intervals_are_the_originals(law):
    s, t = data.make_intervals(3000, law, seed=11)
    s0, t0 = synthetic.make_intervals(3000, distribution=law, seed=11)
    np.testing.assert_array_equal(s, s0)
    np.testing.assert_array_equal(t, t0)


@pytest.mark.parametrize("relation,law,sel", [
    ("containment", "uniform", 0.01), ("containment", "uniform", 0.1),
    ("overlap", "uncapped", 0.05), ("overlap", "uncapped", 0.5)])
def test_query_intervals_are_the_originals(relation, law, sel):
    s, t = data.make_intervals(4000, law, seed=2)
    qv = np.zeros((40, 8), np.float32)
    s_q, t_q, cnt = data.query_intervals(s, t, relation, sel, 40, k=10,
                                         seed=9)
    qs = workloads.generate_queries(qv, s, t, relation, sel, k=10, seed=9)
    np.testing.assert_array_equal(s_q, qs.s_q)
    np.testing.assert_array_equal(t_q, qs.t_q)
    np.testing.assert_array_equal(cnt, np.round(qs.achieved_selectivity
                                                * 4000).astype(np.int64))


def test_pool_deals_the_buckets_evenly():
    s, t = data.make_intervals(2000, "uniform", seed=4)
    qv = np.zeros((103, 4), np.float32)
    pool = data.make_pool(qv, s, t, "containment", [0.01, 0.05, 0.1, 0.5],
                          k=10, seed=4)
    _, counts = np.unique(pool.bucket, return_counts=True)
    assert counts.max() - counts.min() <= 1
    assert np.all(pool.valid >= 10)


@pytest.mark.parametrize("relation", sorted(data.PREDICATES))
def test_predicates_are_the_programs(relation):
    from repro.core.predicates import get_relation

    s, t = data.make_intervals(500, "uncapped", seed=1)
    rel = get_relation(relation)
    for sq, tq in [(100.0, 400.0), (0.0, 1000.0), (500.0, 500.0)]:
        np.testing.assert_array_equal(data.predicate(relation)(s, t, sq, tq),
                                      rel.valid_mask(s, t, sq, tq))


@pytest.mark.parametrize("relation,law", [("containment", "uniform"),
                                          ("overlap", "uncapped")])
def test_reference_is_the_ground_truth(relation, law):
    n, dim, k = 3000, 16, 10
    x, qv = data.make_vectors_and_queries(n, 300, dim, clusters=16,
                                          spread=0.35, seed=6)
    s, t = data.make_intervals(n, law, seed=6)
    pool = data.make_pool(qv, s, t, relation, [0.01, 0.1, 0.5], k=k, seed=6)
    ref = reference.Reference(x, s, t, relation, block=64)
    got = ref.topk(pool.q, pool.s_q, pool.t_q, k)
    qs = workloads.QuerySet(relation, pool.q, pool.s_q, pool.t_q, 0.0,
                            np.zeros(len(pool)), k)
    want = workloads.ground_truth(qs, x, s, t)
    # sets of ids agree (order can differ only among exact ties)
    same = [set(a) == set(b) for a, b in zip(got.ids, want.gt_ids)]
    assert np.mean(same) == 1.0
    np.testing.assert_allclose(np.sort(ref.pair_dist(pool.q, got.ids), 1),
                               want.gt_dists, rtol=1e-5)


def test_compare_reads_each_fault():
    n, dim, k = 1500, 16, 10
    x, qv = data.make_vectors_and_queries(n, 64, dim, clusters=8,
                                          spread=0.35, seed=8)
    s, t = data.make_intervals(n, "uniform", seed=8)
    pool = data.make_pool(qv, s, t, "containment", [0.05, 0.5], k=k, seed=8)
    ref = reference.Reference(x, s, t, "containment", block=32)
    truth = ref.topk(pool.q, pool.s_q, pool.t_q, k)
    idx = np.arange(len(pool))
    d_exact = ref.pair_dist(pool.q, truth.ids)

    def verdict(ids, d, missing=0):
        return reference.compare(ref, truth, idx, pool.q, pool.s_q, pool.t_q,
                                 ids, d, missing=missing, n=n)

    v = verdict(truth.ids, d_exact)
    assert (v.missing, v.bad) == (0, 0) and v.dist_err < 1e-6
    assert np.all(v.recall == 1.0)
    ids = truth.ids.copy()
    ids[0, 1] = ids[0, 0]                                   # repeated id
    assert verdict(ids, d_exact).bad == 1
    d = d_exact.copy()
    d[1] = d[1][::-1]                                       # out of order
    assert verdict(truth.ids, d).bad == 1
    d = d_exact.copy()
    d[2, 3] *= 1.001                                        # altered distance
    assert verdict(truth.ids, d).dist_err > 5e-4
    ids = truth.ids.copy()
    ids[3, 0] = int(np.flatnonzero(~ref.valid(np.arange(n)[None],
                                              pool.s_q[3:4], pool.t_q[3:4])[0])[0])
    assert verdict(ids, d_exact).bad == 1                   # fails the predicate
    assert verdict(truth.ids, d_exact, missing=2).missing == 2
