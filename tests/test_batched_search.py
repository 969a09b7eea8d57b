"""Batched jittable search: parity with the host reference + edge cases."""
import numpy as np
import pytest

from repro.core import build_index, get_relation
from repro.data import generate_queries, ground_truth, make_dataset, recall_at_k
from repro.search import batched_udg_search, export_device_graph, prepare_states


@pytest.fixture(scope="module")
def setup(small_dataset, query_vectors):
    vecs, s, t = small_dataset
    g, et, _ = build_index(vecs, s, t, "overlap", M=10, Z=48, K_p=8)
    dg = export_device_graph(g, et)
    return vecs, s, t, g, dg


@pytest.mark.parametrize("sigma", [0.01, 0.1])
def test_batched_recall_and_validity(setup, query_vectors, sigma):
    vecs, s, t, g, dg = setup
    qs = ground_truth(
        generate_queries(query_vectors, s, t, "overlap", sigma, k=10, seed=8),
        vecs, s, t,
    )
    ids, dists = batched_udg_search(dg, qs.vectors, qs.s_q, qs.t_q,
                                    k=10, beam=64, use_ref=True)
    rel = get_relation("overlap")
    for i in range(qs.nq):
        mask = rel.valid_mask(s, t, qs.s_q[i], qs.t_q[i])
        for j in ids[i]:
            if j >= 0:
                assert mask[j]
    assert recall_at_k(ids, qs) >= 0.95


def test_batched_with_pallas_kernel_matches_ref_path(setup, query_vectors):
    vecs, s, t, g, dg = setup
    qs = generate_queries(query_vectors[:6], s, t, "overlap", 0.05, k=5, seed=9)
    a, _ = batched_udg_search(dg, qs.vectors, qs.s_q, qs.t_q, k=5, beam=32,
                              use_ref=True)
    b, _ = batched_udg_search(dg, qs.vectors, qs.s_q, qs.t_q, k=5, beam=32,
                              use_ref=False)  # interpret-mode Pallas
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("expand", [1, 2])
def test_idle_and_padding_rows_change_no_served_result(setup, query_vectors,
                                                       expand):
    """A batch whose rows go idle (empty valid sets from the start, early
    convergence later) and whose adjacency carries -1 padding: on the
    kernel path, where such rows are dead tiles and skipped fetches, each
    live query gets bitwise the ids and distances it gets in a batch of
    live queries alone, the same ids as the reference path and its
    distances to float rounding; the idle rows come back empty."""
    vecs, s, t, g, dg = setup
    assert (dg.nbr < 0).any()
    qs = generate_queries(query_vectors[:5], s, t, "overlap", 0.05, k=5,
                          seed=12)
    # rows 0, 3 and 6 are sentinels: s_q > t_q, no valid object
    live = np.array([False, True, True, False, True, True, False, True])
    q = np.zeros((8, vecs.shape[1]), np.float32)
    s_q = np.full(8, 100.0)
    t_q = np.full(8, -100.0)
    q[live], s_q[live], t_q[live] = qs.vectors, qs.s_q, qs.t_q

    def serve(q, s_q, t_q, use_ref):
        return batched_udg_search(dg, q, s_q, t_q, k=5, beam=16,
                                  expand=expand, use_ref=use_ref)

    ids, d = serve(q, s_q, t_q, False)
    ids_alone, d_alone = serve(qs.vectors, qs.s_q, qs.t_q, False)
    assert (ids_alone >= 0).any()
    np.testing.assert_array_equal(ids[live], ids_alone)
    np.testing.assert_array_equal(d[live], d_alone)
    assert np.all(ids[~live] == -1) and np.all(np.isinf(d[~live]))
    ids_ref, d_ref = serve(q, s_q, t_q, True)
    np.testing.assert_array_equal(ids, ids_ref)
    np.testing.assert_allclose(d, d_ref, rtol=1e-5)


@pytest.fixture(scope="module")
def setup_containment(small_dataset):
    vecs, s, t = small_dataset
    g, et, _ = build_index(vecs, s, t, "containment", M=10, Z=48, K_p=8)
    return vecs, s, t, export_device_graph(g, et)


@pytest.mark.parametrize("relation", ["overlap", "containment"])
def test_fused_path_parity_and_recall(setup, setup_containment, query_vectors,
                                      relation):
    """The gather-fused loop (in-kernel HBM gather, cached norms, bit-packed
    visited; n=1500 exercises the bitmap tail word) returns the same ids as
    the unfused baseline and the pallas kernel matches its jnp oracle
    bit-for-bit, on both workload relations."""
    if relation == "overlap":
        vecs, s, t, g, dg = setup
    else:
        vecs, s, t, dg = setup_containment
    qs = ground_truth(
        generate_queries(query_vectors, s, t, relation, 0.1, k=10, seed=21),
        vecs, s, t,
    )
    unfused, _ = batched_udg_search(dg, qs.vectors, qs.s_q, qs.t_q,
                                    k=10, beam=64, use_ref=True, fused=False)
    fused_ref, _ = batched_udg_search(dg, qs.vectors, qs.s_q, qs.t_q,
                                      k=10, beam=64, use_ref=True, fused=True)
    fused_pl, _ = batched_udg_search(dg, qs.vectors, qs.s_q, qs.t_q,
                                     k=10, beam=64, use_ref=False, fused=True)
    np.testing.assert_array_equal(fused_ref, fused_pl)
    assert recall_at_k(fused_ref, qs) == recall_at_k(unfused, qs)
    assert recall_at_k(fused_ref, qs) >= 0.95


def test_multi_expand_recall(setup, query_vectors):
    """expand=M>1 pops the best M unexpanded beam entries per iteration —
    fewer while-loop trips, same quality."""
    vecs, s, t, g, dg = setup
    qs = ground_truth(
        generate_queries(query_vectors, s, t, "overlap", 0.1, k=10, seed=22),
        vecs, s, t,
    )
    base, _ = batched_udg_search(dg, qs.vectors, qs.s_q, qs.t_q,
                                 k=10, beam=64, use_ref=True)
    for m in (2, 4):
        ids, _ = batched_udg_search(dg, qs.vectors, qs.s_q, qs.t_q,
                                    k=10, beam=64, use_ref=True, expand=m)
        assert recall_at_k(ids, qs) >= recall_at_k(base, qs) - 1e-9
    with pytest.raises(ValueError):
        batched_udg_search(dg, qs.vectors, qs.s_q, qs.t_q, k=10, beam=64,
                           use_ref=True, fused=False, expand=2)
    for bad in (0, -1, 65):   # out of [1, beam]
        with pytest.raises(ValueError):
            batched_udg_search(dg, qs.vectors, qs.s_q, qs.t_q, k=10, beam=64,
                               use_ref=True, expand=bad)


def test_int8_storage_end_to_end(setup, query_vectors):
    """quantize_int8 export carries vec_q/scales/dequantized norms and the
    public entry point serves from them (satellite: int8 actually reachable)."""
    vecs, s, t, g, dg = setup
    dg8 = export_device_graph(g, None, quantize_int8=True)
    assert dg8.vec_q is not None and dg8.vec_q.dtype == np.int8
    assert dg8.scales is not None and dg8.norms is not None
    qs = ground_truth(
        generate_queries(query_vectors, s, t, "overlap", 0.05, k=10, seed=33),
        vecs, s, t,
    )
    a, _ = batched_udg_search(dg8, qs.vectors, qs.s_q, qs.t_q,
                              k=10, beam=64, use_ref=True)
    b, _ = batched_udg_search(dg8, qs.vectors, qs.s_q, qs.t_q,
                              k=10, beam=64, use_ref=False)
    np.testing.assert_array_equal(a, b)
    assert recall_at_k(a, qs) >= 0.95


def test_empty_and_sentinel_queries(setup):
    vecs, s, t, g, dg = setup
    q = vecs[:3]
    # sentinel row: s_q > t_q -> no valid objects -> all -1
    s_q = np.array([s.min(), 50.0, 10.0])
    t_q = np.array([t.max(), 40.0, -5.0])  # rows 1,2 invalid intervals
    states, ep = prepare_states(dg, s_q, t_q)
    assert ep[0] >= 0
    ids, dists = batched_udg_search(dg, q, s_q, t_q, k=5, beam=16, use_ref=True)
    assert np.all(ids[2] == -1)


def test_prepare_states_matches_host_canonicalization(setup):
    vecs, s, t, g, dg = setup
    rng = np.random.default_rng(1)
    s_q = rng.uniform(s.min(), s.max(), 50)
    t_q = s_q + rng.uniform(0, (t - s).max() * 3, 50)
    states, ep = prepare_states(dg, s_q, t_q)
    for i in range(50):
        st = g.canonical_rank_state(float(s_q[i]), float(t_q[i]))
        if st is None:
            assert ep[i] == -1
        else:
            assert tuple(states[i]) == st


def test_device_graph_export_consistency(setup):
    vecs, s, t, g, dg = setup
    assert dg.nbr.shape[0] == g.n
    # default export bit-packs the rank rectangles (grid fits 16 bits);
    # labels_i32() is the unpacked view the parity-oracle paths use
    assert dg.plabels is not None and dg.plabels.dtype == np.uint32
    assert dg.plabels.shape == (g.n, dg.max_degree, 2)
    labels = dg.labels_i32()
    for u in (0, 5, 100):
        nbr, l, r, b, e = g.tuples(u)
        k = nbr.shape[0]
        np.testing.assert_array_equal(dg.nbr[u, :k], nbr)
        assert np.all(dg.nbr[u, k:] == -1)
        np.testing.assert_array_equal(labels[u, :k, 0], l)
        np.testing.assert_array_equal(labels[u, :k, 3], e)


def test_int8_search_path_recall(setup, query_vectors):
    """§Perf U3: int8-quantized database vectors keep full recall."""
    import jax.numpy as jnp
    from repro.data import generate_queries, ground_truth, recall_at_k
    from repro.kernels.int8dist import quantize_int8
    from repro.search.batched import _batched_search_core

    vecs, s, t, g, dg = setup
    qs = ground_truth(
        generate_queries(query_vectors, s, t, "overlap", 0.05, k=10, seed=33),
        vecs, s, t,
    )
    states, ep = prepare_states(dg, qs.s_q, qs.t_q)
    vq, sc = quantize_int8(jnp.asarray(dg.vectors))
    ids = _batched_search_core(
        vq, jnp.asarray(dg.nbr), jnp.asarray(dg.labels_i32()),
        jnp.asarray(qs.vectors), jnp.asarray(states), jnp.asarray(ep),
        k=10, beam=64, max_iters=128, use_ref=True, scales=sc,
    )[0]
    assert recall_at_k(np.asarray(ids), qs) >= 0.95
