"""Packed-metadata layout: pack/unpack round-trip, rank-width guard and
fallback, packed-vs-int32 search parity across all five relations, and the
memoized DeviceGraph.device() bundle."""
import numpy as np
import pytest

from repro.core import build_index
from repro.core.predicates import RELATIONS
from repro.data import generate_queries, ground_truth, make_queries_vectors, recall_at_k
from repro.search import (
    batched_udg_search,
    export_device_graph,
    pack_labels,
    unpack_labels,
)
from repro.search import device_graph as dgmod


def test_pack_unpack_bitwise_round_trip():
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 1 << 16, size=(37, 11, 4)).astype(np.int32)
    # force the 16-bit corners into the sample
    labels[0, 0] = [0, 0, 0, 0]
    labels[0, 1] = [(1 << 16) - 1] * 4
    packed = pack_labels(labels)
    assert packed.dtype == np.uint32 and packed.shape == (37, 11, 2)
    np.testing.assert_array_equal(unpack_labels(packed), labels)
    # words are exactly lo | hi << 16
    assert packed[0, 1, 0] == 0xFFFFFFFF and packed[0, 0, 0] == 0


def test_pack_labels_rejects_out_of_range():
    bad_hi = np.zeros((2, 2, 4), np.int32)
    bad_hi[0, 0, 1] = 1 << 16
    with pytest.raises(ValueError):
        pack_labels(bad_hi)
    bad_neg = np.zeros((2, 2, 4), np.int32)
    bad_neg[1, 1, 2] = -1
    with pytest.raises(ValueError):
        pack_labels(bad_neg)
    with pytest.raises(ValueError):
        pack_labels(np.zeros((2, 3), np.int32))       # wrong trailing dim
    with pytest.raises(ValueError):
        unpack_labels(np.zeros((2, 4), np.uint32))    # wrong trailing dim


@pytest.fixture(scope="module")
def tiny_index(tiny_dataset):
    vecs, s, t = tiny_dataset
    g, et, _ = build_index(vecs, s, t, "overlap", M=6, Z=24, K_p=4)
    return vecs, s, t, g, et


def test_export_packs_by_default(tiny_index):
    vecs, s, t, g, et = tiny_index
    dg = export_device_graph(g, et)
    assert dg.plabels is not None and dg.labels is None
    assert dg.plabels.dtype == np.uint32
    # labels_i32 unpacks (and caches) the oracle layout bitwise
    lab = dg.labels_i32()
    np.testing.assert_array_equal(pack_labels(lab), dg.plabels)
    assert dg.labels_i32() is lab  # cached
    # itemized nbytes counts the packed at-rest layout: 8 bytes/edge
    comp = dg.nbytes_by_component()
    assert comp["labels"] == dg.plabels.nbytes
    assert sum(comp.values()) == dg.nbytes()


def test_rank_width_guard_fallback_round_trip(tiny_index, monkeypatch):
    """A grid over the 16-bit budget must warn + fall back to the int32
    layout (packed_labels=None), raise under packed_labels=True, and the
    fallback index must serve identically to the packed one."""
    vecs, s, t, g, et = tiny_index
    packed_dg = export_device_graph(g, et)
    monkeypatch.setattr(dgmod, "RANK_LIMIT", 4)   # grid no longer "fits"
    with pytest.warns(RuntimeWarning, match="16-bit rank budget"):
        dg = export_device_graph(g, et)
    assert dg.plabels is None and dg.labels is not None
    with pytest.raises(ValueError, match="16-bit rank budget"):
        export_device_graph(g, et, packed_labels=True)
    monkeypatch.undo()
    # fallback layout round-trip: same rectangles, same search results
    np.testing.assert_array_equal(dg.labels, packed_dg.labels_i32())
    qv = make_queries_vectors(8, vecs.shape[1], seed=5)
    qs = generate_queries(qv, s, t, "overlap", 0.1, k=5, seed=6)
    a, da = batched_udg_search(dg, qs.vectors, qs.s_q, qs.t_q,
                               k=5, beam=24, use_ref=True)
    b, db = batched_udg_search(packed_dg, qs.vectors, qs.s_q, qs.t_q,
                               k=5, beam=24, use_ref=True)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(da, db)
    with pytest.raises(ValueError, match="no packed labels"):
        batched_udg_search(dg, qs.vectors, qs.s_q, qs.t_q,
                           k=5, beam=24, use_ref=True, packed=True)


def test_forced_int32_export(tiny_index):
    vecs, s, t, g, et = tiny_index
    dg = export_device_graph(g, et, packed_labels=False)
    assert dg.plabels is None and dg.labels is not None
    assert dg.labels_i32() is dg.labels


@pytest.mark.parametrize("relation", sorted(RELATIONS))
def test_packed_vs_int32_parity_all_relations(tiny_dataset, relation):
    """The packed superkernel path returns bit-identical ids/distances to
    both the int32 fused oracle and the unfused baseline, per relation."""
    if relation == "query_within_data":
        # feasible only with uncapped data intervals at low selectivity
        from repro.data import make_dataset

        vecs, s, t = make_dataset(120, 8, distribution="uncapped", seed=3)
        sigma = 0.05
    else:
        vecs, s, t = tiny_dataset
        sigma = 0.15
    g, et, _ = build_index(vecs, s, t, relation, M=6, Z=24, K_p=4)
    dg = export_device_graph(g, et)
    qv = make_queries_vectors(10, vecs.shape[1], seed=11)
    qs = ground_truth(
        generate_queries(qv, s, t, relation, sigma, k=5, seed=12), vecs, s, t
    )
    packed, d_p = batched_udg_search(dg, qs.vectors, qs.s_q, qs.t_q,
                                     k=5, beam=24, use_ref=True)
    int32, d_i = batched_udg_search(dg, qs.vectors, qs.s_q, qs.t_q,
                                    k=5, beam=24, use_ref=True, packed=False)
    unfused, d_u = batched_udg_search(dg, qs.vectors, qs.s_q, qs.t_q,
                                      k=5, beam=24, use_ref=True, fused=False)
    np.testing.assert_array_equal(packed, int32)
    np.testing.assert_array_equal(packed, unfused)
    np.testing.assert_array_equal(d_p, d_i)
    assert recall_at_k(packed, qs) == recall_at_k(unfused, qs)


@pytest.mark.parametrize("E", [1, 48, 64, 96, 128, 130])
def test_label_rows_round_trip(E):
    """``label_rows`` lays word 0 then word 1 of every edge into one
    ``⌈2E/128⌉·128``-lane row, identically from numpy and jnp, and
    ``label_words`` reads the words back bitwise."""
    import jax.numpy as jnp

    from repro.kernels.layout import label_row_bytes, label_rows, label_words

    rng = np.random.default_rng(E)
    p = rng.integers(0, 2 ** 32, size=(5, E, 2), dtype=np.uint64).astype(
        np.uint32)
    rows = label_rows(p)
    W = -(-2 * E // 128) * 128
    assert rows.shape == (5, 1, W) and rows.dtype == np.int32
    assert label_row_bytes(E) == W * 4
    np.testing.assert_array_equal(rows[:, 0, :E], p[..., 0].view(np.int32))
    np.testing.assert_array_equal(rows[:, 0, E:2 * E],
                                  p[..., 1].view(np.int32))
    assert not rows[:, 0, 2 * E:].any()
    np.testing.assert_array_equal(np.asarray(label_rows(jnp.asarray(p))), rows)
    np.testing.assert_array_equal(
        np.asarray(label_words(jnp.asarray(rows), jnp.arange(5), E)), p)


def test_device_bundle_memoized(tiny_index):
    """The bundle holds the kernels' row layouts: ``[n, 1, ⌈2E/128⌉·128]``
    int32 label rows and ``[n, 1, W]`` vector rows, each decoding bitwise to the
    export's host arrays."""
    import jax.numpy as jnp

    from repro.kernels.layout import gather_vectors, label_words

    vecs, s, t, g, et = tiny_index
    dg = export_device_graph(g, et)
    dev = dg.device()
    assert dg.device() is dev                      # memoized
    E = dg.max_degree
    assert dev.packed
    assert dev.labels.shape == (dg.n, 1, -(-2 * E // 128) * 128)
    assert dev.table.shape == (dg.n, 1, 128)
    assert dev.scales is None
    np.testing.assert_array_equal(np.asarray(dev.nbr), dg.nbr)
    ids = jnp.arange(dg.n)
    np.testing.assert_array_equal(
        np.asarray(label_words(dev.labels, ids, E)), dg.plabels)
    np.testing.assert_array_equal(
        np.asarray(gather_vectors(dev.table, ids, dg.vectors.shape[1])),
        dg.vectors)
    lab32 = dg.device_labels_i32()
    assert dg.device_labels_i32() is lab32         # memoized
    np.testing.assert_array_equal(np.asarray(lab32), dg.labels_i32())
    dg.invalidate_device()
    assert dg.device() is not dev                  # rebuilt after invalidate


def test_executor_unfused_oracle_on_packed_export(tiny_index):
    """The planned executor's fused=False parity baseline must serve from
    a packed export (int32 labels derived on demand), matching the fused
    packed path bitwise."""
    from repro.exec import execute_batch

    vecs, s, t, g, et = tiny_index
    dg = export_device_graph(g, et)
    qv = make_queries_vectors(6, vecs.shape[1], seed=21)
    qs = generate_queries(qv, s, t, "overlap", 0.1, k=5, seed=22)
    a, da = execute_batch(dg, qs.vectors, qs.s_q, qs.t_q, k=5, beam=24,
                          use_ref=True, fused=False, plan="graph")
    b, db = execute_batch(dg, qs.vectors, qs.s_q, qs.t_q, k=5, beam=24,
                          use_ref=True, fused=True, plan="graph")
    np.testing.assert_array_equal(a, b)
    # ids bit-equal; distances differ only by the cached-norm float residue
    # (‖c‖²−2qc+‖q‖² vs diff-square — same tolerance as test_streaming)
    np.testing.assert_allclose(da, db, atol=1e-4)


def test_unpack_labels_device_matches_host():
    import jax.numpy as jnp

    from repro.search.device_graph import unpack_labels_device

    rng = np.random.default_rng(7)
    labels = rng.integers(0, 1 << 16, size=(9, 5, 4)).astype(np.int32)
    packed = pack_labels(labels)
    np.testing.assert_array_equal(
        np.asarray(unpack_labels_device(jnp.asarray(packed))), labels)


def test_device_bundle_int8_storage(tiny_index):
    """int8 storage rides as int32 words, four values per word; the words
    decode bitwise to the export's int8 rows."""
    import jax.numpy as jnp

    from repro.kernels.layout import gather_vectors

    vecs, s, t, g, et = tiny_index
    dg = export_device_graph(g, et, quantize_int8=True)
    dev = dg.device()
    assert dev.table.dtype == np.int32 and dev.scales is not None
    got = gather_vectors(dev.table, jnp.arange(dg.n), dg.vec_q.shape[1])
    np.testing.assert_array_equal(np.asarray(got), dg.vec_q)
