"""Observability layer: metrics registry + export, device-side traversal
counters (pinned against a Python re-execution oracle), the stats=False
jaxpr guard, and no-recompile across epoch swaps / plan mixes with stats on.
"""
import json
import math
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_index
from repro.exec import PlannerConfig, QueryPlan, execute_batch
from repro.obs import (
    COUNT_BUCKETS,
    MetricsRegistry,
    SearchStats,
    capture_trace,
    combine_stats,
    get_registry,
    json_snapshot,
    parse_prometheus_text,
    per_query_dict,
    record_search_stats,
    start_metrics_server,
    to_json,
    to_prometheus_text,
    trace_span,
    write_json,
    write_prometheus,
)
from repro.search import batched_udg_search, export_device_graph, prepare_states
from repro.obs.stats import LOOP_TOTALS
from repro.search.batched import _batched_search_core


# --- registry -----------------------------------------------------------------


def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("x_total", "help")
    c.inc()
    c.inc(2.5)
    c.inc(1, plan="GRAPH")
    assert c.value() == 3.5
    assert c.value(plan="GRAPH") == 1.0
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(7)
    g.dec(2)
    assert g.value() == 5.0
    # get-or-create is idempotent; type clash raises
    assert reg.counter("x_total") is c
    with pytest.raises(TypeError):
        reg.gauge("x_total")


def test_inc_counters_one_locked_update():
    """Several counters, labelled or new, in one acquisition of the
    registry's lock (the series updates inside re-enter it)."""
    reg = MetricsRegistry()
    reg.counter("a_total").inc(1, plan="GRAPH")
    taken = []
    lock = reg._lock

    class Counted:
        def __enter__(self):
            taken.append(1)
            return lock.__enter__()

        def __exit__(self, *exc):
            return lock.__exit__(*exc)

    reg._lock = Counted()
    reg.inc_counters([("a_total", "", 2.0, {"plan": "GRAPH"}),
                      ("a_total", "", 1.0, {"plan": "GRAPH_WIDE"})])
    assert len(taken) == 1
    reg._lock = lock
    reg.inc_counters([("b_total", "created here", 3.0, {})])
    assert reg.counter("a_total").value(plan="GRAPH") == 3.0
    assert reg.counter("a_total").value(plan="GRAPH_WIDE") == 1.0
    assert reg.counter("b_total").value() == 3.0
    reg.histogram("h_seconds")
    with pytest.raises(TypeError):
        reg.inc_counters([("h_seconds", "", 1.0, {})])


def test_histogram_percentiles_exact_on_single_value():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
    h.observe(0.42)
    s = h.summary()
    # min/max clamping: one observation reports itself at every quantile
    assert s["count"] == 1 and s["p50"] == pytest.approx(0.42)
    assert s["p99"] == pytest.approx(0.42)


def test_histogram_percentiles_interpolate():
    reg = MetricsRegistry()
    h = reg.histogram("v", buckets=tuple(float(x) for x in range(1, 101)))
    h.observe_many(float(x) for x in range(1, 101))   # 1..100, one per bucket
    assert h.percentile(0.5) == pytest.approx(50.0, abs=1.0)
    assert h.percentile(0.9) == pytest.approx(90.0, abs=1.0)
    assert h.percentile(0.99) == pytest.approx(99.0, abs=1.0)
    assert math.isnan(h.percentile(0.5, missing="yes"))


def test_histogram_out_of_range_lands_in_inf_bucket():
    reg = MetricsRegistry()
    h = reg.histogram("v", buckets=(1.0, 2.0))
    h.observe(5.0)
    text = to_prometheus_text(reg)
    samples = parse_prometheus_text(text)
    assert samples['v_bucket{le="2"}'] == 0
    assert samples['v_bucket{le="+Inf"}'] == 1
    assert samples["v_count"] == 1


# --- export -------------------------------------------------------------------


def _tiny_registry():
    reg = MetricsRegistry()
    reg.counter("repro_queries_total", "q").inc(5)
    reg.gauge("repro_depth").set(2)
    h = reg.histogram("repro_lat_seconds", buckets=(0.01, 0.1, 1.0))
    h.observe_many([0.005, 0.05, 0.5, 0.05])
    reg.counter("labeled_total").inc(3, plan="GRAPH", shard="0")
    return reg


def test_prometheus_text_round_trip():
    reg = _tiny_registry()
    text = to_prometheus_text(reg)
    assert "# TYPE repro_lat_seconds histogram" in text
    samples = parse_prometheus_text(text)
    assert samples["repro_queries_total"] == 5
    assert samples["repro_depth"] == 2
    # cumulative buckets + sum/count
    assert samples['repro_lat_seconds_bucket{le="0.1"}'] == 3
    assert samples['repro_lat_seconds_bucket{le="+Inf"}'] == 4
    assert samples["repro_lat_seconds_count"] == 4
    assert samples['labeled_total{plan="GRAPH",shard="0"}'] == 3


def test_json_snapshot_has_summaries():
    reg = _tiny_registry()
    snap = json.loads(json_snapshot(reg))
    fams = {f["name"]: f for f in snap["metrics"]}
    hist = fams["repro_lat_seconds"]["samples"][0]
    assert hist["count"] == 4
    assert not math.isnan(hist["p50"])
    assert to_json(reg)["metrics"]


def test_file_writers(tmp_path):
    reg = _tiny_registry()
    p1 = write_prometheus(tmp_path / "metrics.prom", reg)
    p2 = write_json(tmp_path / "metrics.json", reg)
    assert parse_prometheus_text(p1.read_text())["repro_queries_total"] == 5
    assert json.loads(p2.read_text())["metrics"]


def test_http_metrics_server():
    reg = _tiny_registry()
    with start_metrics_server(reg) as srv:
        text = urllib.request.urlopen(srv.url, timeout=5).read().decode()
        assert parse_prometheus_text(text)["repro_queries_total"] == 5
        js = urllib.request.urlopen(
            srv.url + ".json", timeout=5
        ).read().decode()
        assert json.loads(js)["metrics"]


def test_trace_span_records_duration():
    reg = MetricsRegistry()
    with trace_span("unit_test_span", reg):
        pass
    h = reg.histogram("repro_span_seconds")
    assert h.summary(span="unit_test_span")["count"] == 1


def test_capture_trace_degrades_gracefully(tmp_path):
    reg = MetricsRegistry()
    with capture_trace(tmp_path / "trace", reg) as started:
        assert started in (True, False)
    assert reg.histogram("repro_span_seconds").summary(
        span="capture_trace"
    )["count"] == 1


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_capture_trace_fails_loudly_only_on_tpu(tmp_path, monkeypatch, backend):
    """A trace that cannot start degrades to timing off the TPU; asked for
    on the TPU it raises — no caller mistakes an empty trace for a
    measurement."""
    def refuse(logdir):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    reg = MetricsRegistry()
    if backend == "tpu":
        with pytest.raises(RuntimeError, match="profiler unavailable"):
            with capture_trace(tmp_path / "trace", reg):
                pass
    else:
        with capture_trace(tmp_path / "trace", reg) as started:
            assert started is False
        assert reg.histogram("repro_span_seconds").summary(
            span="capture_trace")["count"] == 1


# --- device-side traversal counters ------------------------------------------


@pytest.fixture(scope="module")
def obs_setup(tiny_dataset):
    vecs, s, t = tiny_dataset
    g, et, _ = build_index(vecs, s, t, "overlap", M=6, Z=24, K_p=4)
    dg = export_device_graph(g, et)
    return vecs, s, t, dg


def _oracle_stats(dg, q, s_q, t_q, *, beam, max_iters):
    """Sequential per-query re-execution of the lockstep beam search,
    counting with the documented semantics (expand=1); ``unvisited``
    counts the real candidates not visited before their iteration, the
    rows the packed gather fetches."""
    labels = dg.labels_i32()
    nbr = dg.nbr
    vecs = dg.vectors.astype(np.float64)
    states, ep = prepare_states(dg, s_q, t_q)
    B = q.shape[0]
    out = []
    for b in range(B):
        a, c = int(states[b, 0]), int(states[b, 1])
        st = dict(iters=0, expanded=0, cand_total=0, cand_valid=0, kept=0,
                  visited=0, beam_occupancy=0, hit_max_iters=False,
                  unvisited=0)
        if ep[b] < 0:
            out.append(st)
            continue
        qv = q[b].astype(np.float64)
        d0 = float(np.sum((qv - vecs[ep[b]]) ** 2))
        beam_list = [(d0, int(ep[b]), False)]   # (dist, id, expanded)
        visited = {int(ep[b])}
        it = 0
        while it < max_iters:
            unexp = [e for e in beam_list if not e[2]]
            if not unexp:
                break
            cur = min(unexp)[1]
            beam_list = [
                (d, i, True if i == cur else x) for d, i, x in beam_list
            ]
            st["iters"] += 1
            st["expanded"] += 1
            kept_ids = []
            for e in range(nbr.shape[1]):
                nb = int(nbr[cur, e])
                if nb < 0:
                    continue
                st["cand_total"] += 1
                st["unvisited"] += nb not in visited
                lo_x, hi_x, lo_y, hi_y = labels[cur, e]
                if not (lo_x <= a <= hi_x and lo_y <= c <= hi_y):
                    continue
                if nb in visited:
                    continue
                st["cand_valid"] += 1
                if nb not in kept_ids:
                    kept_ids.append(nb)
            st["kept"] += len(kept_ids)
            for nb in kept_ids:
                visited.add(nb)
                d = float(np.sum((qv - vecs[nb]) ** 2))
                beam_list.append((d, nb, False))
            beam_list = sorted(beam_list)[:beam]
            it += 1
        st["visited"] = len(visited)
        st["beam_occupancy"] = min(len(beam_list), beam)
        st["hit_max_iters"] = any(not e[2] for e in beam_list)
        out.append(st)
    return out


@pytest.mark.parametrize("fused", [True, False])
def test_stats_exact_vs_python_oracle(obs_setup, fused):
    """Every counter the device emits equals a sequential Python
    re-execution of the beam search, per query (expand=1: per-query
    lockstep trajectories are independent of the batch)."""
    vecs, s, t, dg = obs_setup
    rng = np.random.default_rng(11)
    B = 6
    q = rng.standard_normal((B, vecs.shape[1])).astype(np.float32)
    s_q = rng.uniform(s.min(), s.max(), B)
    t_q = s_q + rng.uniform(0.1, 0.9, B)
    beam, max_iters = 8, 12   # small cap so hit_max_iters fires for some row
    ids, d, st = batched_udg_search(
        dg, q, s_q, t_q, k=4, beam=beam, max_iters=max_iters,
        use_ref=True, fused=fused, stats=True,
    )
    oracle = _oracle_stats(dg, q, s_q, t_q, beam=beam, max_iters=max_iters)
    for b in range(B):
        for field in ("iters", "expanded", "cand_total", "cand_valid",
                      "kept", "visited", "beam_occupancy"):
            assert int(getattr(st, field)[b]) == oracle[b][field], (
                fused, b, field, oracle[b],
            )
        assert bool(st.hit_max_iters[b]) == oracle[b]["hit_max_iters"], b
        assert int(st.delta_valid[b]) == 0


def test_stats_results_identical_and_packed_parity(obs_setup):
    """stats=True changes no search result, and the packed superkernel path
    reports the same counters as the legacy fused layout."""
    vecs, s, t, dg = obs_setup
    rng = np.random.default_rng(3)
    q = rng.standard_normal((5, vecs.shape[1])).astype(np.float32)
    s_q = rng.uniform(s.min(), s.max(), 5)
    t_q = s_q + rng.uniform(0.2, 0.8, 5)
    ids0, d0 = batched_udg_search(dg, q, s_q, t_q, k=5, beam=16, use_ref=True)
    ids1, d1, st_packed = batched_udg_search(
        dg, q, s_q, t_q, k=5, beam=16, use_ref=True, stats=True,
    )
    np.testing.assert_array_equal(ids0, ids1)
    np.testing.assert_allclose(d0, d1, equal_nan=True)
    if dg.plabels is not None:
        _, _, st_legacy = batched_udg_search(
            dg, q, s_q, t_q, k=5, beam=16, use_ref=True, stats=True,
            packed=False,
        )
        for f in SearchStats._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(st_packed, f)),
                np.asarray(getattr(st_legacy, f)), err_msg=f,
            )


def test_no_entry_rows_contribute_exact_zeros(obs_setup):
    vecs, s, t, dg = obs_setup
    rng = np.random.default_rng(4)
    q = rng.standard_normal((3, vecs.shape[1])).astype(np.float32)
    # s_q > t_q => empty valid set => ep = -1 (the batcher's sentinel rows)
    s_q = np.full(3, 100.0)
    t_q = np.full(3, -100.0)
    _, _, st = batched_udg_search(
        dg, q, s_q, t_q, k=4, beam=8, use_ref=True, stats=True,
    )
    for f in ("iters", "expanded", "cand_total", "cand_valid", "kept",
              "visited", "beam_occupancy", "delta_valid"):
        assert np.all(np.asarray(getattr(st, f)) == 0), f
    assert not np.any(np.asarray(st.hit_max_iters))


def test_stats_false_jaxpr_has_no_stats_outputs(obs_setup):
    """The guard for 'stats=False carries only the fixed-size totals':
    ids, distances and the ``i32[5]`` loop totals, and nothing
    ``[B]``-per-query beyond them; stats=True appends exactly the
    per-query SearchStats leaves, each ``[B]``."""
    vecs, s, t, dg = obs_setup
    rng = np.random.default_rng(5)
    B, k = 4, 4
    q = rng.standard_normal((B, vecs.shape[1])).astype(np.float32)
    s_q = rng.uniform(s.min(), s.max(), B)
    t_q = s_q + 0.5
    states, ep = prepare_states(dg, s_q, t_q)
    dev = dg.device()
    labels = dg.serving_labels(fused=True)
    args = (dev.table, dev.nbr, labels, jnp.asarray(q),
            jnp.asarray(states), jnp.asarray(ep))

    def shapes(stats):
        jaxpr = jax.make_jaxpr(
            lambda *a: _batched_search_core(
                *a, k=k, beam=8, max_iters=37, use_ref=True,
                norms=dev.norms, stats=stats,
            )
        )(*args)
        return [(str(a.dtype), a.shape) for a in jaxpr.out_avals]

    off = shapes(False)
    assert off == [("int32", (B, k)), ("float32", (B, k)),
                   ("int32", (len(LOOP_TOTALS),))]
    on = shapes(True)
    assert on[:3] == off
    assert len(on) == 3 + len(SearchStats._fields)
    assert all(shape == (B,) for _, shape in on[3:])


def test_planned_exec_stats_rows(obs_setup):
    """Planner-routed stats: brute rows contribute exact zeros; each
    graph-planned row's counters equal the pure-graph run (masked rows do
    zero iterations, so plan-merge is addition)."""
    vecs, s, t, dg = obs_setup
    rng = np.random.default_rng(6)
    B = 8
    q = rng.standard_normal((B, vecs.shape[1])).astype(np.float32)
    s_q = rng.uniform(s.min(), s.max(), B)
    t_q = s_q + rng.uniform(0.2, 0.8, B)
    # default thresholds on the tiny graph: every valid set fits the brute
    # capacity, so all rows route BRUTE_VALID and traversal counters are 0
    ids, d, pb, st = execute_batch(
        dg, q, s_q, t_q, k=4, beam=16, use_ref=True, plan="auto",
        return_plans=True, stats=True,
    )
    brute_rows = pb.plans == int(QueryPlan.BRUTE_VALID)
    assert np.any(brute_rows)
    for f in ("iters", "expanded", "cand_total", "cand_valid", "kept",
              "visited", "beam_occupancy"):
        assert np.all(np.asarray(getattr(st, f))[brute_rows] == 0), f
    # squeeze the brute capacity so the same rows route GRAPH: their
    # counters must equal the pure-graph search row for row
    cfg = PlannerConfig(brute_max_valid=1, wide_max_fraction=0.0)
    ids2, d2, pb2, st2 = execute_batch(
        dg, q, s_q, t_q, k=4, beam=16, use_ref=True, plan="auto",
        config=cfg, return_plans=True, stats=True,
    )
    graph_rows = pb2.plans == int(QueryPlan.GRAPH)
    assert np.any(graph_rows)
    _, _, st_pure = batched_udg_search(
        dg, q, s_q, t_q, k=4, beam=16, use_ref=True, stats=True,
    )
    for f in ("iters", "expanded", "cand_total", "cand_valid", "kept",
              "visited", "beam_occupancy"):
        np.testing.assert_array_equal(
            np.asarray(getattr(st2, f))[graph_rows],
            np.asarray(getattr(st_pure, f))[graph_rows], err_msg=f,
        )


def test_combine_stats_adds_per_query_fields():
    """Two instantiations over disjoint rows merge field by field: counts
    add, the iteration-cap flag ORs, and every field stays ``[B]``."""
    a = SearchStats(*(jnp.arange(3, dtype=jnp.int32) for _ in range(7)),
                    jnp.array([False, True, False]),
                    jnp.ones(3, jnp.int32))
    b = SearchStats(*(jnp.full(3, 2, jnp.int32) for _ in range(7)),
                    jnp.array([False, False, True]),
                    jnp.zeros(3, jnp.int32))
    m = combine_stats(a, b)
    for f in ("iters", "expanded", "cand_total", "cand_valid", "kept",
              "visited", "beam_occupancy"):
        np.testing.assert_array_equal(np.asarray(getattr(m, f)), [2, 3, 4])
    np.testing.assert_array_equal(np.asarray(m.hit_max_iters),
                                  [False, True, True])
    np.testing.assert_array_equal(np.asarray(m.delta_valid), [1, 1, 1])
    d = per_query_dict(m)
    assert set(d) == set(SearchStats._fields)
    assert all(v.shape == (3,) and v.dtype == jnp.int32 for v in d.values())


def test_record_search_stats_folds_into_registry():
    reg = MetricsRegistry()
    st = {
        "iters": np.array([3, 5, 0, 9]),
        "expanded": np.array([3, 5, 0, 9]),
        "cand_total": np.array([30, 50, 0, 90]),
        "cand_valid": np.array([10, 25, 0, 90]),
        "kept": np.array([9, 20, 0, 80]),
        "visited": np.array([10, 21, 0, 81]),
        "beam_occupancy": np.array([8, 8, 0, 8]),
        "hit_max_iters": np.array([False, False, False, True]),
        "delta_valid": np.array([1, 0, 0, 2]),
    }
    # n_real=3 truncates the padded 4th row out of every series
    record_search_stats(st, registry=reg, n_real=3)
    assert reg.counter("repro_search_nodes_expanded_total").value() == 8
    assert reg.counter("repro_search_candidates_valid_total").value() == 35
    # the iteration and kept totals come from the always-on loop totals
    names = reg.names()
    assert "repro_search_iterations_total" not in names
    assert "repro_search_candidates_kept_total" not in names
    assert reg.counter("repro_search_queries_total").value() == 3
    term = reg.counter("repro_search_terminations_total")
    assert term.value(cause="beam_converged") == 2
    assert term.value(cause="no_entry") == 1
    assert term.value(cause="iteration_cap") == 0
    frac = reg.histogram("repro_search_valid_fraction")
    assert frac.summary()["count"] == 2   # rows with cand_total > 0
    assert reg.histogram(
        "repro_search_visited_per_query", buckets=COUNT_BUCKETS
    ).summary()["count"] == 3


def test_global_registry_resolution():
    reg = get_registry()
    assert get_registry() is reg


# --- no-recompile gates -------------------------------------------------------


def test_planned_stats_one_compile_across_plan_mixes(obs_setup):
    """stats=True planned execution stays one compiled program across
    batches with different plan mixes (the static shapes are (B, beam,
    max_iters) — data-dependent routing never re-traces)."""
    from repro.exec import planned_exec_cache_size

    vecs, s, t, dg = obs_setup
    rng = np.random.default_rng(7)
    B = 6
    q = rng.standard_normal((B, vecs.shape[1])).astype(np.float32)
    cfg = PlannerConfig(brute_max_valid=1, wide_max_fraction=0.3)
    mixes = {}
    cache0 = None
    for trial, width in enumerate((0.05, 0.5, 5.0)):
        s_q = rng.uniform(s.min(), s.max(), B)
        t_q = s_q + width
        _, _, pb, st = execute_batch(
            dg, q, s_q, t_q, k=4, beam=16, use_ref=True, plan="auto",
            config=cfg, return_plans=True, stats=True,
        )
        if cache0 is None:
            cache0 = planned_exec_cache_size()   # after the warm-up trial
        for name, cnt in pb.mix().items():
            mixes[name] = mixes.get(name, 0) + cnt
        assert np.asarray(st.iters).shape == (B,)
    assert len([n for n, c in mixes.items() if c]) >= 2, mixes
    assert planned_exec_cache_size() == cache0


def test_streaming_stats_no_recompile_across_epoch_swap():
    """StreamingIndex.search(return_stats=True) keeps serving through an
    epoch swap without re-tracing, and the delta tier's filter survivors
    show up in ``delta_valid``."""
    from repro.data import make_dataset, make_queries_vectors
    from repro.stream import StreamingIndex, streaming_search_cache_size

    dim = 8
    vecs, s, t = make_dataset(160, dim, seed=9)
    idx = StreamingIndex(
        dim, "overlap", node_capacity=256, delta_capacity=64,
        edge_capacity=48, M=6, Z=24,
    )
    idx.insert_batch(vecs[:100], s[:100], t[:100])
    idx.compact()
    for i in range(100, 130):
        idx.insert(vecs[i], s[i], t[i])

    qv = make_queries_vectors(4, dim, seed=10)
    broad_s = np.full(4, float(s.min()) - 1.0)
    broad_t = np.full(4, float(t.max()) + 1.0)

    # plan="graph" keeps the graph tier in play (the auto planner would
    # brute every broad query at this scale — exact zeros, tested above)
    ids0, d0, st0 = idx.search(
        qv, broad_s, broad_t, k=5, beam=16, plan="graph", return_stats=True
    )
    assert np.asarray(st0.delta_valid).sum() > 0   # delta tier was searched
    cache_before = streaming_search_cache_size()
    epoch_before = idx.epoch

    idx.compact()   # swap: delta drains into a new graph epoch
    assert idx.epoch > epoch_before
    ids1, d1, st1 = idx.search(
        qv, broad_s, broad_t, k=5, beam=16, plan="graph", return_stats=True
    )
    assert streaming_search_cache_size() == cache_before
    assert np.asarray(st1.delta_valid).sum() == 0  # delta empty post-swap
    assert np.asarray(st1.visited).min() > 0
    # stats=True changes no result on the streaming path either
    ids2, d2 = idx.search(qv, broad_s, broad_t, k=5, beam=16, plan="graph")
    np.testing.assert_array_equal(ids1, ids2)


# --- always-on loop totals ----------------------------------------------------


def _graph_batch(vecs, s, t, B, seed):
    """Queries whose windows span a fifth to most of the time axis, so
    that rows traverse for several iterations."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, vecs.shape[1])).astype(np.float32)
    s_q = rng.uniform(s.min(), s.max(), B)
    span = float(t.max() - s.min())
    t_q = s_q + rng.uniform(0.2, 0.8, B) * span
    return q, s_q, t_q


def _expected_totals(st, rows, width, fetched):
    """``LOOP_TOTALS`` as sums of the per-query counters over ``rows``, a
    mask over the loop's B rows: its trips are the longest row's
    iterations, and masked rows take row slots too. ``fetched(slots)``
    gives the rows fetched."""
    iters = np.asarray(st.iters)[rows]
    slots = int(iters.max(initial=0)) * len(rows)
    return [slots, int(iters.sum()), slots * width,
            int(np.asarray(st.kept)[rows].sum()), fetched(slots)]


def _fetched_within(totals, st, rows):
    """A fused loop fetches at least the kept candidates' rows and at
    most the real candidates' ones."""
    kept = int(np.asarray(st.kept)[rows].sum())
    return kept <= totals[4] <= int(np.asarray(st.cand_total)[rows].sum())


@pytest.mark.parametrize("layout", ["packed", "int32", "unfused"])
def test_loop_totals_equal_per_query_sums_graph(obs_setup, layout):
    """plan="graph": row slots = B × max iters, row iterations = Σ iters,
    candidate slots = row slots × E, kept = Σ kept and rows fetched = the
    unvisited real candidates (packed), those passing the labels too
    (int32, = Σ cand_valid) or every slot (unfused), with a cap small
    enough to cut some rows off."""
    vecs, s, t, dg = obs_setup
    q, s_q, t_q = _graph_batch(vecs, s, t, 6, seed=11)
    E = dg.device().nbr.shape[1]
    fetched = {
        "packed": lambda slots: sum(o["unvisited"] for o in _oracle_stats(
            dg, q, s_q, t_q, beam=8, max_iters=4)),
        "int32": lambda slots: int(np.asarray(st.cand_valid).sum()),
        "unfused": lambda slots: slots * E,
    }[layout]
    states, ep = prepare_states(dg, s_q, t_q)
    dev = dg.device()
    fused = layout != "unfused"
    labels = dg.serving_labels(fused=fused, packed=layout == "packed")
    ids, d, totals, st = _batched_search_core(
        dev.table, dev.nbr, labels, jnp.asarray(q), jnp.asarray(states),
        jnp.asarray(ep), k=4, beam=8, max_iters=4, use_ref=True,
        fused=fused, norms=dev.norms if fused else None, stats=True,
    )
    assert np.asarray(st.hit_max_iters).any()
    assert np.asarray(totals).tolist() == _expected_totals(
        st, np.ones(6, bool), E, fetched)
    ids0, d0, totals0 = _batched_search_core(
        dev.table, dev.nbr, labels, jnp.asarray(q), jnp.asarray(states),
        jnp.asarray(ep), k=4, beam=8, max_iters=4, use_ref=True,
        fused=fused, norms=dev.norms if fused else None,
    )
    np.testing.assert_array_equal(np.asarray(totals0), np.asarray(totals))
    np.testing.assert_array_equal(np.asarray(ids0), np.asarray(ids))


@pytest.mark.parametrize("fused", [True, False])
def test_loop_totals_equal_per_query_sums_planned(obs_setup, monkeypatch,
                                                   fused):
    """plan="auto": the graph loop's totals (row 0) are the sums over the
    rows planned GRAPH, the wide loop's (row 1) over those planned
    GRAPH_WIDE, over all B row slots of each loop; the fused loops fetch
    between the kept and the real candidates' rows, the unfused one
    every slot's."""
    from repro.exec import executor

    vecs, s, t, dg = obs_setup
    seen = []
    core = executor.planned_exec_core

    def spy(*a, **kw):
        out = core(*a, **kw)
        seen.append(np.asarray(out[2]))
        return out

    monkeypatch.setattr(executor, "planned_exec_core", spy)
    cfg = PlannerConfig(brute_max_valid=1, wide_max_fraction=0.1)
    q, s_q, _ = _graph_batch(vecs, s, t, 8, seed=22)
    span = float(t.max() - s.min())
    t_q = s_q + span * np.array([0.005, 0.05, 0.5, 0.005, 0.05, 0.5, 0.3,
                                 0.8])
    _, _, pb, st = execute_batch(
        dg, q, s_q, t_q, k=4, beam=8, max_iters=6, use_ref=True,
        fused=fused, plan="auto", config=cfg, return_plans=True,
        stats=True,
    )
    totals = seen[-1]
    assert totals.shape == (2, len(LOOP_TOTALS))
    assert np.asarray(st.hit_max_iters).any()
    E = dg.device().nbr.shape[1]
    widths = (E, (cfg.wide_expand if fused else 1) * E)
    for row, plan in enumerate((QueryPlan.GRAPH, QueryPlan.GRAPH_WIDE)):
        rows = pb.plans == int(plan)
        assert rows.any(), plan
        want = _expected_totals(st, rows, widths[row], lambda sl: sl * E)
        assert totals[row].tolist()[:4] == want[:4], plan
        if fused:
            assert _fetched_within(totals[row], st, rows), plan
        else:
            assert totals[row][4] == want[4], plan


@pytest.fixture(scope="module")
def stream_idx():
    from repro.data import make_dataset
    from repro.stream import StreamingIndex

    vecs, s, t = make_dataset(220, 8, seed=23)
    idx = StreamingIndex(
        8, "overlap", node_capacity=256, delta_capacity=64,
        edge_capacity=48, M=6, Z=24,
    )
    idx.insert_batch(vecs[:160], s[:160], t[:160])
    idx.compact()
    for i in range(160, 180):
        idx.insert(vecs[i], s[i], t[i])
    return idx, vecs, s, t


@pytest.mark.parametrize("plan", ["auto", "graph"])
def test_streaming_search_folds_loop_totals(stream_idx, plan):
    """StreamingIndex.search folds each loop's totals into the registry it
    is given, labelled by plan, with the slot counters from the loop's
    static shapes; they sum to the stats=True per-query counters."""
    idx, vecs, s, t = stream_idx
    B = 8
    q, s_q, _ = _graph_batch(vecs, s, t, B, seed=24)
    span = float(t.max() - s.min())
    t_q = s_q + span * np.array([0.005, 0.05, 0.5, 0.005, 0.05, 0.5, 0.3,
                                 0.8])
    cfg = PlannerConfig(brute_max_valid=1, wide_max_fraction=0.1)
    reg = MetricsRegistry()
    ids, d, st = idx.search(q, s_q, t_q, k=4, beam=8, plan=plan,
                            planner_config=cfg, return_stats=True,
                            registry=reg)
    loops = ("GRAPH",) if plan == "graph" else ("GRAPH", "GRAPH_WIDE")
    M = (1,) if plan == "graph" else (1, cfg.wide_expand)
    E = idx._dg.nbr.shape[1]

    def value(name, p):
        return reg.counter(name).value(plan=p)

    def summed(name):
        return sum(value(name, p) for p in loops)

    assert summed("repro_search_iterations_total") == int(
        np.asarray(st.iters).sum())
    assert summed("repro_search_candidates_kept_total") == int(
        np.asarray(st.kept).sum())
    fetched = summed("repro_search_rows_fetched_total")
    assert np.asarray(st.kept).sum() <= fetched <= np.asarray(
        st.cand_total).sum()
    slots = [value("repro_search_row_slots_total", p) for p in loops]
    assert min(slots) > 0
    assert max(slots) == B * int(np.asarray(st.iters).max())
    for p, sl, m in zip(loops, slots, M):
        assert sl % B == 0
        assert value("repro_search_candidate_slots_total", p) == sl * m * E
    # the totals ride with stats off too, and change no result
    reg2 = MetricsRegistry()
    ids2, d2 = idx.search(q, s_q, t_q, k=4, beam=8, plan=plan,
                          planner_config=cfg, registry=reg2)
    np.testing.assert_array_equal(ids, ids2)
    for name in ("repro_search_iterations_total",
                 "repro_search_row_slots_total",
                 "repro_search_rows_fetched_total"):
        for p in loops:
            assert reg2.counter(name).value(plan=p) == value(name, p)


def test_served_programs_stay_one_across_plan_mixes_and_swaps():
    """With the totals always on, the planned and streaming steps keep one
    compiled program each across plan mixes and an epoch swap."""
    from repro.data import make_dataset
    from repro.exec import planned_exec_cache_size
    from repro.stream import StreamingIndex, streaming_search_cache_size

    vecs, s, t = make_dataset(200, 8, seed=25)
    idx = StreamingIndex(
        8, "overlap", node_capacity=256, delta_capacity=64,
        edge_capacity=48, M=6, Z=24,
    )
    idx.insert_batch(vecs[:150], s[:150], t[:150])
    idx.compact()
    rng = np.random.default_rng(26)
    B = 6
    q = rng.standard_normal((B, 8)).astype(np.float32)
    s_q = rng.uniform(s.min(), s.max(), B)
    span = float(t.max() - s.min())
    cfg = PlannerConfig(brute_max_valid=1, wide_max_fraction=0.1)
    mixes = set()

    def serve(frac):
        for plan in ("auto", "graph"):
            reg = MetricsRegistry()
            idx.search(q, s_q, s_q + frac * span, k=4, beam=8, plan=plan,
                       planner_config=cfg, registry=reg)
            slots = reg.counter("repro_search_row_slots_total")
            mixes.add((plan, slots.value(plan="GRAPH") > 0,
                       slots.value(plan="GRAPH_WIDE") > 0))

    serve(0.005)
    sizes = (streaming_search_cache_size(), planned_exec_cache_size())
    for frac in (0.05, 0.5):
        serve(frac)
    for i in range(150, 200):
        idx.insert(vecs[i], s[i], t[i])
    serve(0.05)
    idx.compact()
    serve(0.8)
    assert (streaming_search_cache_size(), planned_exec_cache_size()) == sizes
    assert len({m for m in mixes if m[0] == "auto"}) >= 2, mixes
