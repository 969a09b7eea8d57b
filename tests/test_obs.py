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
    counting with the documented semantics (expand=1)."""
    labels = dg.labels_i32()
    nbr = dg.nbr
    vecs = dg.vectors.astype(np.float64)
    states, ep = prepare_states(dg, s_q, t_q)
    B = q.shape[0]
    out = []
    for b in range(B):
        a, c = int(states[b, 0]), int(states[b, 1])
        st = dict(iters=0, expanded=0, cand_total=0, cand_valid=0, kept=0,
                  visited=0, beam_occupancy=0, hit_max_iters=False)
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
    # hop tallies partition the totals
    assert int(st.hop_total.sum()) == int(st.cand_total.sum())
    assert int(st.hop_valid.sum()) == int(st.cand_valid.sum())
    assert st.hop_total.shape == (max_iters,)


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
    """The guard for 'stats=False compiles to the pre-obs program': exactly
    the two historical outputs, and no hop-axis arrays anywhere in the
    jaxpr; stats=True appends exactly the SearchStats leaves."""
    vecs, s, t, dg = obs_setup
    rng = np.random.default_rng(5)
    q = rng.standard_normal((4, vecs.shape[1])).astype(np.float32)
    s_q = rng.uniform(s.min(), s.max(), 4)
    t_q = s_q + 0.5
    states, ep = prepare_states(dg, s_q, t_q)
    dev = dg.device()
    labels = dg.serving_labels(fused=True)
    max_iters = 37   # distinctive: no other axis in the program is 37
    args = (dev.table, dev.nbr, labels, jnp.asarray(q),
            jnp.asarray(states), jnp.asarray(ep))

    def run(stats):
        return jax.make_jaxpr(
            lambda *a: _batched_search_core(
                *a, k=4, beam=8, max_iters=max_iters, use_ref=True,
                norms=dev.norms, stats=stats,
            )
        )(*args)

    off = run(False)
    assert len(off.out_avals) == 2
    assert f"i32[{max_iters}]" not in str(off)
    on = run(True)
    assert len(on.out_avals) == 2 + len(SearchStats._fields)
    assert f"i32[{max_iters}]" in str(on)


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


def test_combine_stats_pads_hop_axes():
    a = SearchStats(*(jnp.ones(2, jnp.int32) for _ in range(7)),
                    jnp.zeros(2, bool), jnp.ones(2, jnp.int32),
                    jnp.ones(3, jnp.int32), jnp.ones(3, jnp.int32))
    b = SearchStats(*(jnp.ones(2, jnp.int32) for _ in range(7)),
                    jnp.ones(2, bool), jnp.ones(2, jnp.int32),
                    jnp.ones(5, jnp.int32), jnp.ones(5, jnp.int32))
    m = combine_stats(a, b)
    assert m.hop_total.shape == (5,)
    np.testing.assert_array_equal(
        np.asarray(m.hop_total), [2, 2, 2, 1, 1]
    )
    assert np.all(np.asarray(m.iters) == 2)
    assert np.all(np.asarray(m.hit_max_iters))
    d = per_query_dict(m)
    assert set(d) == set(SearchStats._fields) - {"hop_valid", "hop_total"}


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
    c = reg.counter("repro_search_iterations_total")
    assert c.value() == 8
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
