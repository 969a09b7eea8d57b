"""Telemetry cost + end-to-end serving-with-metrics benchmark.

Two questions the observability layer must answer before serving leaves it
on by default:

  1. what does the host-side registry cost per event (counter inc,
     histogram observe, labeled variants) — these sit on the serving hot
     path, so they are measured as raw ops/s;
  2. what does a fully instrumented serving loop look like — a request
     stream through ``StreamingServer`` with device-side traversal
     counters (``stats=True``), per-request latency histograms, planner
     route counts, and the Prometheus/JSON exporters all enabled. The
     request-latency quantiles quoted come from the SAME histogram a
     scraper would read, and the run asserts the export actually carries
     the required series (the CI telemetry smoke re-checks this end to
     end).

Emits the usual CSV lines plus a machine-readable ``BENCH_telemetry.json``
at the repo root.

``--tiny`` (or ``main(tiny=True)``) shrinks everything for the CI smoke.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from benchmarks.common import emit
from repro.data import make_dataset, make_queries_vectors
from repro.obs import (
    MetricsRegistry,
    get_registry,
    parse_prometheus_text,
    to_prometheus_text,
    write_json,
    write_prometheus,
)
from repro.serve.batching import StreamingServer
from repro.stream import StreamingIndex

JSON_PATH = Path(__file__).resolve().parent.parent / "BENCH_telemetry.json"

# series the instrumented serving loop must export (CI smoke contract)
REQUIRED_SERIES = (
    "repro_batches_total",
    "repro_batch_occupancy",
    "repro_request_latency_seconds",
    "repro_search_queries_total",
    "repro_search_iterations_total",
    "repro_search_terminations_total",
    "repro_planner_routes_total",
    "repro_span_seconds",
    "repro_epoch",
    # durability series (ISSUE 10): the same scrape must carry the
    # recovery/quarantine story a crashed deployment would be read by
    "repro_recovery_seconds",
    "repro_wal_replayed_records_total",
    "repro_segments_quarantined",
    "repro_snapshot_bytes",
    "repro_snapshot_seconds",
)


def _durability_exercise(reg, *, tiny: bool) -> dict:
    """Checkpoint + crash + recover + quarantine/heal against the SAME
    registry the serving loop used, so one scrape carries the durability
    series the CI smoke asserts on."""
    import shutil
    import tempfile

    from repro.core.predicates import DominanceSpace, get_relation
    from repro.scale import SegmentGrid, SegmentedStreamingIndex
    from repro.stream.index import CompactionPolicy

    n, dim = (160, 8) if tiny else (400, 16)
    tail = n // 8
    vecs, s, t = make_dataset(n, dim, seed=51)
    grid = SegmentGrid.from_space(
        DominanceSpace.from_intervals(get_relation("overlap"), s, t), 2
    )
    policy = CompactionPolicy(max_delta_fraction=0.1, min_mutations=32)
    bk = dict(M=6, Z=24, K_p=4)
    work = tempfile.mkdtemp(prefix="bench_telemetry_dur_")
    try:
        idx = SegmentedStreamingIndex(
            dim, "overlap", grid, node_capacity=2 * n, delta_capacity=64,
            edge_capacity=16, M=6, Z=24, K_p=4, policy=policy,
            build_kwargs=bk, storage_dir=work, registry=reg,
        )
        idx.insert_batch(vecs[: n - tail], s[: n - tail], t[: n - tail])
        idx.save_snapshot()
        idx.insert_batch(vecs[n - tail:], s[n - tail:], t[n - tail:])
        for w in idx._wals:
            if w is not None:
                w.close()
        rec, report = SegmentedStreamingIndex.recover(
            work, policy=policy, build_kwargs=bk, registry=reg,
        )
        rec.quarantine_segment(0, "bench telemetry")
        healed = rec.maybe_rebuild()
        for w in rec._wals:
            if w is not None:
                w.close()
        return {
            "records_replayed": int(report.records_replayed),
            "quarantine_healed": bool(healed.get(0)),
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _registry_micro(n_ops: int) -> dict:
    """Raw registry event rates (ops/s) — the hot-path budget."""
    reg = MetricsRegistry()
    c = reg.counter("c_total")
    h = reg.histogram("h_seconds")
    out = {}
    cases = {
        "counter_inc": lambda: c.inc(),
        "counter_inc_labeled": lambda: c.inc(1, plan="GRAPH"),
        "hist_observe": lambda: h.observe(0.003),
        "hist_observe_labeled": lambda: h.observe(0.003, shard="0"),
    }
    for name, op in cases.items():
        t0 = time.perf_counter()
        for _ in range(n_ops):
            op()
        dt = time.perf_counter() - t0
        out[name + "_ops_per_s"] = round(n_ops / dt, 0)
        emit(f"telemetry.registry.{name}", dt / n_ops * 1e6,
             ops_per_s=int(n_ops / dt))
    return out


def _serving_loop(*, n, dim, n_requests, batch_size, tiny) -> dict:
    """A request stream through a fully instrumented StreamingServer."""
    vecs, s, t = make_dataset(n, dim, seed=31)
    idx = StreamingIndex(
        dim, "overlap", node_capacity=2 * n, delta_capacity=max(64, n // 4),
        edge_capacity=64, M=8, Z=32,
    )
    idx.insert_batch(vecs[: n - n // 8], s[: n - n // 8], t[: n - n // 8])
    idx.compact()
    for i in range(n - n // 8, n):        # leave a live delta tier
        idx.insert(vecs[i], s[i], t[i])

    # the GLOBAL registry, as a deployment would scrape it: the planner's
    # route counters always land there, so one scrape carries the whole
    # serving story (reset first — earlier benchmarks share the process)
    reg = get_registry()
    reg.reset()
    srv = StreamingServer(
        idx, batch_size=batch_size, k=10, beam=32, registry=reg, stats=True,
    )
    rng = np.random.default_rng(32)
    qv = make_queries_vectors(n_requests, dim, seed=33)
    s_q = rng.uniform(s.min(), s.max(), n_requests)
    t_q = s_q + rng.uniform(0.1, (t - s).max(), n_requests)

    # warm-up: compile the serving step off the clock, then zero the
    # registry so the quoted quantiles are steady-state
    for i in range(batch_size):
        srv.submit(qv[i], float(s_q[i]), float(t_q[i]))
    srv.drain()
    reg.reset()

    served = 0
    t0 = time.perf_counter()
    for i in range(n_requests):
        srv.submit(qv[i], float(s_q[i]), float(t_q[i]))
        served += len(srv.step())          # flushes on full batches
    served += len(srv.drain())
    wall = time.perf_counter() - t0
    assert served == n_requests

    lat = reg.histogram("repro_request_latency_seconds").summary()
    occ = reg.histogram("repro_batch_occupancy").summary()
    durability = _durability_exercise(reg, tiny=tiny)
    text = to_prometheus_text(reg)
    samples = parse_prometheus_text(text)
    present = {
        series: any(k == series or k.startswith(series + "{")
                    or k.startswith(series + "_")
                    for k in samples)
        for series in REQUIRED_SERIES
    }
    missing = [k for k, ok in present.items() if not ok]
    assert not missing, f"export missing required series: {missing}"
    write_prometheus(JSON_PATH.parent / "BENCH_telemetry.prom", reg)
    write_json(JSON_PATH.parent / "BENCH_telemetry.metrics.json", reg)

    qps = n_requests / wall
    out = {
        "requests": n_requests,
        "batch_size": batch_size,
        "qps": round(qps, 2),
        "request_latency_p50_ms": round(lat["p50"] * 1e3, 3),
        "request_latency_p90_ms": round(lat["p90"] * 1e3, 3),
        "request_latency_p99_ms": round(lat["p99"] * 1e3, 3),
        "mean_batch_occupancy": round(occ["sum"] / max(occ["count"], 1), 2),
        "search_iterations_total": sum(
            v for key, v in samples.items()
            if key.startswith("repro_search_iterations_total{")),
        "delta_candidates_total": samples.get(
            "repro_search_delta_candidates_valid_total", 0.0),
        "export_series": len(samples),
        "export_bytes": len(text),
        "durability": durability,
    }
    emit(
        "telemetry.serving.instrumented", 1e6 / qps,
        qps=round(qps, 1),
        p99_ms=out["request_latency_p99_ms"],
        series=out["export_series"],
    )
    return out


def main(tiny: bool = False) -> None:
    if tiny:
        n, dim, n_requests, batch_size, n_ops = 240, 8, 64, 8, 20_000
    else:
        n, dim, n_requests, batch_size, n_ops = 2000, 32, 256, 16, 200_000
    record = {
        "bench": "telemetry",
        "tiny": tiny,
        "registry": _registry_micro(n_ops),
        "serving": _serving_loop(
            n=n, dim=dim, n_requests=n_requests, batch_size=batch_size,
            tiny=tiny,
        ),
        "required_series": list(REQUIRED_SERIES),
    }
    JSON_PATH.write_text(json.dumps(record, indent=2) + "\n")
    print(f"# wrote {JSON_PATH}", flush=True)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke scale")
    main(tiny=ap.parse_args().tiny)
