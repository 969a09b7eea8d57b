"""Distributed serving scaling + overload behavior.

Two parts:

  * **scaling** (full mode only): recall + throughput of the shard_map
    serving step as database sharding widens — runs in a subprocess with
    8 host-platform devices so the main process keeps its 1-device view;
  * **overload** (every mode, incl. CI ``--tiny``): a 2x-overload closed
    loop against ``StreamingServer`` + ``AdmissionController`` — every
    serving step, twice the batch capacity arrives. The admission layer
    must shed the excess (bounded queue, deadline-aware) while the
    admitted requests stay inside their deadline.

Emits the usual CSV lines plus a machine-readable ``BENCH_serving.json``
at the repo root. Regression gates (asserted on every run, including
``--tiny``):

  * admitted-request p99 latency <= the configured deadline;
  * shed rate > 0 at 2x offered load (if nothing sheds, the queue grew
    without bound — exactly the failure mode admission exists to stop);
  * observed queue depth never exceeds ``max_queue``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JSON_PATH = Path(REPO) / "BENCH_serving.json"

_CODE = """
import time
import numpy as np
from repro.data import (make_dataset, make_queries_vectors, generate_queries,
                        ground_truth, recall_at_k)
from repro.launch.mesh import make_host_mesh
from repro.serve import build_sharded_index, serve_batch

vecs, s, t = make_dataset(2048, 24, seed=0)
qv = make_queries_vectors(32, 24, seed=1)
qs = ground_truth(generate_queries(qv, s, t, "containment", 0.02, k=10, seed=2),
                  vecs, s, t)
for shards in (2, 4, 8):
    idx = build_sharded_index(vecs, s, t, "containment", shards, M=10, Z=48)
    mesh = make_host_mesh(model_parallel=shards)
    # warm-up compile
    serve_batch(idx, mesh, qs.vectors, qs.s_q, qs.t_q, k=10, beam=48,
                merge="tournament")
    t0 = time.perf_counter()
    iters = 3
    for _ in range(iters):
        ids, _ = serve_batch(idx, mesh, qs.vectors, qs.s_q, qs.t_q, k=10,
                             beam=48, merge="tournament")
    us = (time.perf_counter() - t0) / (iters * qs.nq) * 1e6
    rec = recall_at_k(ids, qs)
    print(f"serving.shards{shards},{us:.1f},recall={rec:.4f}|"
          f"qps={1e6/us:.0f}|n=2048|merge=tournament", flush=True)
"""


def _scaling_subprocess() -> None:
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=os.path.join(REPO, "src"),
    )
    out = subprocess.run(
        [sys.executable, "-c", _CODE], env=env, capture_output=True,
        text=True, timeout=900,
    )
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-1500:])
    print(out.stdout, end="")


def _overload_scenario(tiny: bool) -> dict:
    from repro.serve.admission import (
        AdmissionConfig,
        AdmissionController,
        RequestShed,
    )
    from repro.serve.batching import StreamingServer
    from repro.stream import StreamingIndex

    rng = np.random.default_rng(0)
    if tiny:
        n, dim, batch, rounds = 300, 16, 8, 40
        caps = dict(node_capacity=512, delta_capacity=128, edge_capacity=32)
    else:
        n, dim, batch, rounds = 2000, 32, 16, 80
        caps = dict(node_capacity=4096, delta_capacity=256, edge_capacity=64)
    idx = StreamingIndex(dim, "containment", **caps)
    for _ in range(n):
        s, t = np.sort(rng.uniform(0.0, 100.0, 2))
        idx.insert(rng.standard_normal(dim).astype(np.float32),
                   float(s), float(t))

    # calibrate: warm EVERY degradation rung's compiled program (level 2
    # switches to the "graph" core mid-overload — a cold compile there
    # would land its one-time cost on the queued requests and blow the
    # SLA this bench is gating) and measure the steady batch service time,
    # so the deadline below comes from measurement, not a guess
    import dataclasses

    from repro.exec import default_planner_config

    qcal = rng.standard_normal((batch, dim)).astype(np.float32)
    scal, tcal = np.full(batch, 10.0), np.full(batch, 90.0)
    degraded = dataclasses.replace(
        default_planner_config(), wide_max_fraction=0.0
    )
    idx.search(qcal, scal, tcal, k=10, plan="auto")
    idx.search(qcal, scal, tcal, k=10, plan="auto", planner_config=degraded)
    idx.search(qcal, scal, tcal, k=10, plan="graph")
    cal = StreamingServer(idx, batch_size=batch, k=10, timeout_s=0.0)
    for _ in range(5):
        for _ in range(batch):
            cal.submit(rng.standard_normal(dim).astype(np.float32),
                       10.0, 90.0)
        t0 = time.monotonic()
        cal.step(force=True)
        batch_s = time.monotonic() - t0
    # deadline: headroom for max_queue/batch in-flight batches; the
    # predicted-wait shedder is what has to keep p99 under it
    max_queue = 4 * batch
    deadline_s = max(0.1, 10.0 * batch_s)
    adm = AdmissionController(
        AdmissionConfig(max_queue=max_queue, default_deadline_s=deadline_s,
                        min_batches_for_prediction=1),
        batch_size=batch,
    )
    srv = StreamingServer(idx, batch_size=batch, k=10, timeout_s=0.0,
                          admission=adm)
    adm.observe_batch(batch_s)      # seed the EMA from calibration

    offered = 0
    shed = 0
    answered = {}
    submit_times = {}
    max_depth = 0
    for _ in range(rounds):
        # 2x overload: two batches' worth of arrivals per serving step
        for _ in range(2 * batch):
            offered += 1
            try:
                rid = srv.submit(
                    rng.standard_normal(dim).astype(np.float32), 10.0, 90.0,
                )
                submit_times[rid] = time.monotonic()
            except RequestShed:
                shed += 1
        max_depth = max(max_depth, srv.batcher.pending)
        out = srv.step(force=True)
        now = time.monotonic()
        for rid in out:
            answered[rid] = now - submit_times.pop(rid)
    # drain the tail so every admitted request is accounted for
    while srv.batcher.pending:
        out = srv.step(force=True)
        now = time.monotonic()
        for rid in out:
            answered[rid] = now - submit_times.pop(rid)
    expired = len(submit_times)     # dropped at batch formation
    lats = np.sort(np.fromiter(answered.values(), float))
    p50 = float(np.percentile(lats, 50)) if lats.size else 0.0
    p99 = float(np.percentile(lats, 99)) if lats.size else 0.0
    record = {
        "offered": offered,
        "admitted": adm.admitted,
        "answered": len(answered),
        "shed": shed,
        "expired_in_queue": expired,
        "shed_rate": round(shed / max(offered, 1), 4),
        "deadline_s": round(deadline_s, 4),
        "batch_service_s": round(batch_s, 5),
        "max_queue": max_queue,
        "max_observed_depth": max_depth,
        "admitted_p50_s": round(p50, 5),
        "admitted_p99_s": round(p99, 5),
    }
    # gates: bounded queue, real shedding, and the SLA on what was admitted
    assert shed > 0, f"2x overload must shed: {record}"
    assert max_depth <= max_queue, f"queue bound violated: {record}"
    assert p99 <= deadline_s, (
        f"admitted p99 {p99:.4f}s blew the deadline {deadline_s:.4f}s: "
        f"{record}"
    )
    return record


def main(tiny: bool = False) -> None:
    record = {"bench": "serving", "tiny": tiny, "overload_2x": {}}
    ov = _overload_scenario(tiny)
    record["overload_2x"] = ov
    print(
        f"serving.overload2x,{ov['admitted_p99_s'] * 1e6:.1f},"
        f"shed_rate={ov['shed_rate']}|p99_s={ov['admitted_p99_s']}|"
        f"deadline_s={ov['deadline_s']}|answered={ov['answered']}",
        flush=True,
    )
    if not tiny:
        _scaling_subprocess()
    JSON_PATH.write_text(json.dumps(record, indent=2) + "\n")
    print(f"# wrote {JSON_PATH}", flush=True)


if __name__ == "__main__":
    main(tiny="--tiny" in sys.argv[1:])
