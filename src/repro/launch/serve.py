"""Serving launcher: build (or load) a sharded UDG and serve batched
interval-predicate queries over the device mesh.

Example (CPU, 8 host devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
    python -m repro.launch.serve --n 4096 --dim 32 --shards 4 \
    --relation overlap --selectivity 0.05 --queries 64
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.data import (
    generate_queries,
    ground_truth,
    make_dataset,
    make_queries_vectors,
    recall_at_k,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.serve import RequestBatcher, build_sharded_index, serve_batch


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--relation", default="containment")
    ap.add_argument("--selectivity", type=float, default=0.05)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--beam", type=int, default=64)
    ap.add_argument("--merge", default="all_gather",
                    choices=["all_gather", "tournament"])
    ap.add_argument("--M", type=int, default=16)
    ap.add_argument("--Z", type=int, default=96)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    print(f"building sharded UDG: n={args.n} shards={args.shards} ...")
    vecs, s, t = make_dataset(args.n, args.dim, seed=args.seed)
    t0 = time.perf_counter()
    idx = build_sharded_index(
        vecs, s, t, args.relation, args.shards, M=args.M, Z=args.Z
    )
    print(f"  built in {time.perf_counter()-t0:.1f}s")
    mesh = make_host_mesh(model_parallel=args.shards)

    qv = make_queries_vectors(args.queries, args.dim, seed=args.seed + 1)
    qs = generate_queries(qv, s, t, args.relation, args.selectivity, k=args.k,
                          seed=args.seed + 2)
    qs = ground_truth(qs, vecs, s, t)

    batcher = RequestBatcher(args.batch, args.dim)
    for i in range(args.queries):
        batcher.submit(qv[i], qs.s_q[i], qs.t_q[i])

    all_ids = np.full((args.queries, args.k), -1, dtype=np.int64)
    served = 0
    t0 = time.perf_counter()
    while (b := batcher.next_batch()) is not None:
        q, s_q, t_q, rids, n_real = b
        ids, dists = serve_batch(
            idx, mesh, q, s_q, t_q, k=args.k, beam=args.beam, merge=args.merge
        )
        for row, rid in enumerate(rids):
            all_ids[rid] = ids[row]
        served += n_real
    dt = time.perf_counter() - t0
    print(f"served {served} queries in {dt:.2f}s "
          f"({served/dt:.0f} qps incl. host loop)")
    print(f"recall@{args.k}: {recall_at_k(all_ids, qs):.4f}")


if __name__ == "__main__":
    main()
