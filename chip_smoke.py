"""Smoke test of the served search path on a TPU, through the user entry points.

    python chip_smoke.py [--n N] [--seed S]     # one chip: build, serve, mutate
    python chip_smoke.py --four-chips           # the sharded path on four chips

One chip: a ``StreamingIndex`` at the deployment width of
``repro.configs.udg_serve`` (d = 768, labeled degree 96, containment, beam 64,
k 10, its planner thresholds) is bulk-loaded with ``n`` objects through the
batched constructor, served through ``StreamingServer`` (plan ``"auto"``),
then mutated (512 inserts, 128 deletes) and served again. The answers are
checked against the exact filtered ground truth over the live set and
against the reference formulation (``use_ref=True``) run on the same chip
on the same queries; the compiled serving step must hold the Pallas kernels
(``tpu_custom_call``) and the packed label rows.

Four chips: ``build_sharded_index`` with 4 shards over
``make_host_mesh(model_parallel=4)``, a few ``serve_batch(plan="auto")``
batches checked against the ground truth, and each chip's bytes in use.

Build, compile and serve times are printed as set-up facts, not speeds. The
last line of standard output is one JSON object naming the device. Any
failed check raises; without a TPU the script exits non-zero before any
phase runs. Compiled programs are cached (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.udg_serve import CONFIG  # noqa: E402
from repro.data import (  # noqa: E402
    generate_queries,
    ground_truth,
    make_dataset,
    make_queries_vectors,
)
from repro.data.workloads import QuerySet  # noqa: E402
from repro.exec import QueryPlan  # noqa: E402
from repro.exec.plan import PLAN_NAMES  # noqa: E402
from repro.kernels.layout import is_label_rows  # noqa: E402
from repro.serve import build_sharded_index, serve_batch  # noqa: E402
from repro.serve.batching import StreamingServer  # noqa: E402
import repro.stream.index as stream_index  # noqa: E402

RECALL_GAP = 0.01          # kernel path vs reference formulation, absolute


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Sizes of the one-chip run: the deployment's widths, ``n`` objects."""

    n: int = CONFIG.n_per_shard
    dim: int = CONFIG.dim
    degree: int = CONFIG.degree
    wave: int = CONFIG.build_wave
    batch: int = 256
    delta_capacity: int = 1024
    n_insert: int = 512
    n_delete: int = 128
    min_recall: float = 0.75     # a sanity floor; the gate is the reference


def log(**facts) -> None:
    print(" ".join(f"{k}={v}" for k, v in facts.items()), flush=True)


class CompileClock:
    """Seconds spent compiling (or fetching from the persistent cache) and
    the persistent cache's hits and misses, from JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@dataclasses.dataclass
class Batch:
    """One served query batch and what came back for it."""

    q: np.ndarray
    s_q: np.ndarray
    t_q: np.ndarray
    ids: np.ndarray | None = None       # [B, k] served (kernel path)
    ref_ids: np.ndarray | None = None   # [B, k] reference formulation
    plans: np.ndarray | None = None     # [B] QueryPlan of each row
    gt: np.ndarray | None = None        # [B, k] ground truth (external ids)


class StepSpy:
    """Records the arguments of every planned streaming step the index
    runs, so the served programs can be lowered again and inspected."""

    def __init__(self):
        self.calls = []
        self._orig = stream_index.planned_streaming_search_core

    def __enter__(self):
        def spy(*args, **kwargs):
            self.calls.append((args, kwargs))
            return self._orig(*args, **kwargs)

        stream_index.planned_streaming_search_core = spy
        return self

    def __exit__(self, *exc):
        stream_index.planned_streaming_search_core = self._orig

    def compiled_text(self, i: int = -1) -> str:
        args, kwargs = self.calls[i]
        return self._orig.lower(*args, **kwargs).compile().as_text()


# --- phases ----------------------------------------------------------------------


def build(z: Sizes, *, seed: int):
    """Bulk-load ``z.n`` objects into a StreamingIndex (one batched build).
    The data holds ``z.n_insert`` more objects of the same mixture, which
    :func:`mutate` inserts later."""
    vecs, s, t = make_dataset(z.n + z.n_insert, z.dim, seed=seed)
    idx = stream_index.StreamingIndex(
        z.dim, CONFIG.relation, node_capacity=z.n,
        delta_capacity=z.delta_capacity, edge_capacity=z.degree,
        build_kwargs=dict(CONFIG.build_kwargs(pad_nodes=z.n), wave=z.wave),
    )
    t0 = time.perf_counter()
    idx.bulk_load(vecs[: z.n], s[: z.n], t[: z.n])
    return idx, (vecs, s, t), time.perf_counter() - t0


def query_batch(qv, s, t, selectivity, *, seed, k=CONFIG.k) -> Batch:
    qs = generate_queries(qv, s, t, CONFIG.relation, selectivity, k=k,
                          seed=seed)
    return Batch(qs.vectors, qs.s_q, qs.t_q)


def serve(idx, batches, *, batch_size: int, k=CONFIG.k, beam=CONFIG.beam,
          use_ref=None):
    """Serve every batch through ``StreamingServer`` (plan "auto"), then
    the same queries through the reference formulation. Returns the spy
    holding the served steps' arguments."""
    srv = StreamingServer(idx, batch_size=batch_size, k=k, beam=beam,
                          use_ref=use_ref, plan="auto", timeout_s=0.0)
    with StepSpy() as spy:
        for b in batches:
            rids = [srv.submit(b.q[i], b.s_q[i], b.t_q[i])
                    for i in range(b.q.shape[0])]
            out = srv.step(force=True)
            b.ids = np.stack([out[r][0] for r in rids])
            b.plans = np.asarray(spy.calls[-1][0][14])[: len(rids)]
    for b in batches:
        b.ref_ids, _ = idx.search(b.q, b.s_q, b.t_q, k=k, beam=beam,
                                  use_ref=True, plan="auto")
    return spy


def mutate(idx, data, first: Batch, *, n: int, n_delete: int, seed: int,
           k=CONFIG.k):
    """Insert the objects of ``data`` past the first ``n`` (delta tier) and
    delete ``n_delete``: half of them the top-1 answers of ``first`` (graph
    tier), half of them just-inserted objects. Returns the next batch to
    serve — ``first``'s queries and queries near the inserted objects —
    and the deleted and inserted ids."""
    vecs, s, t = data
    rng = np.random.default_rng(seed)
    new_v = vecs[n:]
    new_ids = idx.insert_batch(new_v, s[n:], t[n:])
    top1 = [int(i) for i in dict.fromkeys(first.ids[:, 0]) if i >= 0]
    deleted = top1[: n_delete // 2]
    deleted += [int(i) for i in new_ids[: n_delete - len(deleted)]]
    for e in deleted:
        if not idx.delete(e):
            raise AssertionError(f"delete of live id {e} failed")
    half = first.q.shape[0] // 2
    near = new_v[:half] + 0.05 * rng.standard_normal(
        new_v[:half].shape).astype(np.float32)
    lv, ls, lt, _ = idx.snapshot_live()
    b = query_batch(near, ls, lt, 0.1, seed=seed, k=k)
    after = Batch(np.concatenate([first.q[:half], b.q]),
                  np.concatenate([first.s_q[:half], b.s_q]),
                  np.concatenate([first.t_q[:half], b.t_q]))
    return after, set(deleted), set(int(i) for i in new_ids)


def attach_ground_truth(idx, batches, *, k=CONFIG.k) -> None:
    """Exact filtered top-k over the current live set, as external ids."""
    lv, ls, lt, lext = idx.snapshot_live()
    for b in batches:
        qs = QuerySet(CONFIG.relation, b.q, b.s_q, b.t_q, 0.0,
                      np.zeros(len(b.q)), k)
        gt = ground_truth(qs, lv, ls, lt).gt_ids
        b.gt = np.where(gt >= 0, lext[np.maximum(gt, 0)], -1)


def recall(ids: np.ndarray, gt: np.ndarray) -> np.ndarray:
    """Per-row recall@k of ``ids`` against ``gt`` (rows without truth: 1)."""
    out = np.ones(len(gt))
    for i, (got, want) in enumerate(zip(ids, gt)):
        want = set(int(x) for x in want if x >= 0)
        if want:
            out[i] = len(want & set(int(x) for x in got if x >= 0)) / len(want)
    return out


def compare(batches, *, deleted=frozenset(), min_recall: float) -> dict:
    """The checks of the one-chip path; raises on the first that fails."""
    served = np.concatenate([recall(b.ids, b.gt) for b in batches])
    ref = np.concatenate([recall(b.ref_ids, b.gt) for b in batches])
    plans = np.concatenate([b.plans for b in batches])
    mix = {PLAN_NAMES[int(p)]: int(np.sum(plans == int(p))) for p in QueryPlan}
    brute = plans == int(QueryPlan.BRUTE_VALID)
    facts = dict(recall_kernel=float(served.mean()),
                 recall_reference=float(ref.mean()),
                 recall_brute_rows=float(served[brute].mean()) if brute.any()
                 else None,
                 recall_brute_rows_reference=float(ref[brute].mean())
                 if brute.any() else None, plan_mix=mix)
    if not brute.any() or mix["GRAPH"] + mix["GRAPH_WIDE"] == 0:
        raise AssertionError(f"plan mix lacks BRUTE_VALID or a graph plan: {mix}")
    if not np.all(served[brute] == 1.0):
        raise AssertionError(f"BRUTE_VALID rows are not exact: {facts}")
    if abs(facts["recall_kernel"] - facts["recall_reference"]) > RECALL_GAP:
        raise AssertionError(f"kernel recall is off the reference: {facts}")
    if facts["recall_kernel"] < min_recall:
        raise AssertionError(f"recall@k below {min_recall}: {facts}")
    back = {int(i) for b in batches for i in np.ravel(b.ids)} & set(deleted)
    back |= {int(i) for b in batches for i in np.ravel(b.ref_ids)} & set(deleted)
    if back:
        raise AssertionError(f"deleted ids came back: {sorted(back)[:10]}")
    return facts


def inspect_step(spy: StepSpy) -> dict:
    """What the served program holds: the Pallas kernels and the packed
    label rows, plus the device bytes of its array arguments."""
    text = spy.compiled_text()
    args, _ = spy.calls[-1]
    names = ("table", "nbr", "labels", "live", "ext", "delta_vectors",
             "delta_labels", "delta_slots", "delta_ext")
    return dict(
        tpu_custom_calls=text.count("tpu_custom_call"),
        packed_labels=bool(is_label_rows(args[2])),
        label_row_shape=tuple(args[2].shape),
        arg_bytes={nm: int(a.nbytes) for nm, a in zip(names, args)},
    )


# --- entry points ---------------------------------------------------------------


def require_tpu(count: int) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU device(s), JAX found "
                 f"{len(devs)} {devs[0].platform} device(s)")


def one_chip(z: Sizes, seed: int, clock: CompileClock, *,
             expect_kernels: bool = True) -> None:
    """Build, serve, mutate, serve again, check, inspect. Off the TPU
    (``expect_kernels=False``) the served step runs the jnp oracle, so the
    kernel check is reversed."""
    log(phase="build", n=z.n, dim=z.dim, degree=z.degree)
    idx, data, build_s = build(z, seed=seed)
    log(phase="build", build_seconds=build_s, epoch=idx.epoch,
        live=idx.live_count)
    vecs, s, t = data
    sels = (32 / z.n, 0.01, 0.01, 0.1, 0.1)
    batches = [
        query_batch(make_queries_vectors(
            z.batch, z.dim, seed=seed + 1 + i), s[: z.n], t[: z.n], sel,
            seed=seed + i)
        for i, sel in enumerate(sels)
    ]
    attach_ground_truth(idx, batches)
    t0, c0 = time.perf_counter(), clock.seconds
    serve(idx, batches, batch_size=z.batch)
    log(phase="serve", batches=len(batches), batch=z.batch,
        selectivities=[round(x, 6) for x in sels],
        seconds=time.perf_counter() - t0, compile_seconds=clock.seconds - c0)
    log(phase="compare", **compare(batches, min_recall=z.min_recall))

    after, deleted, inserted = mutate(idx, data, batches[0], n=z.n,
                                      n_delete=z.n_delete, seed=seed + 100)
    attach_ground_truth(idx, [after])
    spy = serve(idx, [after], batch_size=z.batch)
    facts = compare([after], deleted=deleted, min_recall=z.min_recall)
    delta_hits = len({int(i) for i in after.ids.ravel()} & inserted)
    if delta_hits == 0:
        raise AssertionError("no object of the delta tier was returned")
    log(phase="delta", inserted=len(inserted), deleted=len(deleted),
        delta_hits=delta_hits, **facts)

    info = inspect_step(spy)
    log(phase="verify", **info)
    if (info["tpu_custom_calls"] > 0) != expect_kernels:
        raise AssertionError(
            f"served step holds {info['tpu_custom_calls']} Pallas kernel "
            f"calls (kernels expected: {expect_kernels})")
    if not info["packed_labels"]:
        raise AssertionError("the served step does not use packed labels")
    stats = jax.devices()[0].memory_stats() or {}
    log(phase="memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_in_use=stats.get("bytes_in_use"),
        nbytes_by_component=idx._dg.nbytes_by_component())


def four_chips(n_local: int, seed: int) -> None:
    from repro.launch.mesh import make_host_mesh

    shards = 4
    n = shards * n_local
    log(phase="build", shards=shards, n_local=n_local, dim=CONFIG.dim)
    vecs, s, t = make_dataset(n, CONFIG.dim, seed=seed)
    t0 = time.perf_counter()
    idx = build_sharded_index(
        vecs, s, t, CONFIG.relation, shards,
        build_kwargs=CONFIG.build_kwargs(pad_nodes=n_local),
        edge_capacity=CONFIG.degree)
    log(phase="build", build_seconds=time.perf_counter() - t0,
        degree=idx.nbr.shape[-1], label_row_shape=idx.kernel_layout(
            "labels").shape)
    if idx.nbr.shape[-1] != CONFIG.degree:
        raise AssertionError(f"shards hold degree {idx.nbr.shape[-1]}, "
                             f"not the deployment's {CONFIG.degree}")
    mesh = make_host_mesh(model_parallel=shards)
    rec, sels = [], (0.01, 0.1, 0.5)    # 0.5: graph plans on every shard
    for i, sel in enumerate(sels):
        qv = make_queries_vectors(Sizes.batch, CONFIG.dim, seed=seed + 1 + i)
        qs = ground_truth(generate_queries(
            qv, s, t, CONFIG.relation, sel, k=CONFIG.k, seed=seed + i),
            vecs, s, t)
        ids, _ = serve_batch(idx, mesh, qs.vectors, qs.s_q, qs.t_q,
                             k=CONFIG.k, beam=CONFIG.beam, plan="auto")
        rec.append(float(recall(ids, qs.gt_ids).mean()))
    log(phase="serve", selectivities=list(sels), recall=rec)
    dev = idx.device(mesh)
    holders = sorted({sh.device.id for sh in dev["vectors"].addressable_shards})
    per_dev = {d.id: {k: (d.memory_stats() or {}).get(k) for k in
                      ("bytes_in_use", "peak_bytes_in_use")}
               for d in jax.devices()[:shards]}
    shard_shape = dev["vectors"].addressable_shards[0].data.shape
    log(phase="memory", vector_shard_shape=tuple(shard_shape),
        shard_devices=holders, memory_per_device=per_dev)
    if len(holders) != shards or shard_shape[0] != 1:
        raise AssertionError(f"index is not spread one shard per device: "
                             f"{holders} {shard_shape}")
    if min(rec) < Sizes.min_recall:
        raise AssertionError(f"recall@k below {Sizes.min_recall}: {rec}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path, on four chips")
    ap.add_argument("--n", type=int, default=Sizes.n,
                    help="objects loaded on one chip (default %(default)s)")
    ap.add_argument("--n-local", type=int, default=2048,
                    help="objects per shard with --four-chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    require_tpu(4 if args.four_chips else 1)

    from repro.launch.compile_cache import enable_compile_cache

    clock = CompileClock()
    log(phase="setup", compile_cache=enable_compile_cache(),
        full_n=CONFIG.n_per_shard)
    if args.four_chips:
        if args.n_local < CONFIG.n_per_shard:
            log(cut="n_local", n_local=args.n_local, full=CONFIG.n_per_shard)
        four_chips(args.n_local, args.seed)
    else:
        if args.n < CONFIG.n_per_shard:
            log(cut="n", n=args.n, full=CONFIG.n_per_shard)
        one_chip(Sizes(n=args.n), args.seed, clock)
    log(phase="compile", compile_seconds=clock.seconds,
        cache_hits=clock.hits, cache_misses=clock.misses)
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
