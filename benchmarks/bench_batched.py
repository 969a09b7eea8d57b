"""Batched device search: packed-metadata superkernel vs fused vs unfused.

Measures the jitted lockstep beam search across its three loop structures —

  unfused   XLA gathers a [B, E, D] candidate tensor per iteration, dense
            [B, n] bool visited, per-iteration norm recompute;
  fused     PR 2's gather-fused kernel (in-kernel HBM row DMA, cached
            norms, bit-packed visited) with int32 [n, E, 4] labels gathered
            on the XLA side and an argsort-dedup + stable lax.sort merge;
  packed    the packed-metadata superkernel: bit-packed [n, E, 2] uint32
            label rectangles DMA'd in-kernel (no XLA-side label gather at
            all), matrix dedup + top-L beam-merge primitive instead of the
            argsort + full stable sort (``packed_x4`` adds multi-expand) —

and emits both the usual CSV lines and a machine-readable
``BENCH_search.json`` at the repo root: QPS, p50/p99 batch latency,
recall@10, XLA-visible bytes moved per search iteration (HLO cost-analysis
delta between 1- and 2-iteration unrolled probes), an analytic per-iteration
label-traffic model, and jaxpr checks that the fused paths have no
``[B, M·E, D]`` candidate intermediate and the packed path additionally has
no label-gather intermediate of either layout.

Regression gates (asserted on every run, including the CI ``--tiny``
smoke): packed recall@10 is bit-identical to the ``fused=False`` parity
oracle at every sweep point, packed label bytes/iter are exactly one
packed label row per expanded node (``repro.kernels.layout
.label_row_bytes``: 10.67 B/edge at E = 96 or 48 against the int32
layout's 16),
and packed QPS >= the unpacked fused path. The full-scale run
additionally gates the tentpole acceptance: packed ``xla_bytes_per_iter``
<= 0.6x the fused path and packed QPS >= 1.15x fused at sigma = 0.1, and
the telemetry overhead: ``stats=True`` (device-side traversal counters)
QPS >= 0.95x ``stats=False``. Latency quantiles (p50/p90/p99) are computed
through the ``repro.obs`` histogram — the same estimator the serving stack
exports to Prometheus.

On this CPU container wall-clock timing uses the jnp oracles
(``use_ref=True`` — interpret-mode Pallas is a Python emulation, not a perf
signal); the bytes/jaxpr probes inspect the compiled Pallas variants, where
the structural distinctions are backend-independent.

``--tiny`` (or ``main(tiny=True)``) shrinks everything for the CI smoke run.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import numpy as np

from benchmarks.common import (
    dataset,
    emit,
    get_method,
    latency_percentiles,
    queries,
)
from repro.core import EntryTable
from repro.data import recall_at_k
from repro.kernels.layout import label_row_bytes
from repro.search import batched_udg_search, export_device_graph, prepare_states
from repro.search.batched import _batched_search_core

JSON_PATH = Path(__file__).resolve().parent.parent / "BENCH_search.json"

STATS_QPS_FLOOR = 0.95   # stats=True QPS >= this x stats=False (full scale)


def _core_args(dg, qs, *, layout):
    """Jitted-core positional args with the config's label layout."""
    import jax.numpy as jnp

    states, ep = prepare_states(dg, qs.s_q, qs.t_q)
    dev = dg.device()
    labels = dev.labels if layout == "packed" else dg.device_labels_i32()
    return (
        dev.table, dev.nbr, labels,
        jnp.asarray(np.asarray(qs.vectors, np.float32)),
        jnp.asarray(states), jnp.asarray(ep),
    )


def _cost_bytes(args, norms, *, fused, expand, beam, unroll):
    """XLA-visible 'bytes accessed' of an ``unroll``-iteration probe."""
    lowered = _batched_search_core.lower(
        *args, k=10, beam=beam, max_iters=2 * beam, use_ref=False,
        fused=fused, expand=expand, unroll_iters=unroll,
        norms=norms if fused else None,
    )
    cost = lowered.compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(dict(cost or {}).get("bytes accessed", 0.0))


def _intermediates_in_jaxpr(args, norms, *, fused, expand, beam):
    """(has [B,M·E,D] f32 candidates, has a label gather: [B,M·E,{2,4}]
    rectangles or words, or [B,M,1,W] packed label rows)."""
    B, D = args[3].shape
    E = args[1].shape[1]
    W = args[2].shape[-1]
    jaxpr = str(jax.make_jaxpr(
        lambda *a: _batched_search_core(
            *a, k=10, beam=beam, max_iters=2 * beam, use_ref=False,
            fused=fused, expand=expand, unroll_iters=1,
            norms=norms if fused else None,
        )
    )(*args))
    me = expand * E
    has_bed = f"f32[{B},{me},{D}]" in jaxpr
    has_lab = (f"i32[{B},{me},4]" in jaxpr or f"s32[{B},{me},4]" in jaxpr
               or f"u32[{B},{me},2]" in jaxpr
               or f"i32[{B},{expand},1,{W}]" in jaxpr
               or f"i32[{B},1,{W}]" in jaxpr)
    return has_bed, has_lab


def _timed(dg, qs, *, beam, repeats, **kw):
    """(recall@10, qps, {p50,p90,p99}_ms) of the jitted end-to-end search.

    Latency quantiles come from the ``repro.obs`` histogram (the serving
    stack's Prometheus estimator — see ``latency_percentiles``); QPS keeps
    the exact sample median so the packed-vs-fused gate doesn't inherit
    bucket-interpolation error."""
    run = lambda: batched_udg_search(
        dg, qs.vectors, qs.s_q, qs.t_q, k=10, beam=beam, use_ref=True, **kw
    )
    out = run()  # warm up (compile)
    lat = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        run()
        lat.append(time.perf_counter() - t0)
    lat = np.array(lat)
    # QPS from the median batch latency — robust to scheduler stragglers on
    # the shared CPU host, so the packed-vs-fused gate doesn't flap in CI
    return (
        float(recall_at_k(out[0], qs)),
        float(qs.nq / np.percentile(lat, 50)),
        latency_percentiles(lat),
    )


def _stats_overhead(dg, qs, *, beam, repeats):
    """QPS of the packed search with and without device-side traversal
    counters, measured with interleaved (paired) repeats. The counters are
    folded into values the loop already carries, so the overhead budget is
    tight: stats-on must hold >= ``STATS_QPS_FLOOR`` x stats-off."""
    runs = {
        onoff: (lambda st=st: batched_udg_search(
            dg, qs.vectors, qs.s_q, qs.t_q, k=10, beam=beam, use_ref=True,
            stats=st,
        ))
        for onoff, st in (("off", False), ("on", True))
    }
    for run in runs.values():   # warm up both cache entries
        run()
        run()
    lat = {name: [] for name in runs}
    for _ in range(repeats):
        for name, run in runs.items():
            t0 = time.perf_counter()
            run()
            lat[name].append(time.perf_counter() - t0)
    qps = {name: float(qs.nq / np.median(v)) for name, v in lat.items()}
    return {
        "qps_stats_off": round(qps["off"], 2),
        "qps_stats_on": round(qps["on"], 2),
        "qps_ratio_on_vs_off": round(qps["on"] / max(qps["off"], 1e-9), 4),
        **{f"stats_on_{k}": v
           for k, v in latency_percentiles(lat["on"]).items()},
    }


def main(tiny: bool = False) -> None:
    if tiny:
        n, dim, nq, beam, repeats = 600, 16, 16, 32, 7
    else:
        n, dim, nq, beam, repeats = None, None, None, 64, 7
    if tiny:
        vecs, s, t = dataset("uniform", n, dim)
        m = get_method("udg", "containment", data_key=("uniform", n, dim, 0),
                       M=8, Z=32, K_p=4)
    else:
        vecs, s, t = dataset()
        m = get_method("udg", "containment", M=16, Z=64, K_p=8)
    dg = export_device_graph(m.g, EntryTable(m.g))
    assert dg.plabels is not None, "benchmark grids must fit 16-bit ranks"
    norms = dg.device().norms

    record = {
        "bench": "batched_search",
        "n": dg.n, "dim": dg.vectors.shape[1], "E": dg.max_degree,
        "beam": beam, "tiny": tiny,
        "label_bytes_per_edge": {
            "packed": round(label_row_bytes(dg.max_degree) / dg.max_degree,
                            4),
            "int32": 16},
        "configs": {},
    }
    B, E, D = None, dg.max_degree, dg.vectors.shape[1]
    configs = [
        ("unfused", "int32", dict(fused=False, expand=1)),
        ("fused", "int32", dict(fused=True, expand=1, packed=False)),
        ("packed", "packed", dict(fused=True, expand=1, packed=True)),
        ("packed_x4", "packed", dict(fused=True, expand=4, packed=True)),
    ]
    for sigma in (0.01, 0.1) if not tiny else (0.1,):
        qs = queries(vecs, s, t, "containment", sigma,
                     nq=nq if tiny else 32)
        B = qs.nq
        # canonicalize + stage the probe operands once per label layout
        layout_args = {lay: _core_args(dg, qs, layout=lay)
                       for lay in ("int32", "packed")}
        for name, layout, kw in configs:
            rec, qps, pcts = _timed(dg, qs, beam=beam, repeats=repeats, **kw)
            args = layout_args[layout]
            core_kw = {k: v for k, v in kw.items() if k != "packed"}
            # per-iteration XLA-visible traffic: 2-iter minus 1-iter probe
            b1 = _cost_bytes(args, norms, beam=beam, unroll=1, **core_kw)
            b2 = _cost_bytes(args, norms, beam=beam, unroll=2, **core_kw)
            per_iter = b2 - b1
            has_bed, has_lab = _intermediates_in_jaxpr(
                args, norms, beam=beam, **core_kw)
            M = kw["expand"]
            # analytic HBM traffic models, per iteration:
            #   vectors — unfused round-trips a [B,E,D] tensor; the fused
            #   paths read M*E rows once via in-kernel DMA (+12 B of norm /
            #   visited word / scale metadata per candidate);
            #   labels — 16 B/edge for the int32 layout (XLA gather), one
            #   packed label row per expanded node (in-kernel DMA).
            row = D * 4
            # derived from the label array the config ACTUALLY stages (not
            # a constant), so a silent fallback to the int32 layout on the
            # packed config fails the 0.5x gate below
            lab_arr = args[2]
            if layout == "packed":
                lab_bytes = B * M * lab_arr.shape[-1] * lab_arr.dtype.itemsize
            else:
                lab_bytes = B * M * E * lab_arr.shape[-1] * lab_arr.dtype.itemsize
            analytic = (
                B * M * E * (row + 12) if kw["fused"]
                else B * E * (2 * row) + 2 * B * dg.n
            )
            key = f"sel{sigma}.{name}"
            record["configs"][key] = {
                "fused": kw["fused"], "expand": M, "batch": B,
                "label_layout": layout,
                "recall_at_10": round(rec, 4),
                "qps": round(qps, 2),
                **pcts,
                "xla_bytes_per_iter": per_iter,
                "analytic_gather_bytes_per_iter": analytic,
                "label_bytes_per_iter": lab_bytes,
                "bed_intermediate_in_jaxpr": has_bed,
                "label_gather_in_jaxpr": has_lab,
            }
            emit(
                f"batched.containment.sel{sigma}.{name}",
                1e6 / qps, recall=round(rec, 4), qps=round(qps, 1),
                p99_ms=pcts["p99_ms"], iter_bytes=int(per_iter),
            )
        un = record["configs"][f"sel{sigma}.unfused"]
        fu = record["configs"][f"sel{sigma}.fused"]
        pk = record["configs"][f"sel{sigma}.packed"]
        record["configs"][f"sel{sigma}.summary"] = {
            "qps_speedup_fused_vs_unfused": round(
                fu["qps"] / max(un["qps"], 1e-9), 3),
            "qps_speedup_packed_vs_fused": round(
                pk["qps"] / max(fu["qps"], 1e-9), 3),
            "xla_bytes_reduction_per_iter": round(
                1.0 - fu["xla_bytes_per_iter"] / max(un["xla_bytes_per_iter"], 1e-9), 4),
            "xla_bytes_ratio_packed_vs_fused": round(
                pk["xla_bytes_per_iter"] / max(fu["xla_bytes_per_iter"], 1e-9), 4),
            "label_bytes_ratio_packed_vs_fused": round(
                pk["label_bytes_per_iter"] / max(fu["label_bytes_per_iter"], 1e-9), 4),
        }
    # structural acceptance: no fused jaxpr materializes [B, M*E, D], and
    # the packed superkernel additionally has NO label-gather intermediate
    for k, c in record["configs"].items():
        if k.endswith(".summary"):
            continue
        if c["fused"]:
            assert not c["bed_intermediate_in_jaxpr"], (
                f"{k}: fused path materialized a [B, M*E, D] intermediate")
        if c["label_layout"] == "packed":
            assert not c["label_gather_in_jaxpr"], (
                f"{k}: packed path gathered labels on the XLA side")
    # regression gates (every run, incl. CI --tiny): the packed superkernel
    # must not lose recall vs the parity oracle, must read exactly one
    # packed label row per expanded node, and must not be slower than the
    # unpacked fused path. The tiny smoke
    # applies a noise floor to the wall-clock gate — a 16-query batch over
    # 600 nodes on the shared CI host jitters by more than the strict
    # comparison tolerates (measured packed/fused ratio is ~1.5x even at
    # tiny scale; 0.9 only filters scheduler noise, not regressions)
    qps_floor = 0.9 if tiny else 1.0
    for sigma in (0.01, 0.1) if not tiny else (0.1,):
        un = record["configs"][f"sel{sigma}.unfused"]
        fu = record["configs"][f"sel{sigma}.fused"]
        pk = record["configs"][f"sel{sigma}.packed"]
        sm = record["configs"][f"sel{sigma}.summary"]
        assert pk["recall_at_10"] == un["recall_at_10"], (
            f"sel{sigma}: packed recall {pk['recall_at_10']} != "
            f"unfused oracle {un['recall_at_10']}")
        assert sm["label_bytes_ratio_packed_vs_fused"] <= round(
            label_row_bytes(E) / (16 * E), 4), sm
        assert pk["qps"] >= qps_floor * fu["qps"], (
            f"sel{sigma}: packed {pk['qps']} QPS < {qps_floor}x "
            f"fused {fu['qps']}")
    if not tiny:
        # tentpole acceptance on the benchmark host (sigma = 0.1)
        sm = record["configs"]["sel0.1.summary"]
        assert sm["xla_bytes_ratio_packed_vs_fused"] <= 0.6, sm
        assert sm["qps_speedup_packed_vs_fused"] >= 1.15, sm
    # device-side traversal counters must be ~free: stats=True is the same
    # loop with a handful of mask reductions folded in (no extra gathers,
    # no host sync), so serving can leave telemetry on. Gated at full
    # scale; the tiny smoke records the ratio but a 16-query batch on the
    # shared CI host jitters past any honest threshold.
    record["stats_overhead"] = _stats_overhead(
        dg, queries(vecs, s, t, "containment", 0.1, nq=nq if tiny else 32),
        beam=beam, repeats=repeats,
    )
    if not tiny:
        assert (
            record["stats_overhead"]["qps_ratio_on_vs_off"]
            >= STATS_QPS_FLOOR
        ), record["stats_overhead"]
    JSON_PATH.write_text(json.dumps(record, indent=2) + "\n")
    print(f"# wrote {JSON_PATH}", flush=True)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke scale (small corpus, one selectivity)")
    main(tiny=ap.parse_args().tiny)
