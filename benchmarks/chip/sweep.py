"""Find an open-loop cell's knee on the chip: one set-up, then a window
at each offered rate.

    python3 benchmarks/chip/sweep.py --workload <name> --seed <n> \\
        --rates 250,500,1000 --seconds 10

For each rate: the requests offered and answered by the window's close,
the backlog left at the close, and the median and 99th percentile of
latency from due time. The knee is the highest rate whose backlog does not
grow over the window: at the close no more than two batches (the one being
served and the one filling) are left unanswered.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from benchmarks.chip import harness  # noqa: E402


def main(argv=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    su = harness.set_up(cell, seed=args.seed, t_start=T_START)
    B = int(cell.config["batch"])
    order = np.random.default_rng([args.seed, 4]).permutation(len(su.pool))
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        log = harness.open_loop(su.server, su.request, order,
                                harness.arrivals(rate, args.seconds, args.seed),
                                seconds=args.seconds)
        close = log.window_start + args.seconds
        done = np.array([log.answers[int(r)][2] if int(r) in log.answers
                         else np.nan for r in log.rid])
        lat = np.where(np.isnan(done), np.inf, done - log.due) * 1e3
        by_close = int(np.count_nonzero(done <= close))
        fills = [n for _, _, n in log.batches]
        row = {"rate": rate, "offered": len(log.due), "answered_by_close":
               by_close, "backlog_at_close": len(log.due) - by_close,
               "p50_ms": float(np.percentile(lat, 50)),
               "p99_ms": float(np.percentile(lat, 99)),
               "batches": len(fills),
               "fill_pct": 100.0 * sum(fills) / max(len(fills) * B, 1),
               "sustained": len(log.due) - by_close <= 2 * B}
        harness.log(phase="sweep", **row)
        rows.append(row)
    ok = [r["rate"] for r in rows if r["sustained"]]
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "knee_qps": max(ok) if ok else None, "rows": rows}))
    return rows


if __name__ == "__main__":
    try:
        main()
    except harness.NoChip as exc:
        sys.exit(f"sweep: {exc}")
