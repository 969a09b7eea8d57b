"""Readings the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1,2,3 \\
        --seconds <s> [--out <file.jsonl>]

For each seed, in one process: one run of the cell as the benchmark makes
it (set-up, window, check), whose numbers compared are the program's
reading; then the control, the reference put in the program's place and
computed in bfloat16 (the step below the configuration's float32), for
the same requests, held to the same comparison. The control has to come
out not correct. One JSON line per seed, on standard output and in
``--out``.
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

from benchmarks.chip import harness, reference  # noqa: E402


def control_verdict(out: harness.Outcome) -> reference.Verdict:
    """The comparison of ``out``'s requests answered by the reference in
    bfloat16 instead of by the program."""
    k = int(out.run.cell.config["k"])
    n = int(out.run.cell.config["n_per_shard"])
    pool, log = out.pool, out.log
    used = np.unique(log.pool_idx)
    low = out.reference.topk(pool.q[used], pool.s_q[used], pool.t_q[used], k,
                             precision="bfloat16")
    at = np.searchsorted(used, log.pool_idx)
    return reference.compare(out.reference, out.truth, log.pool_idx,
                             pool.q, pool.s_q, pool.t_q, low.ids[at],
                             low.d[at], missing=0, n=n)


def main(argv=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one set-up each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    rows = []
    start = T_START
    for seed in (int(x) for x in args.seeds.split(",")):
        out = harness.run_cell(args.workload, seed=seed, seconds=args.seconds,
                               trace=False, t_start=start)
        limits = out.run.cell.config["limits"]
        ctl = control_verdict(out)
        row = {"workload": args.workload, "seed": seed,
               "correct": out.line["correct"],
               "program": out.checks,
               "control": ctl.checks(limits),
               "control_correct": ctl.correct(limits),
               "metrics": out.line["metrics"],
               "memory_peak_bytes": out.line["device"]["memory_peak_bytes"]}
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
        rows.append(row)
        del out
        start = time.perf_counter()
    return rows


if __name__ == "__main__":
    try:
        main()
    except harness.NoChip as exc:
        sys.exit(f"control: {exc}")
