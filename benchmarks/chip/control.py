"""Readings the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1,2,3 \\
        --seconds <s> [--out <file.jsonl>]

One set-up, then one measured window per seed, as the benchmark measures
it; then, with the server freed, each window's answers are checked, whose
numbers compared are the program's reading, and the control, the
reference put in the program's place and computed in bfloat16 (the step
below the configuration's float32), for the same requests, held to the
same comparison. The control has to come out not correct. One JSON line
per seed, on standard output and in ``--out``. A mix that writes gets one
seed per call (its window changes the data).
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

from benchmarks.chip import harness, reference  # noqa: E402


def control_verdict(out: harness.Outcome) -> reference.Verdict:
    """The comparison of ``out``'s answered requests answered by the
    reference in bfloat16 instead of by the program."""
    k = int(out.run.cell.config["k"])
    q, s_q, t_q, version = out.queries
    ref = out.reference
    low = ref.topk(q, s_q, t_q, k, precision="bfloat16", version=version)
    return reference.compare(ref, out.truth, out.at, q, s_q, t_q,
                             ref.ids(low.ids[out.at]), low.d[out.at],
                             missing=0, start=out.start, end=out.end)


def main(argv=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one window each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(x) for x in args.seeds.split(",")]
    cell = harness.load_cell(args.workload)
    su = harness.set_up(cell, seed=seeds[0], t_start=T_START,
                        seconds=args.seconds)
    rows = []
    try:
        windows = [harness.measure(su, seed=seed, seconds=args.seconds)
                   for seed in seeds]
        su.free()
        ref = None
        for win in windows:
            out = harness.check(su, win, ref=ref)
            ref = out.reference if cell.writes is None else None
            limits = cell.config["limits"]
            ctl = control_verdict(out)
            row = {"workload": args.workload, "seed": win.seed,
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
    finally:
        su.close()
    return rows


if __name__ == "__main__":
    try:
        main()
    except harness.NoChip as exc:
        sys.exit(f"control: {exc}")
