"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

(``python -m benchmarks.chip.run`` from the checkout's root is the same.)
Sets the cell up from its files (see ``harness.py``), measures for
``--seconds``, checks every answer against the plain reference, and prints
as its last line of standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit,
which also close standard error. Earlier lines name the device and give the
set-up split, the plan mix, compiles inside the window and memory.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell needs, or when the program is not beside the benchmark.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
if not (ROOT / "src" / "repro").is_dir():
    sys.exit(f"benchmark: the program (src/repro) is not in {ROOT}")
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = harness.run_cell(args.workload, seed=args.seed,
                               seconds=args.seconds, trace=bool(args.trace),
                               t_start=T_START)
    except harness.NoChip as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 3
    for name, c in out.checks.items():
        print(f"check {name}={c['value']} limit={c['limit']}", file=sys.stderr)
    print(json.dumps(out.line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
