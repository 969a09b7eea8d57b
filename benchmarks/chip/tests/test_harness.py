"""The harness end to end on the CPU at a tiny size: every cell's path,
a cell added by files alone, and the exits without a chip or a program."""
import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks.chip import harness

from conftest import ROOT, copy_tree, shrink

LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


def run(tree, cell, trace, seed=2**31 + 17):
    return harness.run_cell(cell, seed=seed, seconds=1.5, trace=trace,
                            t_start=time.perf_counter(), root=tree,
                            require_chip=False)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end(tiny_tree, cell, trace):
    out = run(tiny_tree, cell, bool(trace))
    line = out.line
    assert [k for k in line if k != "breakdown"] == LINE_KEYS
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line["checks"]) == ["missing", "bad", "dist_err",
                                    "compiles_in_window"]
    want = out.run.cell.per_layer if trace else out.run.cell.end_to_end
    names = {m["name"] for m in want}
    if trace:
        # the gather roofline needs the chip's peaks and kernel
        names.discard("kernel.filter_dist_gather_packed_roofline")
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert set(line["metrics"]) == names
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])


def test_a_cell_of_new_files_is_found(tmp_path):
    tree = shrink(copy_tree(tmp_path))
    home = tree / "benchmarks" / "chip"
    cfg = json.loads((home / "configs" / "sift128-overlap.json").read_text())
    cfg.update(relation="both_after", intervals="uniform")
    (home / "configs" / "new-conf.json").write_text(json.dumps(cfg))
    (home / "traffic" / "new.mix.json").write_text(json.dumps(dict(
        loop="closed", outstanding=32, selectivities=[0.1, 0.5], pool=64)))
    (home / "metrics" / "new.answered.py").write_text(
        "def read(run):\n    return float(len(run.due))\n")
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="new-conf",
                                 file="benchmarks/chip/configs/new-conf.json"))
    bench["workloads"].append(dict(name="new.cell", config="new-conf",
                                   traffic="new.mix", chips=1, why="test"))
    for m in bench["end_to_end"]:
        if m["name"] == "qps":
            m["workloads"].append("new.cell")
    bench["per_layer"].append(dict(
        name="new.answered", unit="count", better="higher",
        source="host_clock", layer="load generator", moves="qps",
        workloads=["new.cell"]))
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))
    out = run(tree, "new.cell", False)
    assert out.line["correct"] is True
    assert set(out.line["metrics"]) == {"qps", "setup_s"}
    out = run(tree, "new.cell", True)
    assert set(out.line["metrics"]) == {"new.answered"}


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    p = _command(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[-1] \
        .startswith("{")
    assert "TPU" in p.stderr


def test_benchmark_alone_does_not_run(tmp_path):
    copy_tree(tmp_path)
    p = _command(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
