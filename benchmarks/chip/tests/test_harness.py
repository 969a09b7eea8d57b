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
CHECKS = ["missing", "bad", "stale", "dist_err", "compiles_in_window", "lost"]
CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]
# per-layer metrics read from the search loops' counters
LOOP_METRICS = {"search.row_active_pct.closed", "search.gather_useful_pct.closed",
                "search.gather_fetched_pct.closed"}


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
    assert list(line["checks"]) == CHECKS
    want = out.run.cell.per_layer if trace else out.run.cell.end_to_end
    names = {m["name"] for m in want}
    got = set(line["metrics"])
    if trace:
        # the gather roofline needs the chip's peaks and kernel
        names.discard("kernel.filter_dist_gather_packed_roofline")
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        if not out.run.plans["GRAPH"] + out.run.plans["GRAPH_WIDE"]:
            # at this size every row went to the brute path: the loops'
            # counters hold only what earlier runs in this process left
            names -= LOOP_METRICS
            got -= LOOP_METRICS
    assert got == names
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])


def test_a_cell_of_new_files_is_found(tmp_path):
    tree = shrink(copy_tree(tmp_path))
    home = tree / "benchmarks" / "chip"
    cfg = json.loads((home / "configs" / "sift128-overlap.json").read_text())
    cfg.update(relation="both_after", intervals="uniform")
    (home / "configs" / "new-conf.json").write_text(json.dumps(cfg))
    (home / "traffic" / "new.mix.json").write_text(json.dumps(dict(
        loop="closed", outstanding=32, selectivities=[0.1, 0.5], pool=64,
        writes=dict(insert_qps=20, delete_qps=10, objects="new.rising",
                    victims="oldest"))))
    (home / "metrics" / "new.answered.py").write_text(
        "def read(run):\n    return float(len(run.due))\n")
    (home / "streams" / "new.rising.py").write_text(
        "import numpy as np\n"
        "def make(cfg, writes, seed, count):\n"
        "    rng = np.random.default_rng([seed, 6])\n"
        "    x = rng.normal(size=(count, cfg['dim'])).astype(np.float32)\n"
        "    s = np.linspace(900, 990, count).astype(np.float32)\n"
        "    return x, s.astype(np.float64), s.astype(np.float64) + 8.0\n")
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
    # the writes came from the new generator: rising lifespans at the end
    rec = out.run.writes
    assert rec.failed == 0 and len(rec.kind) == 45
    assert out.reference.s_host[-1] == pytest.approx(990.0)
    out = run(tree, "new.cell", True)
    assert set(out.line["metrics"]) == {"new.answered"}


class _Server:
    """Serves the oldest ``batch`` pending requests per step, a few ms
    each, noting which thread submits and serves and how many wait."""

    def __init__(self, batch):
        import threading

        self.batch, self.pending, self.next = batch, [], 0
        self.threads, self.waiting = set(), []
        self._current = threading.current_thread

    def submit(self, *_):
        self.threads.add(self._current().name)
        self.pending.append(self.next)
        self.next += 1
        return self.next - 1

    def step(self):
        self.threads.add(self._current().name)
        self.waiting.append(len(self.pending))
        time.sleep(0.002)
        rids, self.pending = self.pending[:self.batch], self.pending[self.batch:]
        return {r: (None, None) for r in rids}


def test_closed_loop_refills_from_the_serving_thread():
    import threading

    import numpy as np

    with harness._annotate("warm"):        # jax's first import is slow
        pass
    server = _Server(batch=8)
    log = harness.closed_loop(server, lambda i: (i,), np.arange(40),
                              outstanding=16, seconds=0.1)
    steps = len(log.batches)
    assert steps > 3
    # one thread submits and serves; every step finds the full load
    assert server.threads == {threading.current_thread().name}
    assert server.waiting == [16] * steps
    # each step's answers are refilled, due at that step's end
    assert len(log.rid) == 16 + 8 * steps
    ends = [b[1] for b in log.batches]
    assert list(log.due[16:]) == [e for e in ends for _ in range(8)]
    assert log.window_end == ends[-1]


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
