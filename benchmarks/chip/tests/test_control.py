"""``correct`` comes out false for the control and for each fault a
one-chip serving cell can have, with the harness's look for a chip
skipped and the timed path broken underneath."""
import time

import numpy as np
import pytest

from benchmarks.chip import control, harness
from repro.serve.batching import StreamingServer
from repro.stream.index import StreamingIndex

CELLS = ["rag768.contain.mix.closed", "sift128.overlap.mix.open"]


def run(tree, cell):
    return harness.run_cell(cell, seed=2**31 + 29, seconds=1.0, trace=False,
                            t_start=time.perf_counter(), root=tree,
                            require_chip=False)


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(tiny_tree, cell):
    out = run(tiny_tree, cell)
    assert out.line["correct"] is True
    ctl = control.control_verdict(out)
    limits = out.run.cell.config["limits"]
    assert not ctl.correct(limits)
    assert ctl.dist_err > 10 * out.checks["dist_err"]["value"]


def _altered(search):
    """An answer altered where it is produced: each row's nearest id is
    swapped for its second, distances kept."""
    def wrapped(self, *a, **kw):
        ids, d = search(self, *a, **kw)
        ids = ids.copy()
        ids[:, [0, 1]] = ids[:, [1, 0]]
        return ids, d
    return wrapped


def _half_dropped(step):
    """Half of each batch left out of the step's answers."""
    def wrapped(self, *a, **kw):
        out = step(self, *a, **kw)
        keep = sorted(out)[: (len(out) + 1) // 2]
        return {r: out[r] for r in keep}
    return wrapped


def _stale(step):
    """A step that returns its state unchanged: every answer after the
    first batch is the first batch's answer of the same position."""
    first = []

    def wrapped(self, *a, **kw):
        out = step(self, *a, **kw)
        if out and not first:
            first.extend(out.values())
        if first:
            out = {r: first[i % len(first)] for i, r in enumerate(sorted(out))}
        return out
    return wrapped


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["altered", "half_dropped", "stale"])
def test_each_fault_is_not_correct(tiny_tree, cell, fault, monkeypatch):
    if fault == "altered":
        monkeypatch.setattr(StreamingIndex, "search",
                            _altered(StreamingIndex.search))
    elif fault == "half_dropped":
        monkeypatch.setattr(StreamingServer, "step",
                            _half_dropped(StreamingServer.step))
        monkeypatch.setattr(harness, "DRAIN_SECONDS", 2.0)
    else:
        monkeypatch.setattr(StreamingServer, "step",
                            _stale(StreamingServer.step))
    out = run(tiny_tree, cell)
    assert out.line["correct"] is False
    c = out.checks
    broken = [k for k in c if c[k]["value"] > c[k]["limit"]]
    assert broken, c
