"""The served step's phase spans, read back from a profiler trace the way
the chip benchmark reads them, and the benchmark's readers of those spans
and of the always-on loop counters."""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness, tracing  # noqa: E402
from repro.data import make_dataset  # noqa: E402
from repro.obs import MetricsRegistry, metrics  # noqa: E402
from repro.serve.batching import StreamingServer  # noqa: E402
from repro.stream import StreamingIndex  # noqa: E402

PHASES = ("serve_step.batch", "serve_step.canonicalize", "serve_step.plan",
          "serve_step.upload", "serve_step.dispatch", "serve_step.fetch",
          "serve_step.reply")
B = 4


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two served steps (and one that found no batch) under the profiler,
    after a warm-up step that compiled the program."""
    vecs, s, t = make_dataset(160, 8, seed=31)
    idx = StreamingIndex(8, "overlap", node_capacity=256, delta_capacity=64,
                         edge_capacity=48, M=6, Z=24)
    idx.insert_batch(vecs[:150], s[:150], t[:150])
    idx.compact()
    srv = StreamingServer(idx, batch_size=B, k=4, beam=16, timeout_s=0.0,
                          registry=MetricsRegistry())
    lo, hi = float(s.min()), float(t.max())

    def submit(n):
        for i in range(n):
            srv.submit(vecs[i], lo, lo + (hi - lo) * (i + 1) / n)

    submit(B)
    srv.step()
    logdir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(logdir), profiler_options=tracing.options())
    try:
        for _ in range(2):
            submit(B)
            assert len(srv.step()) == B
        assert srv.step() == {}
    finally:
        jax.profiler.stop_trace()
    return tracing.load(str(logdir))


def test_phase_spans_tile_each_served_step(traced):
    steps = sorted(traced.spans("serve_step"))
    assert len(steps) == 3
    served = 0
    for a, b in steps:
        inside = sorted((s, e, nm) for nm, s, e in traced.host_spans
                        if nm.startswith("serve_step.") and a <= s and e <= b)
        if len(inside) == 1:          # the step that found no batch
            assert inside[0][2] == "serve_step.batch"
            continue
        served += 1
        assert tuple(nm for _, _, nm in inside) == PHASES
        edges = [a] + [x for s, e, _ in inside for x in (s, e)] + [b]
        glue = [edges[i + 1] - edges[i] for i in range(0, len(edges), 2)]
        assert min(glue) >= 0                 # disjoint, in order
        # what the phases leave uncovered is the glue between them and the
        # parent span's own histogram update (2-4% of a step here); a
        # phase of real work left out of every span would be more
        assert sum(glue) <= 0.15 * (b - a), glue
    assert served == 2


# --- the benchmark's readers --------------------------------------------------


HOME = ROOT / "benchmarks" / "chip"


def made_up_run(trace=None, cell="rag768.contain.mix.closed", **kw):
    fields = dict(setup_s=0.0, window_start=0.0, window_end=1.0,
                  batch_size=8, due=None, submitted=None, done=None,
                  batches=[], recall=None, trace=trace)
    fields.update(kw)
    return harness.Run(cell=harness.load_cell(cell, ROOT), **fields)


@pytest.fixture
def registry(monkeypatch):
    """A fresh process registry, which the readers read."""
    reg = MetricsRegistry()
    monkeypatch.setattr(metrics, "_GLOBAL", reg)
    return reg


def test_loop_counter_readers(registry):
    from repro.obs import record_loop_totals

    row_active = harness.reader(HOME, "search.row_active_pct.closed")
    useful = harness.reader(HOME, "search.gather_useful_pct.closed")
    fetched = harness.reader(HOME, "search.gather_fetched_pct.closed")
    run = made_up_run()
    assert row_active(run) is None and useful(run) is None
    assert fetched(run) is None
    # graph loop: 10 trips, 25 row iterations, 300 kept, 700 rows fetched,
    # M*E = 16; wide loop: 4 trips, 12 row iterations, 100 kept, 500
    # fetched, M*E = 64; B = 8
    record_loop_totals(np.array([[80, 25, 80 * 16, 300, 700],
                                 [32, 12, 32 * 64, 100, 500]]),
                       plans=("GRAPH", "GRAPH_WIDE"), registry=registry)
    assert registry.counter("repro_search_rows_fetched_total").value(
        plan="GRAPH") == 700
    assert row_active(run) == pytest.approx(100 * 37 / (80 + 32))
    assert useful(run) == pytest.approx(100 * 400 / (80 * 16 + 32 * 64))
    assert fetched(run) == pytest.approx(100 * 1200 / (80 * 16 + 32 * 64))


def test_queue_wait_reader():
    read = harness.reader(HOME, "batching.queue_wait_ms.open")
    open_cell = "sift128.overlap.mix.open"
    # steps (start, end, rows): three answered while the trace ran, one
    # after the tracer stopped at 0.5
    batches = [(0.10, 0.20, 2), (0.25, 0.40, 1), (0.41, 0.45, 1),
               (0.70, 0.90, 1)]
    submitted = np.array([0.01, 0.07, 0.12, 0.412, 0.30, 0.95])
    done = np.array([0.20, 0.20, 0.40, 0.45, 0.90, np.nan])
    run = made_up_run(cell=open_cell, submitted=submitted, done=done,
                      batches=batches, traced_until=0.5)
    # waits 0.09, 0.03, 0.13 and none (submitted after its step started)
    assert read(run) == pytest.approx((0.09 + 0.03 + 0.13 + 0) / 4 * 1e3)
    # untraced: every answered request, the one after 0.5 too (0.40)
    run.traced_until = None
    assert read(run) == pytest.approx((0.09 + 0.03 + 0.13 + 0.40) / 5 * 1e3)
    assert read(made_up_run(cell=open_cell)) is None
    # a closed-loop cell has no such wait
    assert read(made_up_run(submitted=submitted, done=done,
                            batches=batches)) is None


def test_prepare_reader():
    read = harness.reader(HOME, "serve_step.prepare_ms.closed")
    ops = [tracing.Op("a", 20, 60, 0), tracing.Op("b", 130, 170, 0)]
    steps = [("serve_step", 0, 100), ("serve_step.dispatch", 10, 18),
             ("serve_step", 110, 200), ("serve_step.dispatch", 120, 126),
             # a step that served no batch: nothing ran on the device
             ("serve_step", 210, 230), ("serve_step.batch", 210, 220)]
    tr = tracing.Trace(ops, steps, (0.0, 300.0), [0])
    assert read(made_up_run(tr)) == pytest.approx((18 + 16) / 2 * 1e-6)
    # the parent program's trace: serve_step spans only
    old = tracing.Trace(ops, [sp for sp in steps if sp[0] == "serve_step"],
                        (0.0, 300.0), [0])
    assert read(made_up_run(old)) is None
    assert read(made_up_run()) is None
