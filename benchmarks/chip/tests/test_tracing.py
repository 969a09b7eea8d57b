"""The trace reduction: interval arithmetic on a made-up trace, and every
number the readers take from a small trace recorded on the chip."""
import gzip
import shutil
from pathlib import Path

import pytest

from benchmarks.chip import harness, tracing

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def made_up():
    # a while loop (w) covers the operations of its body (the second a)
    ops = [tracing.Op("a", 0, 10, 0), tracing.Op("b", 10, 20, 0),
           tracing.Op("w", 40, 60, 0), tracing.Op("a", 42, 50, 0),
           tracing.Op("c", 90, 120, 0)]
    spans = [("serve_step", 0, 30), ("serve_step", 35, 60),
             ("serve_step", 61, 80), ("bench.idle", 62, 79)]
    return tracing.Trace(ops, spans, (0.0, 100.0), [0])


def test_union_total_overlap_gaps():
    tr = made_up()
    busy = tr.busy(0)
    assert busy == [(0, 20), (40, 60), (90, 100)]     # clipped to the window
    assert tracing.total(busy) == 50
    assert tracing.overlap(busy, 15, 45) == 10
    assert tracing.gaps(busy, tr.window) == [(20, 40), (60, 90)]
    assert tr.busy_s() == pytest.approx(50e-9)


def test_batch_split_and_breakdown():
    tr = made_up()
    # the third span ran nothing on the device: not a batch
    assert tracing.batch_split(tr) == [(30, 20), (25, 20)]
    assert tracing.batch_ms(tr, "device") == pytest.approx(20e-6)
    assert tracing.batch_ms(tr, "host") == pytest.approx(7.5e-6)
    assert tracing.self_times(tr) == {"a": 18, "b": 10, "w": 12, "c": 10}
    top = tracing.top_ops(tr, 2)
    assert [t[0] for t in top] == ["a", "w"]
    assert [t[1] for t in top] == pytest.approx([18e-9, 12e-9])
    gaps = tracing.idle_gaps(tr)
    assert [g[0] for g in gaps] == ["bench.idle", "serve_step"]
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 20e-9])


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """0.6 s of `rag768.contain.mix.closed` traced on a TPU v5 lite: two
    served batches (recorded by the harness's traced run)."""
    path = tmp_path_factory.mktemp("trace") / "rec.xplane.pb"
    with gzip.open(TESTDATA / "rag768.contain.mix.closed.xplane.pb.gz") as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return tracing.load(str(path))


def test_recorded_trace_reduces(recorded):
    tr = recorded
    assert tr.devices == [0]
    assert tr.window_s == pytest.approx(1.005855878)
    assert tr.busy_s() == pytest.approx(0.965990403)
    assert tracing.batch_split(tr) == [(502833557.0, 493775166.0),
                                       (480469132.0, 472215237.0)]
    top = tracing.top_ops(tr, 3)
    assert [t[0] for t in top] == ["filter_dist_gather_packed_pallas.9",
                                   "filter_dist_gather_packed_pallas.8",
                                   "fusion.114"]
    assert top[0][1] == pytest.approx(0.303264138)


def _run_of(tr, cell="rag768.contain.mix.closed"):
    run = harness.Run(cell=harness.load_cell(cell), setup_s=0.0,
                      window_start=0.0, window_end=1.0, batch_size=256,
                      due=None, submitted=None, done=None, batches=[],
                      recall=None, trace=tr,
                      peaks=harness.peaks_for("TPU v5 lite"))
    return run


def test_recorded_trace_metrics(recorded):
    run = _run_of(recorded)
    home = run.cell.home
    read = lambda m: harness.reader(home, m)(run)   # noqa: E731
    assert read("device.idle_pct") == pytest.approx(3.9633386722625397)
    assert read("search.device_ms.closed") == pytest.approx(482.9952015)
    assert read("serve_step.host_ms.closed") == pytest.approx(8.656143)
    assert read("kernel.filter_dist_gather_packed_roofline") == pytest.approx(
        8.43653868770571)


def test_gather_bytes_from_call_shapes(recorded):
    calls = tracing.kernel_calls(recorded, "filter_dist_gather_packed_pallas")
    assert len(calls) == 380
    path = harness.HERE / "metrics" / "kernel.filter_dist_gather_packed_roofline.py"
    ns: dict = {}
    exec(compile(path.read_text(), str(path), "exec"), ns)
    by_name = {c.name: ns["parse"](c.text) for c in calls}
    graph = by_name["filter_dist_gather_packed_pallas.8"]
    wide = by_name["filter_dist_gather_packed_pallas.9"]
    assert graph == ((256, 1, 96), (65536, 1, 768), (65536, 1, 256),
                     (256, 1, 768))
    assert wide[0] == (512, 1, 96)
    # rows 256·96·3072 B, label rows 256·1024 B, per-candidate operands
    # 256·96·16 B, row ids 256·96·8 B, result 256·96·4 B, queries 256·3072 B
    assert ns["gather_bytes"](*graph) == (75497472 + 262144 + 393216
                                          + 196608 + 98304 + 786432)
