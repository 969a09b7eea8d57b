"""Writes in the window: a cell whose mix inserts and deletes is checked
against the live set each step saw, its acknowledged writes are read back,
the faults that breaks are caught, and a mix without writes runs and is
checked as before."""
import json
import time

import numpy as np
import pytest

from benchmarks.chip import harness, reference, writes
from repro.serve.batching import StreamingServer
from repro.stream.index import StreamingIndex

from conftest import copy_tree, shrink

SEED = 2**31 + 41


def add_cell(tree, name, traffic, config="sift128-overlap", **cfg_updates):
    """A cell ``name`` of ``config`` (changed by ``cfg_updates``) under the
    mix ``traffic``, added to ``tree`` as files."""
    home = tree / "benchmarks" / "chip"
    cfg = json.loads((home / "configs" / f"{config}.json").read_text())
    cfg.update(cfg_updates)
    (home / "configs" / f"{name}.conf.json").write_text(json.dumps(cfg))
    (home / "traffic" / f"{name}.mix.json").write_text(json.dumps(traffic))
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(
        bench["configs"][0], name=f"{name}.conf",
        file=f"benchmarks/chip/configs/{name}.conf.json"))
    bench["workloads"].append(dict(name=name, config=f"{name}.conf",
                                   traffic=f"{name}.mix", chips=1, why="test"))
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"] in ("qps", "p99_ms", "recall_at_10"):
            m["workloads"].append(name)
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


def mix(loop, **w):
    base = dict(selectivities=[0.01, 0.1, 0.5], pool=96)
    base.update(dict(loop="closed", outstanding=64) if loop == "closed"
                else dict(loop="open", rate_qps=150))
    base["writes"] = dict(dict(insert_qps=40, delete_qps=20,
                               objects="corpus", victims="uniform"), **w)
    return base


def run(tree, cell, seed=SEED, seconds=1.5, trace=False):
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                            t_start=time.perf_counter(), root=tree,
                            require_chip=False)


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return shrink(copy_tree(tmp_path_factory.mktemp("writes")))


@pytest.mark.parametrize("loop,victims", [("closed", "uniform"),
                                          ("closed", "oldest"),
                                          ("open", "uniform"),
                                          ("open", "oldest")])
def test_a_writes_cell_is_correct(tree, loop, victims):
    cell = add_cell(tree, f"w.{loop}.{victims}", mix(loop, victims=victims),
                    wal="always")
    out = run(tree, cell)
    c = out.checks
    assert out.line["correct"] is True, c
    assert c["stale"] == {"value": 0, "limit": 0}
    assert c["lost"] == {"value": 0, "limit": 0}
    rec = out.run.writes
    W = round(40 * 1.5) + round(20 * 1.5)
    assert len(rec.kind) == W and rec.failed == 0
    assert np.all(rec.acked) and not np.any(rec.refused)
    assert out.line["attempted"] == len(out.log.rid) + W
    assert out.line["failed"] == 0
    # inserts come from the corpus generator, in the plan's order
    assert np.array_equal(rec.ext[rec.kind == writes.INSERT],
                          1024 + np.arange(60))
    deleted = rec.ext[rec.kind == writes.DELETE]
    if victims == "oldest":
        assert np.array_equal(deleted, np.arange(30))
    assert len(set(deleted.tolist())) == 30
    # some answers were served after writes: their truth is another version
    assert out.queries[3].max() > 0


def _with_room(init):
    """An index built with twice the node slots it is asked for, its
    build padded to them, so that a live set grown past the loaded corpus
    still compacts into the shapes served from the start."""
    def wrapped(self, *args, node_capacity, build_kwargs, **kwargs):
        cap = 2 * node_capacity
        init(self, *args, node_capacity=cap,
             build_kwargs=dict(build_kwargs, pad_nodes=cap), **kwargs)
    return wrapped


def test_compaction_under_writes_is_correct(tree, capsys, monkeypatch):
    """Inserts overflow the delta twice: the index compacts inside insert,
    and answers on both sides of each epoch swap hold."""
    monkeypatch.setattr(StreamingIndex, "__init__",
                        _with_room(StreamingIndex.__init__))
    cell = add_cell(tree, "w.compacts", mix("closed", insert_qps=100,
                                            delete_qps=10))
    out = run(tree, cell)
    assert out.line["correct"] is True, out.checks
    assert out.run.writes.failed == 0
    assert " epoch=3 " in capsys.readouterr().out      # load, then two


def _no_op_delete(self, ext_id):
    """A delete acknowledged and never applied."""
    return True


def _dropping_insert(insert):
    """Every third acknowledged insert is dropped from the index."""
    count = [0]

    def wrapped(self, vec, s, t):
        ext = insert(self, vec, s, t)
        count[0] += 1
        if count[0] % 3 == 0:
            with self._lock:
                self._apply_delete(ext)
        return ext
    return wrapped


@pytest.mark.parametrize("fault,check", [("delete_ignored", "stale"),
                                         ("insert_dropped", "lost")])
def test_a_lost_write_is_caught(tree, fault, check, monkeypatch):
    if fault == "delete_ignored":
        monkeypatch.setattr(StreamingIndex, "delete", _no_op_delete)
        traffic = mix("closed", delete_qps=300, victims="uniform")
    else:
        monkeypatch.setattr(StreamingIndex, "insert",
                            _dropping_insert(StreamingIndex.insert))
        traffic = mix("closed")
    out = run(tree, add_cell(tree, f"w.{fault}", traffic))
    assert out.line["correct"] is False
    assert out.checks[check]["value"] > 0, out.checks


def test_an_unlogged_write_is_lost(tree, monkeypatch):
    """With a write-ahead log, an acknowledged write missing from the log's
    replay counts as lost."""
    from repro.stream.wal import WriteAheadLog

    real = WriteAheadLog.append_insert
    monkeypatch.setattr(WriteAheadLog, "append_insert",
                        lambda self, ext, *a: self.last_lsn if ext % 2
                        else real(self, ext, *a))
    out = run(tree, add_cell(tree, "w.unlogged", mix("closed"),
                             wal="always"))
    assert out.checks["lost"]["value"] == 30, out.checks


def test_writes_off_is_the_parent_check(tiny_tree, monkeypatch):
    """A mix without writes starts no writer and never asks for a
    compaction, and its answers are held to the same truth as before: one
    exact top-k per pool query over every object."""
    def refuse(self):
        raise AssertionError("a mix without writes asked for a compaction")

    monkeypatch.setattr(StreamingServer, "maybe_compact_async", refuse)
    for cell in ("rag768.contain.mix.closed", "sift128.overlap.mix.open"):
        out = run(tiny_tree, cell)
        assert out.run.writes is None and out.line["correct"] is True
        log, pool, ref = out.log, out.pool, out.reference
        assert np.all(out.queries[3] == 0)
        got = np.array([int(r) in log.answers for r in log.rid])
        ids = np.stack([log.answers[int(r)][0] for r in log.rid[got]])
        d = np.stack([log.answers[int(r)][1] for r in log.rid[got]])
        whole = ref.topk(pool.q, pool.s_q, pool.t_q, 10)
        parent = reference.compare(ref, whole, log.pool_idx[got], pool.q,
                                   pool.s_q, pool.t_q, ids, d,
                                   missing=int((~got).sum()))
        np.testing.assert_array_equal(out.run.recall[got], parent.recall)
        assert out.checks["dist_err"]["value"] == parent.dist_err
        assert (out.checks["missing"]["value"], out.checks["bad"]["value"]) \
            == (parent.missing, parent.bad)
        assert out.line["attempted"] == len(log.rid)
        assert out.line["failed"] == parent.missing + parent.bad


@pytest.mark.parametrize("w", [
    {"insert_qps": 1, "delete_qps": 1, "objects": "corpus",
     "victims": "oldest", "burst": 3},
    {"insert_qps": 1, "delete_qps": 1, "objects": "corpus"},
    {"insert_qps": 1, "delete_qps": 1, "objects": "corpus",
     "victims": "newest"},
    {"insert_qps": -1, "delete_qps": 1, "objects": "corpus",
     "victims": "oldest"},
    5, 1.5, True, "corpus", None])
def test_a_writes_entry_that_is_not_understood_raises(w):
    with pytest.raises(ValueError):
        writes.spec({"writes": w})


def test_no_writes_is_zero_or_absent(tree):
    assert writes.spec({"writes": 0}) is None
    assert writes.spec({"writes": 0.0}) is None
    assert writes.spec({}) is None
    home = tree / "benchmarks" / "chip"
    bad = dict(json.loads((home / "traffic" / "mix5.closed.json")
                          .read_text()), writes={"insert_qps": 3})
    add_cell(tree, "w.bad", bad)
    with pytest.raises(ValueError, match="insert_qps|missing"):
        harness.load_cell("w.bad", tree)


def test_snapshot_reference_is_brute_force():
    """The reference's top-k at a version is a brute force over the rows
    live at that version."""
    from benchmarks.chip import data

    rng = np.random.default_rng(5)
    N, dim, k, V = 700, 12, 10, 40
    x = rng.normal(size=(N, dim)).astype(np.float32)
    s, t = data.make_intervals(N, "uniform", seed=5)
    born = np.where(rng.random(N) < 0.7, 0, rng.integers(1, V, N))
    dies = np.where(rng.random(N) < 0.6, reference.NEVER,
                    born + rng.integers(1, V, N))
    life = reference.Life(np.arange(N) * 3 + 7, born.astype(np.int32),
                          dies.astype(np.int32), np.full(N, -np.inf),
                          np.full(N, np.inf))
    ref = reference.Reference(x, s, t, "overlap", block=32, life=life)
    Q = 90
    q = rng.normal(size=(Q, dim)).astype(np.float32)
    s_q = np.sort(rng.uniform(0, 1000, (Q, 2)), axis=1).astype(np.float32)
    v = rng.integers(0, V + 1, Q).astype(np.int32)
    got = ref.topk(q, s_q[:, 0], s_q[:, 1], k, version=v)
    for a in range(Q):
        ok = ((t >= s_q[a, 0]) & (s <= s_q[a, 1]) & (born <= v[a])
              & (dies > v[a]))
        d = ((x.astype(np.float64) - q[a]) ** 2).sum(1)
        want = np.flatnonzero(ok)[np.argsort(d[ok], kind="stable")][:k]
        row = got.ids[a][got.ids[a] >= 0]
        assert len(row) == len(want)
        assert np.all(ok[row])
        np.testing.assert_allclose(np.sort(d[row]), d[want], rtol=1e-5)
    # rows are reported as external ids, and ids back as rows
    assert np.array_equal(ref.rows(ref.ids(np.array([0, 5, -1]))),
                          [0, 5, -1])
    assert np.array_equal(ref.rows(np.array([7, 8, 10, 2107])), [0, -1, 1, -1])


def test_stale_and_unknown_answers_are_read():
    """An object deleted before the step began is stale; one whose insert
    came after the step ended, or was never made, is bad; writes inside
    the step may be seen or not."""
    rng = np.random.default_rng(2)
    N, dim, k = 40, 4, 3
    x = rng.normal(size=(N, dim)).astype(np.float32)
    s = np.zeros(N)
    t = np.ones(N)
    called = np.full(N, -np.inf)
    deleted = np.full(N, np.inf)
    called[30:] = 5.0                  # inserted at 5 (acknowledged 5.5)
    deleted[:5] = 2.0                  # deleted at 2
    deleted[5:10] = 8.0                # deleted at 8
    born = np.where(np.arange(N) >= 30, 3, 0).astype(np.int32)
    dies = np.full(N, reference.NEVER, np.int32)
    dies[:5], dies[5:10] = 1, 6
    ref = reference.Reference(x, s, t, "overlap",
                              life=reference.Life(np.arange(N), born, dies,
                                                  called, deleted))
    q = np.zeros((1, dim), np.float32)
    truth = ref.topk(q, [0.0], [1.0], k, version=[2])
    d_of = lambda ids: ref.pair_dist(np.repeat(q, len(ids), 0),  # noqa: E731
                                     np.asarray(ids))

    def verdict(ids, S, E):
        ids = np.asarray(ids)
        return reference.compare(ref, truth, np.zeros(len(ids), int), q,
                                 np.zeros(1), np.ones(1), ids,
                                 np.sort(d_of(ids), axis=1), missing=0,
                                 start=np.full(len(ids), S),
                                 end=np.full(len(ids), E))

    v = verdict([[10, 11, 12], [0, 11, 12], [5, 11, 12]], 3.0, 4.0)
    assert (v.stale, v.bad, v.wrong) == (1, 0, 1)     # 0 was gone at 2
    v = verdict([[30, 11, 12], [99, 11, 12]], 3.0, 4.0)
    assert (v.stale, v.bad) == (0, 2)                  # 30 inserted at 5
    v = verdict([[30, 11, 12], [5, 11, 12]], 4.0, 9.0)
    assert (v.stale, v.bad) == (0, 0)                  # inside the step
