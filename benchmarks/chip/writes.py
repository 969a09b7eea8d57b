"""Writes in a cell's window: inserts and deletes at their due times, from a
writer thread of their own, whatever the serving thread is doing.

A traffic file's ``writes`` is ``0`` or absent (no writes: the window runs
only queries), or an object with exactly these keys::

    "writes": {"insert_qps": 200, "delete_qps": 50,
               "objects": "corpus", "victims": "oldest"}

* Inserts and deletes arrive as Poisson processes conditioned on their
  counts (:func:`harness.arrivals`, streams ``[seed, 6]`` and
  ``[seed, 8]``, apart from the queries' ``[seed, 3]``).
* ``objects`` names a generator ``streams/<name>.py`` with
  ``make(cfg, writes, seed, count) -> (vectors [count, d] f32, s, t)``,
  called at set-up with the configuration's ``data_seed``: every run of a
  deployment inserts the same objects, in the same order.
* ``victims`` picks each delete's object from the ids the writer holds as
  acknowledged and live: ``uniform`` (a uniformly random one, stream
  ``[seed, 9]``) or ``oldest`` (the smallest: retention).

Anything else in ``writes`` raises, so that no traffic file is silently run
without its writes.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Optional

import numpy as np

KEYS = ("insert_qps", "delete_qps", "objects", "victims")
VICTIMS = ("uniform", "oldest")
INSERT, DELETE = 0, 1


def spec(traffic: dict) -> Optional[dict]:
    """The traffic's ``writes`` object, checked; None where it has none."""
    w = traffic.get("writes", 0)
    if not isinstance(w, dict):
        if isinstance(w, bool) or not isinstance(w, (int, float)) or w != 0:
            raise ValueError(f"traffic 'writes' must be 0 or an object "
                             f"with keys {KEYS}, not {w!r}")
        return None
    unknown, absent = set(w) - set(KEYS), set(KEYS) - set(w)
    if unknown or absent:
        raise ValueError(f"traffic 'writes': unknown keys {sorted(unknown)}, "
                         f"missing keys {sorted(absent)}; want {KEYS}")
    for key in ("insert_qps", "delete_qps"):
        rate = w[key]
        if isinstance(rate, bool) or not isinstance(rate, (int, float)) \
                or not rate >= 0:
            raise ValueError(f"traffic 'writes.{key}' must be a rate >= 0, "
                             f"not {rate!r}")
    if w["victims"] not in VICTIMS:
        raise ValueError(f"traffic 'writes.victims' {w['victims']!r} not in "
                         f"{VICTIMS}")
    if not isinstance(w["objects"], str):
        raise ValueError(f"traffic 'writes.objects' must name a generator, "
                         f"not {w['objects']!r}")
    return w


@dataclasses.dataclass
class Plan:
    """A window's writes, drawn before it: ``offset`` seconds after the
    window's start, in order; an insert's object is the next row of
    ``vectors, s, t``."""

    kind: np.ndarray       # [W] INSERT or DELETE
    offset: np.ndarray     # [W] seconds after the window's start
    vectors: np.ndarray    # [I, d] float32, the objects inserted, in order
    s: np.ndarray          # [I]
    t: np.ndarray          # [I]
    victims: str
    seed: int
    seconds: float


def plan(cell, *, seconds: float, seed: int) -> Plan:
    """The writes of one window of ``seconds`` of ``cell`` (a
    ``harness.Cell`` whose mix writes): arrival times from ``seed``,
    objects from its generator and the configuration's ``data_seed``."""
    from benchmarks.chip.harness import arrivals, generator

    cfg, w = cell.config, cell.writes
    ins = arrivals(float(w["insert_qps"]), seconds, seed, stream=6)
    dels = arrivals(float(w["delete_qps"]), seconds, seed, stream=8)
    vectors, s, t = generator(cell.home, w["objects"])(
        cfg, w, int(cfg["data_seed"]), len(ins))
    vectors = np.ascontiguousarray(vectors, dtype=np.float32)
    if vectors.shape != (len(ins), int(cfg["dim"])) or len(s) != len(ins) \
            or len(t) != len(ins):
        raise ValueError(f"generator {w['objects']!r} made "
                         f"{vectors.shape} objects, not {len(ins)}")
    offset = np.concatenate([ins, dels])
    kind = np.concatenate([np.full(len(ins), INSERT, np.int8),
                           np.full(len(dels), DELETE, np.int8)])
    order = np.argsort(offset, kind="stable")
    return Plan(kind[order], offset[order], vectors,
                np.asarray(s, np.float64), np.asarray(t, np.float64),
                w["victims"], seed, seconds)


@dataclasses.dataclass
class Record:
    """What the writer did, one entry per planned write. Times are
    ``time.perf_counter()`` seconds; nan where the write was not issued."""

    kind: np.ndarray       # [W] INSERT or DELETE
    due: np.ndarray        # [W]
    start: np.ndarray      # [W] when the call began
    end: np.ndarray        # [W] when it returned or raised
    ack: np.ndarray        # [W] when it returned (nan: raised or not issued)
    ext: np.ndarray        # [W] the id inserted or deleted (-1: none)
    raised: np.ndarray     # [W] bool: the call raised
    refused: np.ndarray    # [W] bool: a delete the index answered False
    errors: List[str]      # the first few exceptions, as text

    @property
    def issued(self) -> np.ndarray:
        return ~np.isnan(self.start)

    @property
    def acked(self) -> np.ndarray:
        return ~np.isnan(self.ack)

    @property
    def failed(self) -> int:
        """Writes that raised or were never issued."""
        return int(np.count_nonzero(self.raised | ~self.issued))

    def ack_ms(self, kind: int) -> np.ndarray:
        """Due-to-acknowledgement milliseconds of the acknowledged writes
        of ``kind``."""
        m = self.acked & (self.kind == kind)
        return (self.ack[m] - self.due[m]) * 1e3


class Writer:
    """Issues a :class:`Plan` against ``server`` from a thread, keeping the
    ids it holds as acknowledged and live (the loaded ``0..n-1`` first)."""

    def __init__(self, server, plan: Plan, n: int):
        self.server, self.plan = server, plan
        W, I = len(plan.kind), len(plan.s)
        self.live = np.zeros(n + I, bool)    # by id: acknowledged and live
        self.live[:n] = True
        self._oldest = 0
        self._rng = np.random.default_rng([plan.seed, 9])
        nan = np.full(W, np.nan)
        self.record = Record(plan.kind.copy(), nan.copy(), nan.copy(),
                             nan.copy(), nan.copy(), np.full(W, -1, np.int64),
                             np.zeros(W, bool), np.zeros(W, bool), [])
        self._thread: Optional[threading.Thread] = None
        self._stop_at = np.inf

    def start(self, t0: float, give_up: float) -> None:
        """Write from now on, each write at ``t0 + offset``; issue none
        after ``give_up``."""
        self.record.due[:] = t0 + self.plan.offset
        self._stop_at = give_up
        self._thread = threading.Thread(target=self._run, name="bench-writer")
        self._thread.start()

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()

    def _victim(self) -> int:
        alive = np.flatnonzero(self.live) if self.plan.victims == "uniform" \
            else None
        if alive is not None:
            if alive.size == 0:
                raise RuntimeError("no live object to delete")
            return int(alive[self._rng.integers(alive.size)])
        while self._oldest < self.live.size and not self.live[self._oldest]:
            self._oldest += 1
        if self._oldest == self.live.size:
            raise RuntimeError("no live object to delete")
        return self._oldest

    def _run(self) -> None:
        rec, p = self.record, self.plan
        row = 0
        for j in range(len(rec.kind)):
            wait = rec.due[j] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            if time.perf_counter() > self._stop_at:
                return
            rec.start[j] = time.perf_counter()
            try:
                if rec.kind[j] == INSERT:
                    i, row = row, row + 1
                    ext = int(self.server.insert(p.vectors[i], float(p.s[i]),
                                                 float(p.t[i])))
                    rec.ack[j] = rec.end[j] = time.perf_counter()
                    rec.ext[j] = ext
                    if ext >= self.live.size:
                        self.live = np.concatenate(
                            [self.live, np.zeros(ext + 1 - self.live.size,
                                                 bool)])
                    self.live[ext] = True
                else:
                    victim = self._victim()
                    rec.ext[j] = victim
                    self.live[victim] = False
                    rec.refused[j] = not self.server.delete(victim)
                    rec.ack[j] = rec.end[j] = time.perf_counter()
            except Exception as exc:      # recorded; the write failed
                rec.end[j] = time.perf_counter()
                rec.raised[j] = True
                if len(rec.errors) < 5:
                    rec.errors.append(f"{type(exc).__name__}: {exc}")

    def lost(self, live_ids: np.ndarray) -> int:
        """Acknowledged writes the index does not show: loaded or
        acknowledged inserts missing from ``live_ids`` (the victims of
        deletes that raised excepted), acknowledged deletes still in it,
        and deletes of an acknowledged object that the index refused."""
        rec = self.record
        live = np.zeros(max(self.live.size, int(live_ids.max(initial=-1)) + 1),
                        bool)
        live[live_ids] = True
        want = np.zeros(live.size, bool)
        want[:self.live.size] = self.live
        deleted = rec.ext[(rec.kind == DELETE) & rec.acked]
        unsure = rec.ext[(rec.kind == DELETE) & rec.raised & (rec.ext >= 0)]
        want[unsure] = False
        live[unsure] = False
        missing = int(np.count_nonzero(want & ~live))
        kept = int(np.count_nonzero(live[deleted]))
        return missing + kept + int(np.count_nonzero(rec.refused))


def unlogged(rec: Record, wal_records) -> int:
    """Acknowledged writes missing from a write-ahead log's replay."""
    from repro.stream.wal import KIND_INSERT

    ins, dels = set(), set()
    for r in wal_records:
        (ins if r.kind == KIND_INSERT else dels).add(int(r.ext_id))
    acked = rec.acked & ~rec.refused
    want_ins = rec.ext[acked & (rec.kind == INSERT)]
    want_del = rec.ext[acked & (rec.kind == DELETE)]
    return (sum(int(e) not in ins for e in want_ins)
            + sum(int(e) not in dels for e in want_del))


@dataclasses.dataclass
class Lives:
    """When each object of the check's table lived: its rows are the loaded
    objects (ids ``0..n-1``), then the acknowledged inserts by id. A
    version counts the writes acknowledged so far."""

    life: object           # reference.Life of the rows
    objects: np.ndarray    # [N - n] the plan's object of each later row
    acks: np.ndarray       # [V] acknowledgement times, ascending

    def version(self, when: np.ndarray) -> np.ndarray:
        """The version each time in ``when`` saw: the writes acknowledged
        by then."""
        return np.searchsorted(self.acks, when, side="right").astype(np.int32)


def lives(rec: Optional[Record], n: int) -> Lives:
    """The :class:`Lives` of a window with writes ``rec`` (None: none)."""
    from benchmarks.chip.reference import NEVER, Life

    if rec is None:
        return Lives(Life.loaded(n), np.zeros(0, np.int64), np.zeros(0))
    done = rec.acked & ~rec.refused
    order = np.argsort(np.where(done, rec.ack, np.inf), kind="stable")
    order = order[:int(np.count_nonzero(done))]
    version = np.zeros(len(rec.kind), np.int64)
    version[order] = np.arange(1, len(order) + 1)
    nth = np.cumsum(rec.kind == INSERT) - 1     # the plan's object of each
    ins = np.flatnonzero(done & (rec.kind == INSERT))
    ins = ins[np.argsort(rec.ext[ins], kind="stable")]
    ids = np.concatenate([np.arange(n), rec.ext[ins]]).astype(np.int64)
    if np.any(np.diff(ids) <= 0):
        raise RuntimeError("the index gave an insert an id it had given "
                           "before")
    born = np.concatenate([np.zeros(n), version[ins]]).astype(np.int32)
    called = np.concatenate([np.full(n, -np.inf), rec.start[ins]])
    dies = np.full(len(ids), NEVER, np.int32)
    deleted = np.full(len(ids), np.inf)
    gone = np.flatnonzero(done & (rec.kind == DELETE))
    at = np.searchsorted(ids, rec.ext[gone])
    dies[at] = version[gone]
    deleted[at] = rec.ack[gone]
    return Lives(Life(ids, born, dies, called, deleted), nth[ins],
                 rec.ack[order])
