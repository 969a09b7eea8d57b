"""One run of one cell: set-up, the measured window, the check, the numbers.

Everything a cell is made of is data, found by name:

* ``BENCHMARK.json`` at the checkout's root names the cell's configuration
  and traffic mix, and which metrics the cell reports;
* ``configs/<config>.json`` holds the deployment (sizes, relation, interval
  law, batch, the limit of each number compared);
* ``traffic/<traffic>.json`` holds the mix (closed or open loop, its load,
  the selectivity buckets, the pool of distinct queries, and its writes:
  see ``writes.py``);
* ``streams/<objects>.py`` holds a generator of the objects a mix inserts,
  ``make(cfg, writes, seed, count)``;
* ``metrics/<metric>.py`` holds one reader per metric, ``read(run)``, which
  returns the metric's value from the :class:`Run` record, or None where the
  run has nothing for it to read.

The window drives the program's own serving entry, ``StreamingServer``
``submit`` / ``step``, and where the mix writes, ``insert`` / ``delete``
from a writer thread and ``maybe_compact_async`` after every step, over a
``StreamingIndex`` bulk-loaded from the configuration's ``data_seed``
(with a write-ahead log attached where the configuration names a sync
policy), with the program's defaults for the kernels (compiled kernels on
a TPU). Answers are kept in memory; the reference and the comparison run
after the window has closed and the program's state is freed, each answer
held against the live set its step saw.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import tempfile
import threading
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmarks.chip import writes as writes_mod

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = HERE / ".trace"
TRACE_SECONDS = 6.0      # a traced run traces the first seconds of its window
DRAIN_SECONDS = 60.0     # how long an answer may come after the window closes


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def log(**facts) -> None:
    print(" ".join(f"{k}={v}" for k, v in facts.items()), flush=True)


# --- the cell, from files ----------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    home: Path                   # the benchmark's directory (configs/, ...)
    writes: Optional[dict] = None   # the mix's writes (None: no writes)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` and its files."""
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    home = root / bench["paths"][0]
    cfg = json.loads((home / "configs" / f"{w['config']}.json").read_text())
    trf = json.loads((home / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"], config=cfg,
        traffic_name=w["traffic"], traffic=trf,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        home=home, writes=writes_mod.spec(trf),
    )


def _module(home: Path, sub: str, name: str):
    """The module ``<home>/<sub>/<name>.py``."""
    path = Path(home) / sub / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.{sub}." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(home: Path, metric: str) -> Callable:
    """``read`` of ``<home>/metrics/<metric>.py``."""
    return _module(home, "metrics", metric).read


def generator(home: Path, objects: str) -> Callable:
    """``make`` of ``<home>/streams/<objects>.py``."""
    return _module(home, "streams", objects).make


# --- the record that metric readers read ----------------------------------------------


@dataclasses.dataclass
class Run:
    """What one run measured. Times are ``time.perf_counter()`` seconds."""

    cell: Cell
    setup_s: float
    window_start: float
    window_end: float        # closed loop: the end of its last step
    batch_size: int
    due: np.ndarray          # [R] when each request was due
    submitted: np.ndarray    # [R] when it was handed to the server
    done: np.ndarray         # [R] when its answer came (nan: never)
    batches: List[tuple]     # (start, end, real rows) of each served step
    recall: np.ndarray       # [R] recall@k of each answer (nan: no answer)
    gave_up: float = np.nan  # when the run stopped waiting for answers
    traced_until: Optional[float] = None   # a traced run: when tracing stopped
    trace: object = None     # a traced run: its tracing.Trace
    peaks: Optional[dict] = None           # this device kind's peaks.json row
    writes: Optional[writes_mod.Record] = None   # the window's writes
    compactions: list = dataclasses.field(default_factory=list)
    # ^ the server's CompactionReports of background compactions
    plans: Optional[Dict[str, float]] = None
    # ^ the planner's routes counted from the window's start to the end of
    #   its drain (padding rows of partial batches count as BRUTE_VALID)

    @property
    def window_s(self) -> float:
        return self.window_end - self.window_start

    def in_window(self) -> np.ndarray:
        """Requests whose answer counts for the window: those answered by
        its end (closed loop) or due in it (open loop)."""
        if self.cell.traffic["loop"] == "closed":
            return self.done <= self.window_end
        return np.ones(self.due.shape, bool)


# --- device, cache, memory ---------------------------------------------------------


def require_chips(count: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < count:
        raise NoChip(f"needs {count} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs[:count]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program cached, whatever the environment says."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileCounter:
    """Backend compilations and persistent-cache hits, from JAX's events."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def memory(devices) -> Dict[str, Optional[int]]:
    """Peak and current bytes of the fullest chip (None where the backend
    keeps no count)."""
    stats = [d.memory_stats() or {} for d in devices]
    peak = [s.get("peak_bytes_in_use") for s in stats]
    used = [s.get("bytes_in_use") for s in stats]
    return {"peak": None if None in peak else max(peak),
            "in_use": None if None in used else max(used),
            "limit": stats[0].get("bytes_limit")}


def peaks_for(kind: str, home: Path = HERE) -> dict:
    table = json.loads((Path(home) / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table["devices"][kind]


# --- the system under test ------------------------------------------------------------


def build_server(cfg: dict, vectors, s, t, wal=None):
    """A ``StreamingServer`` over a ``StreamingIndex`` of the
    configuration's shard size holding the objects with ids ``0..n-1``,
    with the write-ahead log ``wal`` attached after the load: the loaded
    objects stand for data already durable."""
    from repro.serve.batching import StreamingServer
    from repro.stream.index import StreamingIndex

    n = int(cfg["n_per_shard"])
    idx = StreamingIndex(
        int(cfg["dim"]), cfg["relation"], node_capacity=n,
        delta_capacity=int(cfg["delta_capacity"]),
        edge_capacity=int(cfg["degree"]),
        build_kwargs=dict(batched=True, wave=int(cfg["build_wave"]),
                          pad_nodes=n),
    )
    ext = idx.bulk_load(vectors, s, t)
    if not np.array_equal(ext, np.arange(n)):
        raise RuntimeError("bulk_load did not give the objects ids 0..n-1")
    if wal is not None:
        idx.attach_wal(wal)
    return StreamingServer(
        idx, batch_size=int(cfg["batch"]), k=int(cfg["k"]),
        beam=int(cfg["beam"]), use_ref=None, fused=True, plan="auto",
        timeout_s=float(cfg["timeout_s"]), stats=False, admission=None,
    )


def step_facts(batches: List[tuple]) -> dict:
    """How the window's steps spread, in milliseconds, for its log line: a
    run that reads far off shows there whether all its steps were slower
    or a few stalled."""
    if not batches:
        return {}
    b = np.asarray(batches, float)
    ms = 1e3 * (b[:, 1] - b[:, 0])
    gap = 1e3 * (b[1:, 0] - b[:-1, 1])
    return dict(step_ms_p50=float(np.median(ms)),
                step_ms_p90=float(np.percentile(ms, 90)),
                step_ms_max=float(ms.max()),
                gap_ms_max=float(gap.max()) if gap.size else 0.0)


def plan_counts() -> Dict[str, float]:
    """The planner's route counter in the program's metrics registry
    (padding rows of partial batches count as BRUTE_VALID there)."""
    from repro.obs.metrics import get_registry

    c = get_registry().counter("repro_planner_routes_total")
    return {p: c.value(plan=p) for p in ("BRUTE_VALID", "GRAPH", "GRAPH_WIDE")}


# --- the measured window ------------------------------------------------------------


@dataclasses.dataclass
class Log:
    """The requests of one window, in the order they were due."""

    pool_idx: np.ndarray
    due: np.ndarray
    submitted: np.ndarray
    rid: np.ndarray
    answers: Dict[int, tuple]         # rid -> (ids, d, done, step start)
    batches: List[tuple]
    window_start: float
    window_end: float


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _record(out: dict, answers: dict, now: float, start: float) -> None:
    for rid, (ids, d) in out.items():
        answers[rid] = (ids, d, now, start)


def _after_step(server, writer, on_step, now: float) -> None:
    if writer is not None:
        server.maybe_compact_async()
    if on_step is not None:
        on_step(now)


def closed_loop(server, request: Callable, order: np.ndarray, *,
                outstanding: int, seconds: float,
                on_step: Optional[Callable] = None,
                writer: Optional[writes_mod.Writer] = None) -> Log:
    """Keep ``outstanding`` requests in the server: after each step this
    thread submits one new request for each answer, then serves the next
    step. One thread both submits and serves, so no other thread of the
    load contends with the serve path for the interpreter. Runs whole
    steps until ``seconds`` have passed. A ``writer`` writes from the
    window's start, and the server may compact after each step."""
    answers: dict = {}
    batches: list = []
    pool_idx, due, rids = [], [], []

    def submit(count: int, now: float) -> None:
        with _annotate("bench.submit"):
            for _ in range(count):
                i = int(order[len(rids) % len(order)])
                rids.append(server.submit(*request(i)))
                pool_idx.append(i)
                due.append(now)

    t0 = time.perf_counter()
    submit(outstanding, t0)
    if writer is not None:
        writer.start(t0, t0 + seconds + DRAIN_SECONDS)
    end = t0
    try:
        while end < t0 + seconds:
            start = time.perf_counter()
            out = server.step()
            end = time.perf_counter()
            _record(out, answers, end, start)
            if out:
                batches.append((start, end, len(out)))
            _after_step(server, writer, on_step, end)
            if out:
                submit(len(out), end)
    finally:
        if writer is not None:
            writer.join()
    due_a = np.asarray(due)
    return Log(np.asarray(pool_idx, np.int64), due_a, due_a.copy(),
               np.asarray(rids, np.int64), answers, batches, t0, end)


def open_loop(server, request: Callable, order: np.ndarray,
              offsets: np.ndarray, *, seconds: float,
              on_step: Optional[Callable] = None,
              writer: Optional[writes_mod.Writer] = None) -> Log:
    """Submit request ``i`` at ``window start + offsets[i]`` from a
    generator thread, whatever the server is doing; serve from this
    thread until every request has its answer (or ``DRAIN_SECONDS`` past
    the window's close). A ``writer`` writes from the window's start, and
    the server may compact after each step."""
    R = offsets.shape[0]
    pool_idx = order[np.arange(R) % len(order)].astype(np.int64)
    submitted = np.full(R, np.nan)
    rids = np.full(R, -1, np.int64)
    answers: dict = {}
    batches: list = []
    failure: list = []
    t0 = time.perf_counter() + 0.05
    due = t0 + offsets

    def generate():
        try:
            for i in range(R):
                wait = due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                submitted[i] = time.perf_counter()
                rids[i] = server.submit(*request(int(pool_idx[i])))
        except BaseException as exc:          # raised again below
            failure.append(exc)

    gen = threading.Thread(target=generate, name="bench-generator")
    gen.start()
    if writer is not None:
        writer.start(t0, t0 + seconds + DRAIN_SECONDS)
    try:
        while gen.is_alive() or len(answers) < R:
            if not gen.is_alive() and (
                    failure or time.perf_counter() > t0 + seconds + DRAIN_SECONDS):
                break
            start = time.perf_counter()
            out = server.step()
            now = time.perf_counter()
            if out:
                _record(out, answers, now, start)
                batches.append((start, now, len(out)))
            else:
                with _annotate("bench.idle"):
                    time.sleep(0.0002)
            _after_step(server, writer, on_step, now)
    finally:
        gen.join()
        if writer is not None:
            writer.join()
    if failure:
        raise failure[0]
    return Log(pool_idx, due, submitted, rids, answers, batches, t0,
               t0 + seconds)


def arrivals(rate: float, seconds: float, seed: int,
             stream: int = 3) -> np.ndarray:
    """A Poisson process of ``rate`` over ``seconds``, conditioned on its
    count: ``round(rate·seconds)`` arrival times drawn uniformly from the
    seed's ``stream`` (3: the queries) and sorted, so every seed offers the
    same number of requests."""
    count = int(round(rate * seconds))
    rng = np.random.default_rng([seed, stream])
    return np.sort(rng.uniform(0.0, seconds, size=count))


class Tracer:
    """Profiler trace of the first ``seconds`` of the window, stopped from
    the serving loop between two steps. ``bench.trace_start`` and
    ``bench.trace_stop`` mark the traced window in the trace."""

    def __init__(self, logdir: Path, seconds: float):
        self.logdir, self.seconds = logdir, seconds
        self.stop_at = None
        self.stopped_at = None

    def start(self) -> None:
        import jax

        from benchmarks.chip import tracing

        shutil.rmtree(self.logdir, ignore_errors=True)
        jax.profiler.start_trace(str(self.logdir),
                                 profiler_options=tracing.options())
        with _annotate("bench.trace_start"):
            pass
        self.stop_at = time.perf_counter() + self.seconds

    def on_step(self, now: float) -> None:
        if self.stopped_at is None and now >= self.stop_at:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.stop_at is not None and self.stopped_at is None:
            with _annotate("bench.trace_stop"):
                pass
            self.stopped_at = time.perf_counter()
            jax.profiler.stop_trace()


# --- one run ------------------------------------------------------------------------


@dataclasses.dataclass
class Setup:
    """A cell set up for its window: the server and the query pool, and
    where the mix writes, the writes drawn for ``seed``."""

    cell: Cell
    server: object
    pool: object
    vectors: np.ndarray
    s: np.ndarray
    t: np.ndarray
    devices: list
    counter: CompileCounter
    setup_s: float
    plan: Optional[writes_mod.Plan] = None
    wal: object = None            # the WriteAheadLog the index writes
    wal_dir: Optional[str] = None
    windows: int = 0

    def request(self, i: int):
        p = self.pool
        return p.q[i], float(p.s_q[i]), float(p.t_q[i])

    def free(self) -> None:
        """Drop the program's state (the write-ahead log closed)."""
        if self.wal is not None:
            self.wal.close()
        self.server = None
        gc.collect()

    def close(self) -> None:
        self.free()
        if self.wal_dir is not None:
            shutil.rmtree(self.wal_dir, ignore_errors=True)
            self.wal_dir = None


def set_up(cell: Cell, *, seed: int, t_start: float,
           seconds: Optional[float] = None,
           require_chip: bool = True, label: str = "") -> Setup:
    """Make the corpus and the query pool from the configuration's
    ``data_seed`` (one fixed data set per deployment, as a public data set
    is), build the index, and warm up the one served shape (a full and a
    partial batch, drawn by ``seed``). ``seed`` draws the traffic: which
    pool query comes when, and when it arrives; where the mix writes, the
    writes of a window of ``seconds`` are drawn here too."""
    import jax

    from benchmarks.chip import data

    cfg, trf = cell.config, cell.traffic
    devices = require_chips(cell.chips) if require_chip else jax.devices()[:1]
    dev = devices[0]
    cache = enable_compile_cache()
    counter = CompileCounter()
    log(phase="device", platform=dev.platform, device_kind=dev.device_kind,
        device_count=len(devices), workload=cell.name, seed=seed,
        compile_cache=cache, **({"run": label} if label else {}))
    split: Dict[str, float] = {"start": time.perf_counter() - t_start}

    n, dim, k = int(cfg["n_per_shard"]), int(cfg["dim"]), int(cfg["k"])
    ds = int(cfg["data_seed"])
    t = time.perf_counter()
    vectors, qv = data.make_vectors_and_queries(
        n, int(trf["pool"]), dim, clusters=int(cfg["clusters"]),
        spread=float(cfg["spread"]), seed=ds)
    s, e = data.make_intervals(n, cfg["intervals"], seed=ds)
    split["data"] = time.perf_counter() - t

    t = time.perf_counter()
    pool = data.make_pool(qv, s, e, cfg["relation"], trf["selectivities"],
                          k=k, seed=ds)
    su = Setup(cell, None, pool, vectors, s, e, devices, counter, 0.0)
    if cell.writes is not None:
        if seconds is None:
            raise ValueError(f"{cell.name}: a mix that writes draws its "
                             f"writes at set-up, for a window of seconds")
        su.plan = writes_mod.plan(cell, seconds=seconds, seed=seed)
    split["query_pool"] = time.perf_counter() - t

    t = time.perf_counter()
    try:
        if cfg.get("wal") is not None:
            from repro.stream.wal import WriteAheadLog

            su.wal_dir = tempfile.mkdtemp(prefix="bench-wal-")
            su.wal = WriteAheadLog(su.wal_dir, sync=cfg["wal"])
        su.server = server = build_server(cfg, vectors, s, e, su.wal)
    except BaseException:
        su.close()
        raise
    split["build"] = time.perf_counter() - t

    t = time.perf_counter()
    c0 = counter.seconds
    B = int(cfg["batch"])
    warm = np.random.default_rng([seed, 5]).permutation(len(pool))
    for count in (B, B // 2):           # a full and a partial batch
        for i in warm[:count]:
            server.submit(*su.request(int(i)))
        server.step(force=True)
    split["compile"] = counter.seconds - c0
    split["warm_up"] = time.perf_counter() - t
    su.setup_s = time.perf_counter() - t_start
    log(phase="setup", setup_s=su.setup_s,
        **{f"{key}_s": v for key, v in split.items()},
        compile_s_total=counter.seconds, cache_hits=counter.hits,
        cache_misses=counter.misses,
        index_bytes_in_use=memory(devices)["in_use"])
    return su


@dataclasses.dataclass
class Window:
    """What one measured window left: its log, its writes, and what was
    read off the program before its state went."""

    seed: int
    log: Log
    gave_up: float
    compiles: int
    mem: dict
    plans: Optional[Dict[str, float]] = None
    plan: Optional[writes_mod.Plan] = None
    writes: Optional[writes_mod.Record] = None
    lost: int = 0
    compactions: list = dataclasses.field(default_factory=list)
    traced_until: Optional[float] = None
    trace: object = None


def measure(su: Setup, *, seed: int, seconds: float,
            trace: bool = False) -> Window:
    """One measured window on a set-up cell, drawn by ``seed``. Where the
    mix writes, the writes are checked against the index (``lost``) at the
    close, while the server still stands."""
    from benchmarks.chip import tracing

    cell, server, counter = su.cell, su.server, su.counter
    trf = cell.traffic
    writer = None
    if cell.writes is not None:
        if su.windows:
            raise RuntimeError("a mix that writes gets one window per set-up")
        if (su.plan.seed, su.plan.seconds) != (seed, seconds):
            raise ValueError(f"the writes were drawn at set-up for seed "
                             f"{su.plan.seed} and {su.plan.seconds} s")
        writer = writes_mod.Writer(server, su.plan,
                                   int(cell.config["n_per_shard"]))
    su.windows += 1
    plans0 = plan_counts()
    order = np.random.default_rng([seed, 4]).permutation(len(su.pool))
    compiles0 = counter.compiles

    tracer = Tracer(TRACE_DIR, min(TRACE_SECONDS, seconds)) if trace else None
    on_step = tracer.on_step if tracer else None
    if tracer:
        tracer.start()
    try:
        if trf["loop"] == "closed":
            wlog = closed_loop(server, su.request, order,
                               outstanding=int(trf["outstanding"]),
                               seconds=seconds, on_step=on_step,
                               writer=writer)
            start = time.perf_counter()
            _record(server.drain(), wlog.answers, time.perf_counter(), start)
        elif trf["loop"] == "open":
            wlog = open_loop(server, su.request, order,
                             arrivals(float(trf["rate_qps"]), seconds, seed),
                             seconds=seconds, on_step=on_step, writer=writer)
        else:
            raise ValueError(f"traffic loop {trf['loop']!r} is not "
                             f"'closed' or 'open'")
    finally:
        if tracer:
            tracer.stop()
    gave_up = time.perf_counter()
    win = Window(seed, wlog, gave_up, counter.compiles - compiles0,
                 memory(su.devices))
    win.plans = {p: v - plans0[p] for p, v in plan_counts().items()}
    in_window = [b for b in wlog.batches if b[1] <= wlog.window_end]
    log(phase="window", seconds=wlog.window_end - wlog.window_start,
        requests=len(wlog.due), answered=len(wlog.answers),
        batches=len(wlog.batches), compiles_in_window=win.compiles,
        plan_mix=json.dumps(win.plans, separators=(",", ":")),
        **step_facts(in_window),
        peak_bytes_in_use=win.mem["peak"], bytes_limit=win.mem["limit"])
    if writer is not None:
        win.plan = writer.plan
        _close_writes(su, writer, win)
    if tracer:
        win.traced_until = tracer.stopped_at
        win.trace = tracing.load(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return win


def _close_writes(su: Setup, writer: writes_mod.Writer, win: Window) -> None:
    """Wait for a compaction in flight, then read the acknowledged writes
    back: from the index's live set, and from the write-ahead log."""
    server = su.server
    error = None
    try:
        server.join_compaction()
    except Exception as exc:          # the program's; recorded, not raised
        error = f"{type(exc).__name__}: {exc}"
    rec = writer.record
    win.writes = rec
    win.compactions = list(server.compactions)
    win.lost = writer.lost(server.index.live_ids())
    unlogged = 0
    if su.wal is not None:
        su.wal.close()
        unlogged = writes_mod.unlogged(rec, su.wal.replay())
        win.lost += unlogged
    ins, dels = (rec.ack_ms(kind) for kind in (writes_mod.INSERT,
                                                writes_mod.DELETE))
    log(phase="writes", planned=len(rec.kind),
        issued=int(np.count_nonzero(rec.issued)),
        acked=int(np.count_nonzero(rec.acked)),
        raised=int(np.count_nonzero(rec.raised)),
        refused=int(np.count_nonzero(rec.refused)), lost=win.lost,
        unlogged=unlogged,
        insert_ack_ms_p50=_pct(ins, 50), insert_ack_ms_p99=_pct(ins, 99),
        delete_ack_ms_p50=_pct(dels, 50), delete_ack_ms_p99=_pct(dels, 99),
        compactions=len(win.compactions), epoch=server.index.epoch,
        compaction_error=error, errors=json.dumps(rec.errors))


def _pct(a: np.ndarray, q: float) -> Optional[float]:
    return float(np.percentile(a, q)) if a.size else None


@dataclasses.dataclass
class Outcome:
    """A run's result line, its numbers compared, and what the control
    needs to put the reference in the program's place: the truth's
    queries (``q, s_q, t_q, version``), and for each answer, its query
    ``at`` and its step's ``start`` and ``end``."""

    line: dict
    checks: dict
    run: Run
    log: Log
    pool: object
    reference: object
    truth: object
    queries: tuple
    at: np.ndarray
    start: np.ndarray
    end: np.ndarray


def check(su: Setup, win: Window, *, ref=None) -> Outcome:
    """Hold a window's answers against the plain reference and report the
    run. Runs after the program's state is freed; ``ref`` reuses the
    reference of an earlier window of a mix without writes."""
    from benchmarks.chip import reference, tracing

    cell, cfg = su.cell, su.cell.config
    wlog, pool, dev = win.log, su.pool, su.devices[0]
    k, B = int(cfg["k"]), int(cfg["batch"])
    t = time.perf_counter()
    lives = writes_mod.lives(win.writes, int(cfg["n_per_shard"]))
    if ref is None:
        vectors, s, e = su.vectors, su.s, su.t
        if win.writes is not None:
            p, at = win.plan, lives.objects
            vectors = np.concatenate([vectors, p.vectors[at]])
            s, e = np.concatenate([s, p.s[at]]), np.concatenate([e, p.t[at]])
        ref = reference.Reference(vectors, s, e, cfg["relation"],
                                  life=lives.life)
    R = len(wlog.rid)
    got = np.array([int(r) in wlog.answers for r in wlog.rid], bool)
    ids = np.full((R, k), -1, np.int64)
    dist = np.full((R, k), np.inf, np.float32)
    done = np.full(R, np.nan)
    start = np.full(R, np.nan)
    for j in np.flatnonzero(got):
        ids[j], dist[j], done[j], start[j] = wlog.answers[int(wlog.rid[j])]
    # one truth per (pool query, version of the data its step saw)
    version = lives.version(start[got])
    keys, at = np.unique(np.stack([wlog.pool_idx[got], version], axis=1),
                         axis=0, return_inverse=True)
    at = at.reshape(-1)
    queries = (pool.q[keys[:, 0]], pool.s_q[keys[:, 0]],
               pool.t_q[keys[:, 0]], keys[:, 1].astype(np.int32))
    truth = ref.topk(*queries[:3], k, version=queries[3])
    verdict = reference.compare(
        ref, truth, at, *queries[:3], ids[got], dist[got],
        missing=int(R - got.sum()), start=start[got], end=done[got])
    recall = np.full(R, np.nan)
    recall[got] = verdict.recall
    checks = verdict.checks(cfg["limits"])
    checks["compiles_in_window"] = {"value": win.compiles, "limit": 0}
    checks["lost"] = {"value": win.lost, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    log(phase="check", reference_s=time.perf_counter() - t,
        answers_checked=int(got.sum()), truths=len(keys),
        recall_mean=float(np.mean(verdict.recall)) if got.any() else None)

    run = Run(cell=cell, setup_s=su.setup_s, window_start=wlog.window_start,
              window_end=wlog.window_end, batch_size=B, due=wlog.due,
              submitted=wlog.submitted, done=done, batches=wlog.batches,
              recall=recall, gave_up=win.gave_up, writes=win.writes,
              compactions=win.compactions, plans=win.plans)
    traced = win.trace is not None
    if traced:
        run.traced_until = win.traced_until
        run.trace = win.trace
        run.peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" \
            else None
    W = 0 if win.writes is None else len(win.writes.kind)
    wfail = 0 if win.writes is None else win.writes.failed
    line = {"correct": bool(correct), "attempted": int(R + W),
            "failed": int(R - got.sum()) + verdict.wrong + wfail,
            "metrics": {}}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = reader(cell.home, m["name"])(run)
        if value is not None:
            line["metrics"][m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
    line["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(su.devices),
                      "memory_peak_bytes": win.mem["peak"]}
    if traced:
        tr = run.trace
        line["device"]["busy_s"] = tr.busy_s()
        line["device"]["window_s"] = tr.window_s
        line["breakdown"] = {"device_ops": tracing.top_ops(tr),
                             "idle_gaps": tracing.idle_gaps(tr)}
    line["checks"] = checks
    return Outcome(line, checks, run, wlog, pool, ref, truth, queries, at,
                   start[got], done[got])


def run_cell(name: str, *, seed: int, seconds: float, trace: bool,
             t_start: float, root: Path = ROOT,
             require_chip: bool = True) -> Outcome:
    """Set up, measure, check and report one run of cell ``name``.
    ``require_chip=False`` skips the look for a TPU (the CPU rehearsal)."""
    cell = load_cell(name, root)
    su = set_up(cell, seed=seed, t_start=t_start, seconds=seconds,
                require_chip=require_chip,
                label="traced" if trace else "")
    try:
        win = measure(su, seed=seed, seconds=seconds, trace=trace)
        su.free()       # the program's state goes before the reference runs
        return check(su, win)
    finally:
        su.close()
