"""One run of one cell: set-up, the measured window, the check, the numbers.

Everything a cell is made of is data, found by name:

* ``BENCHMARK.json`` at the checkout's root names the cell's configuration
  and traffic mix, and which metrics the cell reports;
* ``configs/<config>.json`` holds the deployment (sizes, relation, interval
  law, batch, the limit of each number compared);
* ``traffic/<traffic>.json`` holds the mix (closed or open loop, its load,
  the selectivity buckets, the pool of distinct queries);
* ``metrics/<metric>.py`` holds one reader per metric, ``read(run)``, which
  returns the metric's value from the :class:`Run` record, or None where the
  run has nothing for it to read.

The window drives the program's own serving entry, ``StreamingServer``
``submit`` / ``step``, over a ``StreamingIndex`` bulk-loaded from the seed,
with the program's defaults for the kernels (compiled kernels on a TPU).
Answers are kept in memory; the reference and the comparison run after the
window has closed and the program's state is freed.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import queue
import shutil
import threading
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

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
        home=home,
    )


def reader(home: Path, metric: str) -> Callable:
    """``read`` of ``<home>/metrics/<metric>.py``."""
    path = Path(home) / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "benchmarks.chip.metrics." + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


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


def build_server(cfg: dict, vectors, s, t):
    """A ``StreamingServer`` over a ``StreamingIndex`` holding the objects
    with ids ``0..n-1``, as the configuration states it."""
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
    return StreamingServer(
        idx, batch_size=int(cfg["batch"]), k=int(cfg["k"]),
        beam=int(cfg["beam"]), use_ref=None, fused=True, plan="auto",
        timeout_s=float(cfg["timeout_s"]), stats=False, admission=None,
    )


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
    answers: Dict[int, tuple]         # rid -> (ids, d, done)
    batches: List[tuple]
    window_start: float
    window_end: float


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _record(out: dict, answers: dict, now: float) -> None:
    for rid, (ids, d) in out.items():
        answers[rid] = (ids, d, now)


def closed_loop(server, request: Callable, order: np.ndarray, *,
                outstanding: int, seconds: float,
                on_step: Optional[Callable] = None) -> Log:
    """Keep ``outstanding`` requests in the server: a client thread
    submits one new request for each answer, while this thread serves.
    Runs whole steps until ``seconds`` have passed."""
    answers: dict = {}
    batches: list = []
    pool_idx, due, rids = [], [], []
    refills: queue.SimpleQueue = queue.SimpleQueue()
    failure: list = []

    def submit(count: int, now: float) -> None:
        with _annotate("bench.submit"):
            for _ in range(count):
                i = int(order[len(rids) % len(order)])
                rids.append(server.submit(*request(i)))
                pool_idx.append(i)
                due.append(now)

    def client():
        try:
            while True:
                item = refills.get()
                if item is None:
                    return
                submit(*item)
        except BaseException as exc:          # raised again below
            failure.append(exc)

    t0 = time.perf_counter()
    submit(outstanding, t0)
    worker = threading.Thread(target=client, name="bench-client")
    worker.start()
    end = t0
    try:
        while end < t0 + seconds and not failure:
            start = time.perf_counter()
            out = server.step()
            end = time.perf_counter()
            _record(out, answers, end)
            if out:
                batches.append((start, end, len(out)))
                refills.put((len(out), end))
            if on_step is not None:
                on_step(end)
    finally:
        refills.put(None)
        worker.join()
    if failure:
        raise failure[0]
    due_a = np.asarray(due)
    return Log(np.asarray(pool_idx, np.int64), due_a, due_a.copy(),
               np.asarray(rids, np.int64), answers, batches, t0, end)


def open_loop(server, request: Callable, order: np.ndarray,
              offsets: np.ndarray, *, seconds: float,
              on_step: Optional[Callable] = None) -> Log:
    """Submit request ``i`` at ``window start + offsets[i]`` from a
    generator thread, whatever the server is doing; serve from this
    thread until every request has its answer (or ``DRAIN_SECONDS`` past
    the window's close)."""
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
    try:
        while gen.is_alive() or len(answers) < R:
            if not gen.is_alive() and (
                    failure or time.perf_counter() > t0 + seconds + DRAIN_SECONDS):
                break
            start = time.perf_counter()
            out = server.step()
            now = time.perf_counter()
            if out:
                _record(out, answers, now)
                batches.append((start, now, len(out)))
            else:
                with _annotate("bench.idle"):
                    time.sleep(0.0002)
            if on_step is not None:
                on_step(now)
    finally:
        gen.join()
    if failure:
        raise failure[0]
    return Log(pool_idx, due, submitted, rids, answers, batches, t0,
               t0 + seconds)


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """A Poisson process of ``rate`` over ``seconds``, conditioned on its
    count: ``round(rate·seconds)`` arrival times drawn uniformly and
    sorted, so every seed offers the same number of requests."""
    count = int(round(rate * seconds))
    rng = np.random.default_rng([seed, 3])
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
class Outcome:
    """A run's result line, its numbers compared, and what the control
    needs to put the reference in the program's place."""

    line: dict
    checks: dict
    run: Run
    log: Log
    pool: object
    reference: object
    truth: object


@dataclasses.dataclass
class Setup:
    """A cell set up for its window: the server and the query pool."""

    cell: Cell
    server: object
    pool: object
    vectors: np.ndarray
    s: np.ndarray
    t: np.ndarray
    devices: list
    counter: CompileCounter
    setup_s: float

    def request(self, i: int):
        p = self.pool
        return p.q[i], float(p.s_q[i]), float(p.t_q[i])


def set_up(cell: Cell, *, seed: int, t_start: float,
           require_chip: bool = True, label: str = "") -> Setup:
    """Make the corpus and the query pool from the configuration's
    ``data_seed`` (one fixed data set per deployment, as a public data set
    is), build the index, and warm up the one served shape (a full and a
    partial batch, drawn by ``seed``). ``seed`` draws the traffic: which
    pool query comes when, and when it arrives."""
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
    split["query_pool"] = time.perf_counter() - t

    t = time.perf_counter()
    server = build_server(cfg, vectors, s, e)
    split["build"] = time.perf_counter() - t

    su = Setup(cell, server, pool, vectors, s, e, devices, counter, 0.0)
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


def run_cell(name: str, *, seed: int, seconds: float, trace: bool,
             t_start: float, root: Path = ROOT,
             require_chip: bool = True) -> Outcome:
    """Set up, measure, check and report one run of cell ``name``.
    ``require_chip=False`` skips the look for a TPU (the CPU rehearsal)."""
    from benchmarks.chip import reference, tracing

    cell = load_cell(name, root)
    cfg, trf = cell.config, cell.traffic
    su = set_up(cell, seed=seed, t_start=t_start, require_chip=require_chip,
                label="traced" if trace else "")
    server, pool, counter, devices = su.server, su.pool, su.counter, su.devices
    dev = devices[0]
    n, k, B = int(cfg["n_per_shard"]), int(cfg["k"]), int(cfg["batch"])
    plans0 = plan_counts()
    order = np.random.default_rng([seed, 4]).permutation(len(pool))
    compiles0 = counter.compiles

    tracer = Tracer(TRACE_DIR, min(TRACE_SECONDS, seconds)) if trace else None
    on_step = tracer.on_step if tracer else None
    if tracer:
        tracer.start()
    try:
        if trf["loop"] == "closed":
            wlog = closed_loop(server, su.request, order,
                               outstanding=int(trf["outstanding"]),
                               seconds=seconds, on_step=on_step)
            _record(server.drain(), wlog.answers, time.perf_counter())
        elif trf["loop"] == "open":
            wlog = open_loop(server, su.request, order,
                             arrivals(float(trf["rate_qps"]), seconds, seed),
                             seconds=seconds, on_step=on_step)
        else:
            raise ValueError(f"traffic loop {trf['loop']!r} is not "
                             f"'closed' or 'open'")
    finally:
        if tracer:
            tracer.stop()
    gave_up = time.perf_counter()
    in_window = counter.compiles - compiles0
    plans = {p: v - plans0[p] for p, v in plan_counts().items()}
    mem = memory(devices)
    log(phase="window", seconds=wlog.window_end - wlog.window_start,
        requests=len(wlog.due), answered=len(wlog.answers),
        batches=len(wlog.batches), compiles_in_window=in_window,
        plan_mix=json.dumps(plans, separators=(",", ":")),
        peak_bytes_in_use=mem["peak"], bytes_limit=mem["limit"])

    # the program's state goes before the reference runs
    del server
    su.server = None
    gc.collect()

    t = time.perf_counter()
    ref = reference.Reference(su.vectors, su.s, su.t, cfg["relation"])
    truth = ref.topk(pool.q, pool.s_q, pool.t_q, k)
    R = len(wlog.rid)
    got = np.array([int(r) in wlog.answers for r in wlog.rid], bool)
    ids = np.full((R, k), -1, np.int64)
    dist = np.full((R, k), np.inf, np.float32)
    done = np.full(R, np.nan)
    for j in np.flatnonzero(got):
        ids[j], dist[j], done[j] = wlog.answers[int(wlog.rid[j])]
    verdict = reference.compare(
        ref, truth, wlog.pool_idx[got], pool.q, pool.s_q, pool.t_q,
        ids[got], dist[got], missing=int(R - got.sum()), n=n)
    recall = np.full(R, np.nan)
    recall[got] = verdict.recall
    checks = verdict.checks(cfg["limits"])
    checks["compiles_in_window"] = {"value": in_window, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    log(phase="check", reference_s=time.perf_counter() - t,
        answers_checked=int(got.sum()),
        recall_mean=float(np.mean(verdict.recall)) if got.any() else None)

    run = Run(cell=cell, setup_s=su.setup_s, window_start=wlog.window_start,
              window_end=wlog.window_end, batch_size=B, due=wlog.due,
              submitted=wlog.submitted, done=done, batches=wlog.batches,
              recall=recall, gave_up=gave_up)
    if tracer:
        run.traced_until = tracer.stopped_at
        run.trace = tracing.load(str(TRACE_DIR))
        run.peaks = peaks_for(dev.device_kind) if require_chip else None
    line = {"correct": bool(correct), "attempted": int(R),
            "failed": int(R - got.sum()) + verdict.bad, "metrics": {}}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(cell.home, m["name"])(run)
        if value is not None:
            line["metrics"][m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
    line["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices), "memory_peak_bytes": mem["peak"]}
    if tracer:
        tr = run.trace
        line["device"]["busy_s"] = tr.busy_s()
        line["device"]["window_s"] = tr.window_s
        line["breakdown"] = {"device_ops": tracing.top_ops(tr),
                             "idle_gaps": tracing.idle_gaps(tr)}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    line["checks"] = checks
    return Outcome(line, checks, run, wlog, pool, ref, truth)
