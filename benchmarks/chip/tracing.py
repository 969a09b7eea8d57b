"""Device trace capture and its reduction to numbers.

A traced run records one profiler trace of the measured window (Python
function tracing off, so the host runs as it does untraced apart from the
profiler's own annotations). :func:`load` reads the ``.xplane.pb`` file
with nothing but JAX and keeps three things:

* the device operations of each chip (the ``XLA Ops`` line of every
  ``/device:TPU:<i>`` plane): the event's name there is the operation's
  whole HLO text, kept as ``text``; its short name is the HLO name before
  `` = `` (a Pallas kernel's is its function's name, such as
  ``filter_dist_gather_packed_pallas.8``). Operations nest: a ``while``
  covers the operations of its body;
* the host spans named ``serve_step`` (the program's own span around one
  ``StreamingServer.step``) and the harness's own ``bench.*`` spans;
* the traced window, between the harness's ``bench.trace_start`` and
  ``bench.trace_stop`` marks (the whole trace where they are missing).

Off the TPU (the CPU rehearsal of a traced run) there is no device plane;
there the operations that carry an ``hlo_op`` statistic on the host's
threads stand in for device 0, so that the reduction can be driven end to
end. Such numbers are never reported.

Everything else here is arithmetic on intervals, so that every metric
reads the trace the same way.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

SERVE_SPAN = "serve_step"
HOST_SPAN_PREFIXES = (SERVE_SPAN, "bench.")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclasses.dataclass
class Op:
    name: str            # short HLO name, without the leading %
    start: float
    end: float
    device: int
    text: str = ""       # the whole HLO text of the operation


def short_name(text: str) -> str:
    return text.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    host_spans: List[Tuple[str, float, float]]   # (name, start, end)
    window: Interval
    devices: List[int]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self, device: int) -> List[Interval]:
        """Disjoint intervals in which ``device`` ran an operation, clipped
        to the traced window."""
        lo, hi = self.window
        return union([(max(o.start, lo), min(o.end, hi)) for o in self.ops
                      if o.device == device and o.end > lo and o.start < hi])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(total(self.busy(d)) for d in self.devices) * 1e-9 / max(
            len(self.devices), 1)

    def spans(self, name: str) -> List[Interval]:
        return [(a, b) for nm, a, b in self.host_spans if nm == name]


def options():
    """Profiler options of a traced run: host annotations, no Python tracer."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def find_trace_file(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` file (or the newest one under a log dir)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_trace_file(path)
    pd = ProfileData.from_file(path)
    ops: List[Op] = []
    host_ops: List[Op] = []
    spans: List[Tuple[str, float, float]] = []
    lo, hi = float("inf"), float("-inf")
    devices = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices.append(int(m.group(1)))
        for line in plane.lines:
            for e in line.events:
                a = float(e.start_ns)
                b = a + float(e.duration_ns)
                lo, hi = min(lo, a), max(hi, b)
                if m:
                    if line.name == OPS_LINE:
                        ops.append(Op(short_name(e.name), a, b,
                                      int(m.group(1)), e.name))
                elif e.name.startswith(HOST_SPAN_PREFIXES):
                    spans.append((e.name, a, b))
                elif any(k == "hlo_op" for k, _ in e.stats):
                    host_ops.append(Op(e.name, a, b, 0, e.name))
    if not devices:
        ops, devices = host_ops, [0]
    if not ops:
        raise ValueError(f"{path}: no device operations in the trace")
    marks = {nm: a for nm, a, _ in spans
             if nm in ("bench.trace_start", "bench.trace_stop")}
    window = (marks.get("bench.trace_start", lo),
              marks.get("bench.trace_stop", hi))
    return Trace(ops, spans, window, sorted(set(devices)))


# --- interval arithmetic ----------------------------------------------------------


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of the intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(merged: Sequence[Interval]) -> float:
    return sum(b - a for a, b in merged)


def overlap(merged: Sequence[Interval], a: float, b: float) -> float:
    """Length of ``[a, b]`` covered by the disjoint sorted ``merged``."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged
               if x < b and y > a)


def gaps(merged: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that ``merged`` leaves uncovered."""
    out, cur = [], window[0]
    for a, b in merged:
        if a > cur:
            out.append((cur, min(a, window[1])))
        cur = max(cur, b)
    if cur < window[1]:
        out.append((cur, window[1]))
    return [(a, b) for a, b in out if b > a]


# --- per-batch split of the program's serve_step spans ----------------------------


def batch_split(tr: Trace, device: Optional[int] = None) -> List[Tuple[float, float]]:
    """``(span_ns, device_busy_ns)`` of every ``serve_step`` span in which
    the device ran something: one per served batch (a step that found no
    batch to serve runs nothing on the device and is left out)."""
    dev = tr.devices[0] if device is None else device
    busy = tr.busy(dev)
    out = []
    for a, b in tr.spans(SERVE_SPAN):
        d = overlap(busy, a, b)
        if d > 0:
            out.append((b - a, d))
    return out


def batch_ms(tr: Trace, part: str) -> Optional[float]:
    """Mean milliseconds per served batch of the device (``part="device"``)
    or of the rest of the ``serve_step`` span (``part="host"``)."""
    split = batch_split(tr)
    if not split:
        return None
    i = {"host": 0, "device": 1}[part]
    vals = [(s - d, d)[i] for s, d in split]
    return sum(vals) / len(vals) * 1e-6


# --- breakdown for the result line --------------------------------------------------


def self_times(tr: Trace) -> Dict[str, float]:
    """Nanoseconds per operation name inside the traced window, each
    operation counted without the operations nested in it (a ``while``
    keeps only the time its body's operations leave uncovered)."""
    lo, hi = tr.window
    acc: Dict[str, float] = {}
    for dev in tr.devices:
        ops = sorted((o for o in tr.ops if o.device == dev
                      and o.end > lo and o.start < hi),
                     key=lambda o: (o.start, -o.end))
        stack: List[list] = []           # [op, clipped length, child time]

        def close(entry):
            op, length, child = entry
            acc[op.name] = acc.get(op.name, 0.0) + max(length - child, 0.0)

        for o in ops:
            a, b = max(o.start, lo), min(o.end, hi)
            while stack and stack[-1][0].end <= o.start:
                close(stack.pop())
            if stack:
                stack[-1][2] += min(b, stack[-1][0].end) - a
            stack.append([o, b - a, 0.0])
        while stack:
            close(stack.pop())
    return acc


def top_ops(tr: Trace, n: int = 10) -> List[List[object]]:
    """The ``n`` device operations (by short name) with the most self time
    in the traced window, seconds summed over the chips."""
    top = sorted(self_times(tr).items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in top]


def kernel_calls(tr: Trace, prefix: str) -> List[Op]:
    """The device operations of a kernel, by its name, inside the window."""
    lo, hi = tr.window
    return [o for o in tr.ops if o.name.startswith(prefix + ".")
            or o.name == prefix if o.start >= lo and o.end <= hi]


def idle_gaps(tr: Trace, n: int = 10) -> List[List[object]]:
    """The ``n`` longest idle gaps of the first chip, each named by the
    innermost host span that covers its middle (``host`` where none does)."""
    longest = sorted(gaps(tr.busy(tr.devices[0]), tr.window),
                     key=lambda g: g[0] - g[1])[:n]
    out = []
    for a, b in longest:
        mid = 0.5 * (a + b)
        cover = [(e - s, nm) for nm, s, e in tr.host_spans if s <= mid <= e]
        out.append([min(cover)[1] if cover else "host", (b - a) * 1e-9])
    return out
