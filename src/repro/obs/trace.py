"""Profiling hooks: span annotation + device trace capture.

``trace_span`` is the one instrumentation primitive hot host code uses: it
annotates the span in the XLA/perfetto timeline via
``jax.profiler.TraceAnnotation`` when the profiler is importable (so a
captured device trace shows host phases interleaved with device launches)
and ALWAYS times the span into the ``repro_span_seconds`` histogram, so the
same call sites feed Prometheus whether or not a trace is being captured.

``capture_trace`` wraps ``jax.profiler.start_trace``/``stop_trace`` for an
on-demand capture window (benchmarks, incident debugging). Off the TPU it
degrades to a timed no-op when the profiler cannot start; on the TPU a
trace that was asked for and did not start raises, so no caller reads an
empty trace as a measurement.
"""
from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

from repro.obs.metrics import MetricsRegistry, resolve

SPAN_METRIC = "repro_span_seconds"


try:
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:       # profiler unavailable: spans are timed only
    _TraceAnnotation = None


@contextlib.contextmanager
def trace_span(
    name: str,
    registry: Optional[MetricsRegistry] = None,
    **labels: str,
) -> Iterator[None]:
    """Time a host-side span into ``repro_span_seconds{span=name,...}``,
    annotating the profiler timeline when one is attached. The histogram
    update happens inside the annotation, so nested spans tile their
    parent with no bookkeeping left between them."""
    reg = resolve(registry)
    ann = _TraceAnnotation(name) if _TraceAnnotation is not None else None
    if ann is not None:
        ann.__enter__()
    t0 = time.perf_counter()
    try:
        yield
    finally:
        reg.histogram(
            SPAN_METRIC, "host-side span wall-clock duration"
        ).observe(time.perf_counter() - t0, span=name, **labels)
        if ann is not None:
            ann.__exit__(None, None, None)


@contextlib.contextmanager
def capture_trace(
    logdir: str,
    registry: Optional[MetricsRegistry] = None,
) -> Iterator[bool]:
    """Capture a device trace window into ``logdir`` (view with perfetto /
    tensorboard). Yields True when a real profiler trace is running, False
    on the degraded (timing-only) path, which only a backend other than
    the TPU may take: there a failed ``start_trace`` propagates. Either way
    the window's duration lands in
    ``repro_span_seconds{span="capture_trace"}``."""
    import jax

    reg = resolve(registry)
    try:
        jax.profiler.start_trace(str(logdir))
        started = True
    except Exception:
        if jax.default_backend() == "tpu":
            raise
        started = False
    t0 = time.perf_counter()
    try:
        yield started
    finally:
        if started:
            jax.profiler.stop_trace()
        reg.histogram(
            SPAN_METRIC, "host-side span wall-clock duration"
        ).observe(time.perf_counter() - t0, span="capture_trace")
