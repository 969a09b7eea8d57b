"""Device-busy milliseconds per served batch: the union of the device's
operations inside each ``serve_step`` span that served a batch."""
from benchmarks.chip import tracing


def read(run):
    return None if run.trace is None else tracing.batch_ms(run.trace, "device")
