"""Host milliseconds per served batch: the program's ``serve_step`` span
minus the device-busy time inside it (batching, canonicalize, plan,
upload, result fetch and assembly)."""
from benchmarks.chip import tracing


def read(run):
    return None if run.trace is None else tracing.batch_ms(run.trace, "host")
