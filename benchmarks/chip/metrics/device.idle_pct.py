"""Share of the traced window in which the device ran no operation."""
from benchmarks.chip import tracing


def read(run):
    tr = run.trace
    if tr is None:
        return None
    busy = tracing.total(tr.busy(tr.devices[0])) * 1e-9
    return 100.0 * (1.0 - busy / tr.window_s)
