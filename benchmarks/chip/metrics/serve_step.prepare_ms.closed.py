"""Host milliseconds before each batch reaches the device: the mean, over
the program's ``serve_step`` spans that served a batch, of the end of the
``serve_step.dispatch`` span inside it less the start of the
``serve_step`` span (batch formation, canonicalize, plan, upload and the
dispatch of the jitted step). None where the trace has no dispatch
spans."""
from benchmarks.chip import tracing

DISPATCH = "serve_step.dispatch"


def read(run):
    tr = run.trace
    if tr is None:
        return None
    busy = tr.busy(tr.devices[0])
    ends = sorted(b for a, b in tr.spans(DISPATCH))
    vals = []
    for a, b in tr.spans(tracing.SERVE_SPAN):
        inside = [e for e in ends if a <= e <= b]
        if inside and tracing.overlap(busy, a, b) > 0:
            vals.append(inside[0] - a)
    if not vals:
        return None
    return sum(vals) / len(vals) * 1e-6
