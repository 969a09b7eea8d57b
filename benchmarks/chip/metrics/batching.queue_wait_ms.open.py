"""Mean milliseconds a request waited in the batcher (open loop): from its
submit to the start of the served step that answered it, over the
requests answered while the trace ran (every answered request in an
untraced run). Every answer of a step carries the step's end as its
``done`` time, which finds the step in ``run.batches``. A request
submitted after its step started, before the step drained the queue,
counts as no wait. Read from the harness's clock, so the tracer's stop
and the drain after the window are not in it."""
import numpy as np


def read(run):
    if run.cell.traffic["loop"] != "open" or not run.batches:
        return None
    start_of = {end: start for start, end, _ in run.batches}
    until = run.traced_until if run.traced_until is not None else np.inf
    waits = [max(start_of[d] - s, 0.0)
             for s, d in zip(run.submitted, run.done)
             if d <= until and d in start_of]
    if not waits:
        return None
    return float(np.mean(waits) * 1e3)
