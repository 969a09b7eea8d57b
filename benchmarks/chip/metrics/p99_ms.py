"""99th percentile latency of every request due in the window, from the
time it was due to its answer (open loop). A request that never got an
answer counts as answered when the run stopped waiting for it."""
import numpy as np


def read(run):
    if run.cell.traffic["loop"] != "open":
        return None
    done = np.where(np.isnan(run.done), run.gave_up, run.done)
    return float(np.percentile(done - run.due, 99) * 1e3)
