"""99th percentile of how late the load generator handed a request to the
server (submit - due), over the traced part of an open-loop window."""
import numpy as np


def read(run):
    if run.cell.traffic["loop"] != "open":
        return None
    end = run.traced_until if run.traced_until is not None else run.window_end
    sel = run.due <= end
    if not np.any(sel):
        return None
    return float(np.percentile(run.submitted[sel] - run.due[sel], 99) * 1e3)
