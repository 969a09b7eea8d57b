"""Queries answered in the window per second of the window (closed loop)."""
import numpy as np


def read(run):
    if run.cell.traffic["loop"] != "closed":
        return None
    return float(np.count_nonzero(run.done <= run.window_end)) / run.window_s
