"""Mean recall@10 of the window's answers against the exact filtered top-10
(a request without an answer counts 0)."""
import numpy as np


def read(run):
    if run.cell.config["k"] != 10:
        return None
    r = run.recall[run.in_window()]
    return float(np.mean(np.nan_to_num(r, nan=0.0))) if r.size else None
