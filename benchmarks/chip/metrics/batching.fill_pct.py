"""Real rows per served batch as a share of the batch size, over the
batches started in the traced part of the window."""


def read(run):
    end = run.traced_until if run.traced_until is not None else run.window_end
    rows = [n for start, _, n in run.batches
            if run.window_start <= start <= end]
    if not rows:
        return None
    return 100.0 * sum(rows) / (len(rows) * run.batch_size)
