"""95th percentile of time to first token over the requests due in the
window: first output token stamped minus due time. A request that failed or
had no token by the window's end counts its time so far at the end."""
import numpy as np


def read(run):
    ws, we = run.window
    vals = []
    for rec in run.records:
        if ws <= rec.due < we:
            first = rec.first_t
            if rec.failed or first is None or first > we:
                first = we
            vals.append(first - rec.due)
    return float(np.percentile(vals, 95)) * 1e3 if vals else None
