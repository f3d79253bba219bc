"""95th percentile of admission wait over the requests due in the window:
the engine's admit time minus the due time; a request not admitted by the
window's end counts its wait so far."""
import numpy as np


def read(run):
    ws, we = run.window
    vals = []
    for rec in run.records:
        if ws <= rec.due < we:
            t = rec.req.admit_time if rec.req is not None else 0.0
            vals.append((t if 0 < t <= we else we) - rec.due)
    return float(np.percentile(vals, 95)) * 1e3 if vals else None
