"""Output tokens stamped in the window, finished requests or not, over the
window's seconds."""


def read(run):
    ws, we = run.window
    n = sum(k for rec in run.records for t, k in rec.stamps if ws <= t <= we)
    return n / (we - ws) if n else None
