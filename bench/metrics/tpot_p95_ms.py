"""95th percentile, over requests whose tokens reached the host at two or
more syncs in the window, of (last - first in-window stamp) / (tokens
stamped after the first stamp): the mean gap a user sees between output
tokens. Tokens that arrive at one sync share its stamp."""
import numpy as np


def read(run):
    ws, we = run.window
    vals = []
    for rec in run.records:
        st = [(t, n) for t, n in rec.stamps if ws <= t <= we]
        if len(st) >= 2:
            vals.append((st[-1][0] - st[0][0]) / sum(n for _, n in st[1:]))
    return float(np.percentile(vals, 95)) * 1e3 if vals else None
