"""Paged attention kernel's share of its roofline: the least time its calls
in the traced window need (the larger of FLOPs over peak FLOP/s and bytes
over HBM bandwidth, per round and prefill span of every layer, counted by
the configuration's family from the rows' lengths at the sync before each
loop) over the kernel's device time in the trace."""
from bench import model, roofline


def read(run):
    if run.trace is None or not run.trace.classified or \
            not run.trace.kernels.get("paged_attention"):
        return None
    c, fam = run.cfg, model.family(run.cfg)
    need = 0.0
    bound = {"compute": 0.0, "memory": 0.0}
    for s in run.steps_in_window():
        for r in range(s.rounds):
            lengths = [ell for ell, active, _ in s.rows if active > r]
            if lengths:
                t, b = roofline.least_time(
                    *fam.paged_attn_call(c, lengths, s.window), run.peaks)
                need += t
                bound[b] += t
        for start, end in s.prefills:
            t, b = roofline.least_time(
                *fam.paged_attn_prefill(c, start, end), run.peaks)
            need += t
            bound[b] += t
    run.note("paged_attn least time by bound (s, all layers): "
             f"compute {bound['compute']!r}, memory {bound['memory']!r}")
    return 100.0 * need / run.trace.kernels["paged_attention"]
