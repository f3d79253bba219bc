"""Paged attention kernel's share of its roofline: the least time its calls
in the traced window need (the larger of FLOPs over peak FLOP/s and bytes
over HBM bandwidth, per call, counted from the rows' lengths at the sync
before each loop) over the kernel's device time in the trace."""
from bench import roofline


def read(run):
    if run.trace is None or not run.trace.classified or \
            not run.trace.kernels.get("paged_attention"):
        return None
    c = run.cfg
    dims = dict(n_heads=c["num_attention_heads"],
                n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"])
    need = 0.0
    bound = {"compute": 0.0, "memory": 0.0}
    for s in run.steps_in_window():
        for r in range(s.rounds):
            lengths = [ell for ell, active, _ in s.rows if active > r]
            if lengths:
                t, b = roofline.least_time(
                    *roofline.paged_attn_call(lengths, s.window, **dims),
                    run.peaks)
                need += t
                bound[b] += t
        for start, end in s.prefills:
            t, b = roofline.least_time(
                *roofline.paged_attn_prefill(start, end, **dims), run.peaks)
            need += t
            bound[b] += t
    need *= c["num_hidden_layers"]
    run.note("paged_attn least time by bound (s, one layer): "
             f"compute {bound['compute']!r}, memory {bound['memory']!r}")
    return 100.0 * need / run.trace.kernels["paged_attention"]
