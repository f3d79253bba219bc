"""Whole-step share of the chip's peak: forward FLOPs required by every
output token emitted and every prompt token prefilled in the traced window
(counted by the configuration's family: for dense GQA 2 x matmul parameters
+ 4 L H d position each; rejected window positions, padding and tiles past
a row's length do not count) over window x peak."""
from bench import model


def read(run):
    if run.trace is None:
        return None
    fam = model.family(run.cfg)
    flops = 0
    for s in run.steps_in_window():
        for ell, _, n in s.rows:
            flops += fam.span_flops(run.cfg, ell + 1, ell + 1 + n)
        for start, end in s.prefills:
            flops += fam.span_flops(run.cfg, start, end)
    return (100.0 * flops / (run.trace.window_s * run.peaks.bf16_flops)
            if flops else None)
