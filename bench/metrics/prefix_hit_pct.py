"""Share of the prompt tokens of requests admitted in the window that the
prefix cache served: prefix-hit blocks times block size over prompt
tokens."""


def read(run):
    ws, we = run.window
    hit = total = 0
    for rec in run.records:
        t = rec.req.admit_time if rec.req is not None else 0.0
        if ws <= t <= we:
            hit += rec.req.prefix_hit_blocks * run.engine["block_size"]
            total += rec.prompt_len
    return 100.0 * hit / total if total else None
