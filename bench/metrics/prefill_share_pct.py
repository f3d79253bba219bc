"""Share of the traced window the device spent in prefill programs."""


def read(run):
    if run.trace is None or not run.trace.classified:
        return None
    t = run.trace.programs.get("prefill", 0.0)
    return 100.0 * t / run.trace.window_s if t else None
