"""Device milliseconds per verify round: device time of the round-loop
programs in the traced window over the rounds they ran."""


def read(run):
    if run.trace is None or not run.trace.classified:
        return None
    rounds = sum(s.rounds for s in run.steps_in_window())
    t = run.trace.programs.get("round")
    return 1e3 * t / rounds if t and rounds else None
