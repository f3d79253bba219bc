"""Host time of the serving engine's spans (the default ``SpanLog`` of
``repro.serving.metrics``) per engine step in a run's window: what the
span readers in ``bench/metrics`` share."""


def span_ms_per_step(run, reader: str, span: str, noted) -> float | None:
    """Host ms in ``span`` per ``serve.step`` span that lies in
    ``run.window``, over the spans recorded in those steps (a step without
    ``span`` counts 0). None where the program keeps no span log or no
    step lies in the window. Notes, under ``reader``, the window's seconds
    in each span of ``noted``, and whether the log still holds the
    window's start (else spans were dropped)."""
    try:
        from repro.serving.metrics import default_span_log
    except ImportError:
        return None
    log = default_span_log()
    ws, we = run.window
    steps = {(s.engine, s.step) for s in log.spans(ws, we)
             if s.name == "serve.step"}
    if not steps:
        return None
    if log.records[0].t0 > ws:
        run.note(f"{reader}: the span log kept nothing before "
                 f"{log.records[0].t0 - ws:.3f} s into the window: spans "
                 "were dropped")
    total = {}
    for s in log.records:
        if (s.engine, s.step) in steps:
            total[s.name] = total.get(s.name, 0.0) + (s.t1 - s.t0)
    run.note(f"{reader}: window totals " + ", ".join(
        f"{name} {total.get(name, 0.0)!r} s" for name in noted)
        + f" over {len(steps)} steps")
    return 1e3 * total.get(span, 0.0) / len(steps)
