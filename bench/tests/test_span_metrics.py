"""The readers of the engine's host spans, ``admit_host_ms`` and
``harvest_host_ms``, on a span log built by hand."""
import pytest

from bench.run import Run, load_reader
from repro.serving import metrics
from repro.serving.metrics import Span, SpanLog

MS = 1e-3


def _log(*steps):
    """A log of one engine's steps; each step is ``(t0, t1, phases)`` with
    ``phases`` a list of ``(name, t0, t1)`` children of the step."""
    log = SpanLog()
    for k, (t0, t1, phases) in enumerate(steps):
        for name, a, b in phases:
            log.records.append(Span(name, a, b, k, None, 0))
        log.records.append(Span("serve.step", t0, t1, k, None, 0))
    return log


def _step(t, admit=0.0, harvest=0.0):
    """A 100-ms step at ``t`` whose admission and harvest take the given
    milliseconds (0: the phase recorded no span)."""
    phases = []
    if admit:
        phases += [("serve.admit_request", t, t + admit * MS),
                   ("serve.admit", t, t + admit * MS)]
    phases.append(("serve.round_dispatch", t + 0.010, t + 0.011))
    phases.append(("serve.sync", t + 0.011, t + 0.090))
    if harvest:
        phases.append(("serve.harvest", t + 0.090, t + 0.090 + harvest * MS))
    return (t, t + 0.100, phases)


@pytest.fixture
def use_log(monkeypatch):
    def use(log):
        monkeypatch.setattr(metrics, "default_span_log", lambda: log)
    return use


def _run(window):
    return Run(window=window)


@pytest.mark.parametrize("name,expect", [
    # steps at 1.0, 1.1, 1.2 lie in the window; the admission-free step
    # counts as 0; steps before, after and across its edges do not count
    ("admit_host_ms", (4.0 + 0.0 + 2.0) / 3),
    ("harvest_host_ms", (3.0 + 5.0 + 0.0) / 3),
])
def test_reader_averages_over_the_window_steps(use_log, name, expect):
    use_log(_log(_step(0.5, admit=50.0, harvest=50.0),
                 _step(0.95, admit=50.0, harvest=50.0),
                 _step(1.0, admit=4.0, harvest=3.0),
                 _step(1.1, harvest=5.0),
                 _step(1.2, admit=2.0),
                 _step(1.25, admit=50.0, harvest=50.0),
                 _step(2.0, admit=50.0, harvest=50.0)))
    run = _run((0.99, 1.31))
    assert load_reader(name)(run) == pytest.approx(expect)
    assert len(run.notes) == 1 and "over 3 steps" in run.notes[0]
    assert "dropped" not in run.notes[0]


@pytest.mark.parametrize("name", ["admit_host_ms", "harvest_host_ms"])
def test_reader_reports_nothing_without_steps(use_log, name):
    use_log(SpanLog())
    assert load_reader(name)(_run((0.0, 1.0))) is None
    use_log(_log(_step(0.5, admit=1.0, harvest=1.0)))
    run = _run((1.0, 2.0))
    assert load_reader(name)(run) is None
    assert run.notes == []


@pytest.mark.parametrize("name", ["admit_host_ms", "harvest_host_ms"])
def test_reader_notes_dropped_spans(use_log, name):
    log = _log(*(_step(1.0 + 0.1 * i, admit=1.0, harvest=1.0)
                 for i in range(5)))
    log.records = type(log.records)(list(log.records)[-12:], maxlen=12)
    use_log(log)
    run = _run((0.9, 2.0))
    assert load_reader(name)(run) == pytest.approx(1.0)
    assert any("dropped" in line for line in run.notes)
