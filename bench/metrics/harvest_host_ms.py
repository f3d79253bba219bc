"""Host milliseconds in the engine's harvest phase (``serve.harvest``: all
of a step after its sync) per engine step (``serve.step``) that lies in the
window, over the spans of those steps. Read from the serving layer's
default span log; nothing where the program records no spans or no step
lies in the window."""
from bench.spans import span_ms_per_step


def read(run):
    return span_ms_per_step(run, "harvest_host_ms", "serve.harvest", (
        "serve.step", "serve.harvest", "serve.round_dispatch",
        "serve.sync"))
