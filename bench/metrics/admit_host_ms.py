"""Host milliseconds in the engine's admission phase (``serve.admit``) per
engine step (``serve.step``) that lies in the window, over the spans of
those steps; steps that admit nothing count as 0. Read from the serving
layer's default span log; nothing where the program records no spans or
no step lies in the window."""
from bench.spans import span_ms_per_step


def read(run):
    return span_ms_per_step(run, "admit_host_ms", "serve.admit", (
        "serve.admit", "serve.admit_request", "serve.prefix_lookup",
        "serve.alloc_blocks", "serve.spill", "serve.slot_state",
        "serve.prefill_dispatch"))
