"""Seconds of set-up spent making programs: tracing, lowering and compiling,
or loading the compiled program from the persistent cache (jax.monitoring
events)."""


def read(run):
    return run.compile_s
