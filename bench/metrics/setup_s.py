"""Seconds from process start to the window's opening: interpreter and
imports, weights, engine, warm-up and compilation (or loading compiled
programs from the cache), the document fill where the mix has documents,
and the traffic served before the window."""


def read(run):
    return run.setup_s
