"""Seconds from the process's start to the window's: JAX start-up, graph
generation, upload, and the warm-up queries with their compiles."""


def read(run):
    return run.setup_s
