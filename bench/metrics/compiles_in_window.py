"""Device: XLA programs built (compiled, or loaded from the persistent
cache) after the window opened. The warm-up builds the programs of the
window's queries (query 0 in full, the seed-shaped entries of the later
ones), so a build here means the window ran past them or a shape followed
the seed unforeseen."""


def read(run):
    return run.compiles_in_window
