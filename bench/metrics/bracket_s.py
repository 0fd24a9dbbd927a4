"""Seconds per certified bracket: the timed interval over the answers it
completed (every query ends in a host fetch, so its device work is in)."""


def read(run):
    n = len(run.queries)
    return run.window_s / n if n else None
