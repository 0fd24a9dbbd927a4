"""Mean over the window's brackets of ``upper / lower``: speed bought with a
looser bound shows here."""
from bench.metrics._common import done


def read(run):
    r = [q.result.upper / q.result.lower for q in done(run)
         if getattr(q.result, "lower", None) and q.result.upper is not None]
    return sum(r) / len(r) if r else None
