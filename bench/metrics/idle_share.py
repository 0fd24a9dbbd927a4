"""Device: % of the traced bracket in which no operation ran on the chip
(1 - union of the operation intervals / the traced window)."""


def read(run):
    t = run.trace
    if not t or t.get("idle_share") is None:
        return None
    return 100.0 * t["idle_share"]
