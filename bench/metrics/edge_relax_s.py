"""Edge-relax kernel: device seconds in the kernel's operations during one
traced bracket (query 0 again, after the window)."""


def read(run):
    t = run.trace
    if not t or not t["kernel_calls"].get("edge_relax"):
        return None
    return t["kernel_s"]["edge_relax"]
