"""Device: share of the traced slice in which no operation ran on the
device (1 - union of the operation intervals / slice), mean over the
cell's chips."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
