"""Device: 1 minus the union of the operations' intervals over the traced
window, in %, averaged over the chips."""

from bench import xtrace


def read(ctx):
    if ctx.trace is None:
        return None
    busy = xtrace.busy_ns(ctx.trace)
    if not busy:
        return None
    window = ctx.trace["window_ns"][1]
    return 100.0 * (1.0 - sum(busy) / len(busy) / window)
