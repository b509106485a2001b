"""Sketch-scan kernel: device time of the Pallas kernel
(``sinnamon_score.tile_scores``, one custom call per dispatch and chip)
per dispatch, averaged over the chips."""

from bench import xtrace

KERNEL = r"^tile_scores"


def read(ctx):
    if ctx.trace is None:
        return None
    total, count = xtrace.matching(xtrace.op_totals(ctx.trace), KERNEL)
    if not count:
        return None
    return total / count / 1e6
