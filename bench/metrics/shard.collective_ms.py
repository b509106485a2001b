"""Cross-shard merge: device time of the collective operations per search
dispatch and chip."""

from bench import xtrace

PROGRAM = r"search_batch|local_search"


def read(ctx):
    if ctx.trace is None or ctx.chips < 2:
        return None
    coll, _ = xtrace.matching(xtrace.op_totals(ctx.trace), ctx.collectives)
    _, runs = xtrace.matching(
        xtrace.op_totals(ctx.trace, xtrace.MODULES_LINE), PROGRAM)
    if not runs:
        return None
    return coll / runs / 1e6
