"""Search program: device time per execution of the jitted search program
(``engine.search_batch`` on one chip, the ``shard_map`` search step of
``serving.sharded.make_search_step`` on several), from the trace's
``XLA Modules`` line, averaged over the chips."""

from bench import xtrace

PROGRAM = r"search_batch|local_search"


def read(ctx):
    if ctx.trace is None:
        return None
    total, count = xtrace.matching(
        xtrace.op_totals(ctx.trace, xtrace.MODULES_LINE), PROGRAM)
    if not count:
        return None
    return total / count / 1e6
