"""Server: host time per dispatch.  Each ``server/device`` span (the
``device`` stage of ``QueryServer.query_many``, a profiler annotation on the
trace's clock) less the part of it in which the search program ran on a
chip (its ``XLA Modules`` events), averaged over the traced dispatches: the
operands' transfer, the launch, the wait for and the copy of the answers,
and ``unpack_ids64``.  A program without the span reads nothing."""

import re

from bench import xtrace

SPAN = "server/device"
PROGRAM = re.compile(r"search_batch|local_search")


def read(ctx):
    if ctx.trace is None:
        return None
    spans = [(s, d) for name, s, d in xtrace.host_events(ctx.trace)
             if name == SPAN]
    if not spans:
        return None
    runs = [(e[1], e[2]) for p in xtrace.device_planes(ctx.trace)
            for e in xtrace.line_events(p, xtrace.MODULES_LINE)
            if PROGRAM.search(e[0])]
    host = [d - xtrace.union_ns(runs, s, s + d) for s, d in spans]
    return sum(host) / len(host) / 1e6
