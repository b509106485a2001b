"""Front door: the dispatcher's turnaround between dispatches while a
request waits.  For each pair of consecutive ``server/device`` spans (the
server's device stage, a profiler annotation on the trace's clock) with a
request waiting between them (``ctx.pending``), the time from the end of
the first to the start of the second: the answers handed back, the next
batch taken and assembled.  Mean over those pairs; a program without the
span reads nothing."""

from bench import xtrace

SPAN = "server/device"


def read(ctx):
    if ctx.trace is None or ctx.pending is None:
        return None
    spans = sorted((s, s + d) for name, s, d in xtrace.host_events(ctx.trace)
                   if name == SPAN)
    gaps = [b[0] - a[1] for a, b in zip(spans, spans[1:])
            if b[0] > a[1] and ctx.pending(a[1], b[0])]
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6
