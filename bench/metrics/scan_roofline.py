"""Sketch-scan kernel: share of its roofline, in %.  The bound is the bytes
the scan needs per dispatch (``bench.roofline.scan_bytes``, from the traced
dispatches' own queries) over the chip's HBM bandwidth; the scan is
memory-bound (about one operation per byte read).  The share is the bound
over the kernel's measured time per dispatch."""

from bench import xtrace

KERNEL = r"^tile_scores"


def read(ctx):
    if ctx.trace is None or not ctx.dispatch_bytes:
        return None
    total, count = xtrace.matching(xtrace.op_totals(ctx.trace), KERNEL)
    if not count:
        return None
    kernel_s = total / count / 1e9
    bound_s = (sum(ctx.dispatch_bytes) / len(ctx.dispatch_bytes)
               / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * bound_s / kernel_s
