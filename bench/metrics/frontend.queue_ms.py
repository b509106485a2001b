"""Front door: mean of each request's own ``queue`` stage (enqueue to
dispatch, as ``ServingFrontend`` records it) over the window's requests."""


def read(ctx):
    if not ctx.queue_ms:
        return None
    return sum(ctx.queue_ms) / len(ctx.queue_ms)
