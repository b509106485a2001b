"""Front door: mean live requests per dispatch over ``max_batch``, in %,
from the ``repro_frontend_batch_size`` histogram over the window."""


def read(ctx):
    total, count = ctx.batch_sizes
    if not count:
        return None
    return 100.0 * total / count / ctx.max_batch
