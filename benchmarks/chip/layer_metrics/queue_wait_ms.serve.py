"""Median time a request waited in the micro-batcher's queue.

From the request's due time to the start of the ``drain()`` that scored it,
both on the benchmark's own clock, over every answered request due in the
traced stretch.
Moves ``serve_p99_ms``.
"""
import numpy as np


def read(ctx):
    run = ctx.run
    if run.drain_start is None:
        return None
    wait = (run.drain_start - run.due)[ctx.requests() & np.isfinite(run.drain_start)]
    return float(np.median(wait) * 1e3) if len(wait) else None
