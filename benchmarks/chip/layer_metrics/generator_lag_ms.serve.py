"""99th percentile of how late the load generator sent a request.

Actual submit time minus due time, on the benchmark's own clock, over every
request due in the traced stretch. A starved generator would make the server
look fast. Moves ``serve_p99_ms``.
"""
import numpy as np


def read(ctx):
    run = ctx.run
    if run.sent is None:
        return None
    lag = (run.sent - run.due)[ctx.requests() & np.isfinite(run.sent)]
    return float(np.percentile(lag, 99, method="higher") * 1e3) if len(lag) else None
