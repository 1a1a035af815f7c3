"""The serving path's share of the chip's peak over the traced stretch.

The required work of every query due and answered in the stretch (its k
nonzeros read once, 8 B each, the k weights they touch, 4 B each, and its
score written, 4 B; 2 flops per nonzero) under the bound that binds (bytes), over the
stretch's wall time on the host clock: max(flops / peak FLOP/s, bytes / peak
HBM B/s) / seconds. It bounds any scoring kernel's roofline share from below.
Moves ``serve_p99_ms``.
"""
import numpy as np


def required(nnz):
    """(flops, bytes) of scoring queries with ``nnz`` nonzeros each."""
    k, n = int(np.sum(nnz)), len(nnz)
    return 2 * k, 12 * k + 4 * n


def read(ctx):
    run, peaks = ctx.run, ctx.peaks
    if run.due is None or peaks is None:
        return None
    scored = ctx.requests() & np.isfinite(run.score)
    if not scored.any():
        return None
    flops, nbytes = required(run.query_nnz[scored])
    least = max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ctx.stretch[1] - ctx.stretch[0] - 0.1)
