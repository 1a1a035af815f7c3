"""Share of the traced serving window in which no operation ran on the chip.

1 − (union of the device's operation intervals) / window, from the profiler
trace (``tracing.busy_s``). Moves ``serve_p99_ms``.
"""
import tracing


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - tracing.busy_s(ctx.trace) / ctx.trace.window_s)
