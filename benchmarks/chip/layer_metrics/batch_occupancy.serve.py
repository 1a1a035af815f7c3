"""Live query rows over the padded bucket rows of every batch scored in the
window, from the micro-batcher's own counters (``requests`` and
``padded_rows``). Moves ``serve_p99_ms``.
"""


def read(ctx):
    run = ctx.run
    if not run.padded_rows:
        return None
    return 100.0 * run.live_rows / run.padded_rows
