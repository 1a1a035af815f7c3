"""Host time at the training segment boundary, while the chip waits.

For each ``train.segment.wait`` span of the program in the traced stretch,
the time from its end (the device finished the segment) to the start of the
next ``train.segment.dispatch`` (the next segment is handed to the device):
the read-backs, the accounting and whatever the caller does between
segments. The median over the segments, in ms, from the program's host spans
on the profiler trace's clock. Moves ``train_samples_per_s``.
"""
import statistics

import scopes


def read(ctx):
    sc = scopes.load(ctx)
    if sc is None:
        return None
    starts = sorted(s for _, s, _, _ in scopes.spans_in_window(sc, "train.segment.dispatch"))
    gaps = []
    for _, _, end, _ in scopes.spans_in_window(sc, "train.segment.wait"):
        nxt = [s for s in starts if s >= end]
        if nxt:
            gaps.append((nxt[0] - end) * 1e-6)
    return statistics.median(gaps) if gaps else None
