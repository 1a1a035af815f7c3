"""Device-to-host reads per training segment.

The program's ``train.readback`` spans (one per array read back at a segment
boundary) in the traced stretch ÷ its ``train.segment`` spans there. Each read
is a round trip in which the chip waits. Moves ``train_samples_per_s``.
"""
import scopes


def read(ctx):
    sc = scopes.load(ctx)
    if sc is None:
        return None
    segs = scopes.spans_in_window(sc, "train.segment")
    if not segs:
        return None
    return len(scopes.spans_in_window(sc, "train.readback")) / len(segs)
