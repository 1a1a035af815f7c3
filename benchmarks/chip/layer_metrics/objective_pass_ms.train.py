"""Device time of the full-data objective pass per training segment.

Device seconds of the leaf operations in the traced stretch whose innermost
``gadget.*`` scope is ``gadget.objective`` (``scopes.scope_seconds``), ÷ the
segments that ran in the stretch, in ms. The pass runs once per segment, at
its ε-check, and the stopping rule does not read it. Moves
``train_samples_per_s``.
"""
import scopes


def read(ctx):
    sc, segs = scopes.load(ctx), ctx.segments()
    seconds = scopes.scope_seconds(sc).get("gadget.objective") if sc else None
    if not seconds or not segs:
        return None
    return 1e3 * seconds / len(segs)
