"""Device time of the Push-Sum mix per training iteration.

Device seconds of the leaf operations in the traced stretch whose innermost
``gadget.*`` scope is ``gadget.push_sum_mix`` (this iteration's mixing
matrices, the R rounds or their collapsed product, the renormalizing divide)
÷ the iterations of the segments that ran in the stretch, in µs. Moves
``train_samples_per_s``.
"""
import scopes


def read(ctx):
    sc, segs = scopes.load(ctx), ctx.segments()
    seconds = scopes.scope_seconds(sc).get("gadget.push_sum_mix") if sc else None
    iters = sum(r for _, _, r in segs)
    if not seconds or not iters:
        return None
    return 1e6 * seconds / iters
