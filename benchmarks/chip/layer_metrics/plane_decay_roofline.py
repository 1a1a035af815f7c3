"""The sparse half-step's plane-wide decay-and-scatter: its share of its roofline.

With the touched-block schedule the kernels return only the touched blocks'
sub-gradient buckets, and the Pegasos axpy (1 − λα)·W plus the scatter of
those buckets runs over the whole (m, C, d) weight plane under the program's
``gadget.decay`` device scope. Per iteration it needs at the least
(``required``) the plane read and written once, 8·m·C·d B, C =
``dataset.n_classes`` (default 1); its flops (two a weight) never bind. The
share is iterations × bytes / peak HBM B/s ÷ the device seconds of the leaf
operations whose innermost scope is ``gadget.decay`` in the traced stretch;
null where the program has no such scope. Moves ``train_samples_per_s``.
"""
import scopes


def required(config):
    m, d = config["gadget"]["n_nodes"], config["dataset"]["d"]
    return 8 * m * config["dataset"].get("n_classes", 1) * d


def read(ctx):
    sc, segs, peaks = scopes.load(ctx), ctx.segments(), ctx.peaks
    seconds = scopes.scope_seconds(sc).get("gadget.decay") if sc else None
    iters = sum(r for _, _, r in segs)
    if not seconds or not iters or peaks is None:
        return None
    return 100.0 * iters * required(ctx.run.config) / peaks["hbm_bytes_per_s"] / seconds
