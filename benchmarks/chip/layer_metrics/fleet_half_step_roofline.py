"""The dense fleet half-step kernel's share of its roofline.

Per iteration the dense half-step of m nodes on B rows of width d needs at
the least (``required``): every entry of the sampled rows read once (4 B) and
its label (4 B), m·B·(4d + 4) B; the weights read and written once,
8·m·B·d B; and 4·m·B·d flops (margin and sub-gradient); with C =
``dataset.n_classes`` classes (default 1) the weights and the flops count C
times. The share is
iterations × max(flops / peak FLOP/s, bytes / peak HBM B/s) ÷ the device
seconds of the leaf operations the kernel's ``pallas_call`` name
(``fleet_half_step``) finds in the traced stretch. Moves
``train_samples_per_s``.
"""
import scopes

KERNEL = r"(?<!\w)fleet_half_step(?!\w)"


def required(config):
    m, B = config["gadget"]["n_nodes"], config["gadget"]["batch_size"]
    d, C = config["dataset"]["d"], config["dataset"].get("n_classes", 1)
    return 4 * m * B * d * C, m * B * (4 * d + 4) + 8 * m * B * d * C


def read(ctx):
    sc, segs, peaks = scopes.load(ctx), ctx.segments(), ctx.peaks
    seconds = scopes.kernel_seconds(sc, KERNEL) if sc else 0.0
    iters = sum(r for _, _, r in segs)
    if not seconds or not iters or peaks is None:
        return None
    flops, nbytes = required(ctx.run.config)
    least = iters * max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
