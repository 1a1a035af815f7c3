"""The sparse half-step kernels' share of their roofline.

Per iteration the sparse half-step of m nodes on B rows of k nonzeros needs
at the least (``required``): every entry of the sampled rows read once
(column id and value, 8 B) and its label (4 B), m·B·(8k + 4) B; the weights
those entries touch read and written once, 8·m·B·k B; and 4·m·B·k flops
(margin and sub-gradient); with C = ``dataset.n_classes`` classes (default 1)
the touched weights and the flops count C times. The share is iterations × max(flops / peak FLOP/s,
bytes / peak HBM B/s) ÷ the device seconds of the leaf operations the
kernels' ``pallas_call`` names find (``ell_fleet_half_step_gather`` and
``_update``, or their ``sweep_`` twins) in the traced stretch. Moves
``train_samples_per_s``.
"""
import scopes

KERNELS = r"(?<!\w)ell_fleet_half_step_(?:sweep_)?(?:gather|update)(?!\w)"


def required(config):
    m, B = config["gadget"]["n_nodes"], config["gadget"]["batch_size"]
    k, C = config["dataset"]["nnz_per_row"], config["dataset"].get("n_classes", 1)
    return 4 * m * B * k * C, m * B * (8 * k + 4) + 8 * m * B * k * C


def read(ctx):
    sc, segs, peaks = scopes.load(ctx), ctx.segments(), ctx.peaks
    seconds = scopes.kernel_seconds(sc, KERNELS) if sc else 0.0
    iters = sum(r for _, _, r in segs)
    if not seconds or not iters or peaks is None:
        return None
    flops, nbytes = required(ctx.run.config)
    least = iters * max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
