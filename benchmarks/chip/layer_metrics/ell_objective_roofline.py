"""The ELL objective-pass kernel's share of its roofline.

Each segment's full-data objective pass over n_train rows of k nonzeros needs
at the least (``required``): every entry read once (column id and value,
8 B) and every label (4 B), n_train·(8k + 4) B, and w read once, 4·d B; and
n_train·(2k + 4) flops (a multiply-add per entry, then the label product,
the hinge and the sum). With C = ``dataset.n_classes`` classes (default 1) W is
read once, 4·C·d B, and each row's flops count C times, n_train·C·(2k + 4);
the entries and labels are read once whatever C is. The share is segments ×
max(flops / peak FLOP/s, bytes / peak HBM B/s) ÷ the device seconds of the
leaf operations the kernel's ``pallas_call`` name (``ell_objective``) finds in
the traced stretch; null where no such operation ran (the ``jnp.take`` pass, or a dense
configuration). Moves ``train_samples_per_s``.
"""
import scopes

KERNEL = r"(?<!\w)ell_objective(?!\w)"


def required(config):
    ds = config["dataset"]
    n, k, d, C = ds["n_train"], ds["nnz_per_row"], ds["d"], ds.get("n_classes", 1)
    return n * C * (2 * k + 4), n * (8 * k + 4) + 4 * C * d


def read(ctx):
    sc, segs, peaks = scopes.load(ctx), ctx.segments(), ctx.peaks
    seconds = scopes.kernel_seconds(sc, KERNEL) if sc else 0.0
    if not seconds or not segs or peaks is None:
        return None
    flops, nbytes = required(ctx.run.config)
    least = len(segs) * max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
