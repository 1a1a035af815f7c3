"""The whole training step's share of the chip's peak, under the bound that binds.

Per iteration the paper's step needs at the least (``required``):

  bytes  every data entry of the m·B sampled rows read once (column id and
         value, 8 B, for ELL; the 4 B value for dense rows) and its label;
         the weights those entries touch read and written once (4 B each);
         the dense (m, d) weight plane read and written once for the Push-Sum
         mix and the two projections; and, once per ε-check of check_every
         iterations, the plane and its previous copy read for ‖W − W_prev‖
  flops  margins and sub-gradient (2 + 2 per entry), the decay-and-add of
         every weight (2 per weight), the m×m mix (2·m per weight) and the two
         projections (3 per weight each)

With C = ``dataset.n_classes`` one-vs-rest classes (default 1) the weights are
(m, C, d): the touched weights, the plane, the ε-check and every weight's flops
count C times; the data does not, a label being one class id of 4 B whatever
implements it. At B = 1 the bytes bind by far. The share is
max(flops / peak FLOP/s, bytes / peak HBM B/s) × iterations of the segments
in the traced stretch / those segments' seconds on the host clock. The
full-data objective pass at each ε-check is left out: the stopping rule does
not need it. Moves ``train_samples_per_s``.
"""


def required(config):
    ds, g = config["dataset"], config["gadget"]
    m, B, d, C = g["n_nodes"], g["batch_size"], ds["d"], ds.get("n_classes", 1)
    ell = ds["layout"] == "ell"
    k = ds["nnz_per_row"] if ell else d
    entry_bytes = 8 if ell else 4
    data = m * B * (k * entry_bytes + 4)
    touched = 2 * 4 * m * B * k * C
    plane = 2 * 4 * m * C * d + 2 * 4 * m * C * d / g["check_every"]
    flops = 4 * m * B * k * C + m * C * d * (2 + 2 * m + 6)
    return flops, data + touched + plane


def read(ctx):
    segs, peaks = ctx.segments(), ctx.peaks
    if not segs or peaks is None:
        return None
    flops, nbytes = required(ctx.run.config)
    iters = sum(r for _, _, r in segs)
    seconds = segs[-1][1] - segs[0][0]
    least = iters * max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
