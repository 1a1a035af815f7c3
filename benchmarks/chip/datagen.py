"""Device data maker: a configuration's data planes, made on the chip from a seed.

The shapes follow the configuration file's ``dataset`` block, which copies the
paper's Table 2 signature (rows, d, nonzeros per row, Zipf column profile,
label model). Everything is made in one jitted call, in the dtype the system
trains on (float32 values, int32 columns), already laid out as the (m, n_i, ...)
node partitions the training loop takes. Rows are drawn i.i.d., so node i simply
owns rows [i·n_i, i·n_i + count_i); padded rows carry col 0, value 0 and label 0.

Sparse rows (``layout: "ell"``) hold ``nnz_per_row`` distinct columns drawn with
Zipf popularity P(col = r) ∝ (r + 1)^-col_skew. Drawing with replacement and
keeping the first ``nnz_per_row`` distinct draws is exact weighted sampling
without replacement (each next distinct column is drawn in proportion to its
weight among those not yet taken). Draws use Walker's alias method; a row that
gets fewer than ``nnz_per_row`` distinct columns in ``ALIAS_DRAWS`` draws keeps
the ones it got (the rest is padding). Dense rows (``layout: "dense"``) hold
``nnz_per_row`` uniformly chosen nonzero columns. Values are |N(0, 1)|, rows
are scaled to unit norm, and labels threshold y = x·w* at the class-balance
quantile, then flip with the label noise.

With ``n_classes`` C in the ``dataset`` block the rows are drawn the same way
and each valid row gets one of C classes instead: the argmax over c of its
planted margin x·w*_c, standardized over the split's valid rows, plus a
per-class offset; W* (C, d) is N(0, 1). The configuration states its class
profile as ``class_shares`` (C positive numbers, normalized by their sum, so a
source's per-class row counts may be given as they are), and the offsets are
fitted so that the argmax gives each class its share of the split's valid
rows, as the class-balance quantile does for two classes (``fit_offsets``).
Then, with probability label_noise, the class is replaced by a uniformly drawn
other one, as two-class labels flip after their threshold. ``y`` is then the
one-vs-rest layout (…, C): +1 for the row's class, −1 for every other, 0 on
padded rows. Margins are computed in row chunks (``CHUNK_MARGINS``), so no
(rows, k, C) temporary is made.

A serving mix may give the test rows a length distribution (``query_nnz``):
each row then keeps the first L distinct draws, L drawn from the seed, with
enough draws per row (``alias_draws``) for the longest.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ALIAS_DRAWS = 384      # per sparse row; 76 distinct Zipf(1.25) columns need ≤ 260
CHUNK_ROWS = 32768     # sparse rows deduplicated per map step
CHUNK_MARGINS = 4096   # sparse rows per map step of the C-class margins
BINARY_KEYS = ("layout", "n_train", "n_test", "d", "nnz_per_row", "col_skew",
               "label_noise", "class_balance")
CLASS_KEYS = ("layout", "n_train", "n_test", "d", "nnz_per_row", "col_skew",
              "label_noise", "n_classes", "class_shares")
FIT_STEPS = 200        # annealed Sinkhorn steps that fit the class offsets
FIT_TAUS = (0.3, 0.005)  # their first and last temperature, on unit-variance margins


def node_counts(n: int, m: int) -> tuple[np.ndarray, int]:
    """Near-equal split of n rows over m nodes: (counts, padded rows per node)."""
    q, r = divmod(n, m)
    counts = np.full(m, q, np.int64)
    counts[:r] += 1
    return counts, q + (1 if r else 0)


def alias_table(d: int, skew: float) -> tuple[np.ndarray, np.ndarray]:
    """Walker/Vose alias table of the Zipf(skew) profile over d columns."""
    p = np.arange(1, d + 1, dtype=np.float64) ** -skew
    q = p * (d / p.sum())
    prob = np.ones(d)
    alias = np.arange(d, dtype=np.int32)
    small = list(np.nonzero(q < 1.0)[0])
    large = list(np.nonzero(q >= 1.0)[0])
    while small and large:
        s, big = small.pop(), large.pop()
        prob[s], alias[s] = q[s], big
        q[big] -= 1.0 - q[s]
        (small if q[big] < 1.0 else large).append(big)
    return prob.astype(np.float32), alias


def alias_draws(d: int, skew: float, nnz: int) -> int:
    """Draws per sparse row for rows of up to ``nnz`` distinct columns: the
    least of ALIAS_DRAWS·2^j at which the expected distinct count is 1.5·nnz."""
    p = np.arange(1, d + 1, dtype=np.float64) ** -skew
    p /= p.sum()
    draws = ALIAS_DRAWS
    while -np.expm1(draws * np.log1p(-p)).sum() < 1.5 * nnz and draws < 64 * ALIAS_DRAWS:
        draws *= 2
    return draws


def _zipf_rows(key, n_rows, d, nnz, prob, alias, lengths=None, draws=ALIAS_DRAWS):
    """(n_rows, nnz) sorted distinct columns and a live mask, by chunks. With
    ``lengths`` (n_rows,), row r keeps its first lengths[r] distinct draws."""
    n_chunks = -(-n_rows // CHUNK_ROWS)
    rows = CHUNK_ROWS
    if lengths is None:
        lengths = jnp.full((n_rows,), nnz, jnp.int32)
    lengths = jnp.pad(lengths, (0, n_chunks * rows - n_rows)).reshape(n_chunks, rows)

    def chunk(args):
        k, keep = args
        k1, k2 = jax.random.split(k)
        shape = (rows, draws)
        i = jax.random.randint(k1, shape, 0, d, jnp.int32)
        c = jnp.where(jax.random.uniform(k2, shape) < prob[i], i, alias[i])
        pos = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        sc, sp = jax.lax.sort((c, pos), dimension=1, num_keys=2)
        first = jnp.concatenate([jnp.ones((rows, 1), bool),
                                 sc[:, 1:] != sc[:, :-1]], axis=1)
        order, cand = jax.lax.sort((jnp.where(first, sp, draws), sc),
                                   dimension=1, num_keys=1)
        live = (order[:, :nnz] < draws) & (
            jax.lax.broadcasted_iota(jnp.int32, (rows, nnz), 1) < keep[:, None])
        return jnp.sort(jnp.where(live, cand[:, :nnz], d), axis=1)

    cols = jax.lax.map(chunk, (jax.random.split(key, n_chunks), lengths))
    cols = cols.reshape(n_chunks * rows, nnz)[:n_rows]
    live = cols < d
    return jnp.where(live, cols, 0), live


def _row_lengths(key, n_rows, q):
    """Query lengths: round(median · exp(sigma · N(0, 1))), clipped to [1, max]."""
    if q["dist"] != "lognormal":
        raise LookupError(f"unknown query length distribution {q['dist']!r} (known: lognormal)")
    z = jax.random.normal(key, (n_rows,))
    return jnp.clip(jnp.round(q["median"] * jnp.exp(q["sigma"] * z)), 1, q["max"]).astype(jnp.int32)


def _uniform_mask(key, n_rows, d, nnz):
    """(n_rows, d) mask with exactly nnz uniformly chosen columns per row."""
    bits = jax.random.bits(key, (n_rows, d), jnp.uint32)
    kth = jax.lax.top_k(bits, nnz)[0][:, -1:]
    return bits >= kth


def _labels(key, margin, valid, spec):
    """Threshold at the class-balance quantile of the valid rows, then flip."""
    thr = jnp.nanquantile(jnp.where(valid, margin, jnp.nan), 1.0 - spec["class_balance"])
    y = jnp.where(margin > thr, 1.0, -1.0)
    flip = jax.random.uniform(key, margin.shape) < spec["label_noise"]
    return jnp.where(valid, jnp.where(flip, -y, y), 0.0).astype(jnp.float32)


def class_shares(spec) -> np.ndarray:
    """(C,) the configuration's ``class_shares``, normalized by their sum."""
    p = np.asarray(spec["class_shares"], np.float64)
    if p.shape != (spec["n_classes"],) or not np.all(p > 0):
        raise ValueError(f"class_shares must be n_classes = {spec['n_classes']} "
                         f"positive numbers, got {spec['class_shares']!r}")
    return p / p.sum()


def fit_offsets(z, w, shares):
    """(C,) offsets b at which the argmax over c of z + b, over the rows (n, C)
    weighted by w (n,), gives each class its share: annealed Sinkhorn, which
    moves b until the soft argmax softmax((z + b)/τ) gives every class its
    share while τ falls geometrically over FIT_TAUS in FIT_STEPS steps; at the
    last τ the soft and the hard argmax part a handful of rows."""
    log_p = jnp.log(jnp.asarray(shares, jnp.float32))
    n = jnp.sum(w)

    def step(b, tau):
        soft = jax.nn.softmax((z + b) / tau, axis=1)
        s = jnp.sum(soft * w[:, None], axis=0) / n
        return b + tau * (log_p - jnp.log(jnp.maximum(s, 1e-30))), None

    taus = jnp.asarray(np.geomspace(*FIT_TAUS, FIT_STEPS), jnp.float32)
    return jax.lax.scan(step, jnp.zeros(z.shape[1], jnp.float32), taus)[0]


def _class_margins(rows, w_star):
    """(n_rows, C) margins x·w*_c: ELL rows gather rows of W*ᵀ (d, C) by chunks
    of CHUNK_MARGINS rows; dense rows take one matmul."""
    if not isinstance(rows, tuple):
        return jnp.matmul(rows, w_star.T, precision=HIGHEST)
    cols, vals = rows
    n_rows = cols.shape[0]
    n_chunks = -(-n_rows // CHUNK_MARGINS)
    pad = ((0, n_chunks * CHUNK_MARGINS - n_rows), (0, 0))
    wt = w_star.T

    def chunk(args):
        c, v = args
        return jnp.einsum("rk,rkc->rc", v, wt[c], precision=HIGHEST)

    out = jax.lax.map(chunk, (jnp.pad(cols, pad).reshape(n_chunks, CHUNK_MARGINS, -1),
                              jnp.pad(vals, pad).reshape(n_chunks, CHUNK_MARGINS, -1)))
    return out.reshape(n_chunks * CHUNK_MARGINS, -1)[:n_rows]


def _class_labels(key, margin, valid, spec):
    """One-vs-rest ±1 labels (n_rows, C) of the argmax of the standardized
    margins plus the fitted class offsets; a label_noise share moves to
    another class."""
    C = spec["n_classes"]
    w = valid.astype(jnp.float32)[:, None]
    n = jnp.sum(w)
    mean = jnp.sum(margin * w, axis=0) / n
    std = jnp.sqrt(jnp.sum(jnp.square(margin - mean) * w, axis=0) / n)
    z = (margin - mean) / jnp.maximum(std, 1e-30)
    cls = jnp.argmax(z + fit_offsets(z, w[:, 0], spec["class_shares"]), axis=1).astype(jnp.int32)
    k_flip, k_other = jax.random.split(key)
    other = jax.random.randint(k_other, cls.shape, 0, C - 1)
    other = other + (other >= cls)
    cls = jnp.where(jax.random.uniform(k_flip, cls.shape) < spec["label_noise"], other, cls)
    y = jnp.where(cls[:, None] == jnp.arange(C)[None, :], 1.0, -1.0)
    return jnp.where(valid[:, None], y, 0.0).astype(jnp.float32)


def _unit_rows(vals):
    return vals / jnp.maximum(jnp.linalg.norm(vals, axis=-1, keepdims=True), 1e-8)


def _split(key, spec, n_rows, valid, w_star, tables, query=None):
    """One split's rows: ELL (cols, vals) or dense X, and labels. ``query``
    (ELL only) draws each row's length from a query length distribution."""
    k_cols, k_vals, k_lab = jax.random.split(key, 3)
    d, nnz = spec["d"], spec["nnz_per_row"]
    lengths, draws = None, ALIAS_DRAWS
    if query is not None:
        nnz, draws = query["max"], query["draws"]
        lengths = _row_lengths(jax.random.fold_in(key, 1), n_rows, query)
    if spec["layout"] == "ell":
        cols, live = _zipf_rows(k_cols, n_rows, d, nnz, *tables, lengths=lengths, draws=draws)
        live = live & valid[:, None]
        vals = jnp.where(live, jnp.abs(jax.random.normal(k_vals, (n_rows, nnz))), 0.0)
        vals = _unit_rows(vals)
        cols = jnp.where(live, cols, 0)
        if "n_classes" in spec:
            return (cols, vals), _class_labels(k_lab, _class_margins((cols, vals), w_star),
                                               valid, spec)
        margin = jnp.sum(vals * w_star[cols], axis=-1)
        return (cols, vals), _labels(k_lab, margin, valid, spec)
    mask = _uniform_mask(k_cols, n_rows, d, nnz) & valid[:, None]
    X = _unit_rows(jnp.where(mask, jnp.abs(jax.random.normal(k_vals, (n_rows, d))), 0.0))
    if "n_classes" in spec:
        return X, _class_labels(k_lab, _class_margins(X, w_star), valid, spec)
    margin = jnp.matmul(X, w_star, precision=HIGHEST)
    return X, _labels(k_lab, margin, valid, spec)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 6))
def _make(key, spec_items, m, with_train, with_test, tables, query_items=None):
    spec = dict(spec_items)
    query = dict(query_items) if query_items else None
    k_w, k_train, k_test = jax.random.split(key, 3)
    if "n_classes" in spec:
        w_star = jax.random.normal(k_w, (spec["n_classes"], spec["d"]))
    else:
        w_star = jnp.abs(jax.random.normal(k_w, (spec["d"],)))
    out = {}
    if with_train:
        counts, n_i = node_counts(spec["n_train"], m)
        valid = (jnp.arange(n_i)[None, :] < jnp.asarray(counts)[:, None]).reshape(-1)
        X, y = _split(k_train, spec, m * n_i, valid, w_star, tables)
        out["y"] = y.reshape((m, n_i) + y.shape[1:])
        if spec["layout"] == "ell":
            out["cols"], out["vals"] = (a.reshape(m, n_i, -1) for a in X)
        else:
            out["X"] = X.reshape(m, n_i, spec["d"])
    if with_test:
        n_te = spec["n_test"]
        (out["test_cols"], out["test_vals"]), out["test_y"] = _split(
            k_test, spec, n_te, jnp.ones((n_te,), bool), w_star, tables, query)
    return out


def make_data(seed: int, dataset: dict, m: int, *, with_train: bool = True,
              with_test: bool = False, query_nnz: dict | None = None) -> dict:
    """A configuration's device arrays from ``seed`` in one jitted call.

    Returns a dict of device arrays — with ``with_train`` the node partitions
    ``cols``/``vals`` (m, n_i, k) or ``X`` (m, n_i, d) and ``y`` (m, n_i), or
    (m, n_i, C) with ``dataset["n_classes"]`` C (one-vs-rest ±1), with
    ``with_test`` the test split's ``test_cols``/``test_vals``/``test_y`` (ELL
    only) — plus the host-side ``counts`` (m,) of valid rows per node. Each
    split's rows are the same whether or not the other split is made.

    ``query_nnz`` ({"dist": "lognormal", "median", "sigma", "max"}) gives the
    test rows lengths drawn from that distribution instead of the
    configuration's fixed nonzeros per row; the test planes are then ``max``
    wide. The training split does not change.
    """
    spec = {k: dataset[k] for k in (CLASS_KEYS if "n_classes" in dataset else BINARY_KEYS)}
    if "n_classes" in spec:
        spec["class_shares"] = tuple(float(p) for p in class_shares(spec))
    tables = None
    if spec["layout"] == "ell":
        tables = tuple(jnp.asarray(a) for a in alias_table(spec["d"], spec["col_skew"]))
    query = None
    if query_nnz is not None:
        if spec["layout"] != "ell":
            raise ValueError("query lengths need ELL rows")
        query = dict(query_nnz, draws=alias_draws(spec["d"], spec["col_skew"],
                                                  int(query_nnz["max"])))
        query = tuple(sorted(query.items()))
    out = _make(jax.random.PRNGKey(seed), tuple(sorted(spec.items())), m, with_train,
                with_test and spec["layout"] == "ell", tables, query)
    out["counts"] = node_counts(spec["n_train"], m)[0]
    return out
