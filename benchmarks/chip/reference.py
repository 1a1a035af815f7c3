"""Plain reference of what the timed paths compute, in straightforward jax.numpy.

It imports nothing of the system under test. Training follows the paper's
Algorithm 2 (GADGET) iteration by iteration, on the same seeded random streams
as the system's training loop — the stream is part of what a seed means there:

  data_key, mix_key = split(PRNGKey(seed))
  iteration t (1-based, int32):
    row ids   node i draws randint(split(fold_in(data_key, t), m)[i], (B,), 0, count_i)
    half-step α = 1/(λt); L = mean over the node's B rows of 1[y·x·w < 1]·y·x;
              w ← (1 − λα)·w + α·L, then projected onto the 1/√λ ball
    gossip    R Push-Sum rounds; round r's matrix B_r = ½·I + ½·onehot(target),
              target = randint(fold_in(fold_in(mix_key, t), r), (m,), 0, m − 1),
              shifted past the node itself; values n_i·w_i and masses n_i are
              pushed as x ← B_rᵀ x each round, then w_i = value_i / mass_i
    project   each w_i onto the 1/√λ ball again
    W_sum     += W

The objective at a segment's end is the primal objective of the data-weighted
consensus Σ n_i w_i / N over every valid training row.

One-vs-rest (``y`` of shape (m, n_i, C), ±1 per class): C fleets in one scan.
This sets the contract that a one-vs-rest training path has to match to be
checked here; it copies no trainer of the system. W and W_sum are (m, C, d);
each node draws one row-id stream that every class shares; all classes share
the Push-Sum matrices and each node's one mass; the half-step is the formula
above for each class; each (node, class) row is projected on its own. Each
segment returns one objective per class, of that class's consensus.

``dtype`` computes the whole iteration in a lower precision (the control), and
``fault`` plants one fault in the step: ``"no_mix"`` leaves out the exchange
between nodes, ``"half_batch"`` leaves out the rows of half the nodes.

Serving: a query's score is Σ_k vals[k]·w[cols[k]], computed on the host in
float64 (or, for the control, in bfloat16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

FAULTS = (None, "no_mix", "half_batch")


def _project(W, lam):
    norm = jnp.linalg.norm(W.astype(jnp.float32), axis=-1, keepdims=True)
    scale = jnp.minimum(1.0, (1.0 / np.sqrt(lam)) / jnp.maximum(norm, 1e-30))
    return W * scale.astype(W.dtype)


def _neighbor_matrix(key, m):
    target = jax.random.randint(key, (m,), 0, m - 1)
    target = target + (target >= jnp.arange(m))
    return 0.5 * jnp.eye(m) + 0.5 * jax.nn.one_hot(target, m)


def _half_step(W, rows, y, lam, t, B):
    """Pegasos half-step for every node. ``rows`` is (cols, vals) of shape
    (m, B, k) or a dense (m, B, d) X."""
    if isinstance(rows, tuple):
        cols, vals = rows
        margins = y * jnp.sum(vals * jax.vmap(lambda w, c: w[c])(W, cols), axis=-1)
        coeff = jnp.where(margins < 1, y, 0).astype(W.dtype)
        L = jax.vmap(lambda c, v, cf: jnp.zeros(W.shape[1], W.dtype)
                     .at[c.reshape(-1)].add((cf[:, None] * v).reshape(-1)))(cols, vals, coeff)
    else:
        margins = y * jnp.einsum("mbd,md->mb", rows, W)
        coeff = jnp.where(margins < 1, y, 0).astype(W.dtype)
        L = jnp.einsum("mb,mbd->md", coeff, rows)
    alpha = 1.0 / (lam * t.astype(jnp.float32))
    return ((1.0 - lam * alpha) * W.astype(jnp.float32)
            + alpha * (L / B).astype(jnp.float32)).astype(W.dtype)


def _half_step_classes(W, rows, y, lam, t, B):
    """The Pegasos half-step of every (node, class): W (m, C, d), y (m, B, C),
    every class on the node's same rows."""
    yc = jnp.swapaxes(y, 1, 2)                                     # (m, C, B)
    if isinstance(rows, tuple):
        cols, vals = rows
        wg = jax.vmap(lambda w, c: w[:, c])(W, cols)               # (m, C, B, k)
        margins = yc * jnp.sum(vals[:, None] * wg, axis=-1)
        coeff = jnp.where(margins < 1, yc, 0).astype(W.dtype)
        C, d = W.shape[1:]
        L = jax.vmap(lambda c, v, cf: jnp.zeros((C, d), W.dtype)
                     .at[:, c.reshape(-1)].add((cf[:, :, None] * v[None]).reshape(C, -1))
                     )(cols, vals, coeff)
    else:
        margins = yc * jnp.einsum("mbd,mcd->mcb", rows, W)
        coeff = jnp.where(margins < 1, yc, 0).astype(W.dtype)
        L = jnp.einsum("mcb,mbd->mcd", coeff, rows)
    alpha = 1.0 / (lam * t.astype(jnp.float32))
    return ((1.0 - lam * alpha) * W.astype(jnp.float32)
            + alpha * (L / B).astype(jnp.float32)).astype(W.dtype)


@functools.partial(jax.jit, static_argnames=("gadget_items", "seg_iters", "fault"))
def _segment(data, y, counts, keys, W, W_sum, t0, *, gadget_items, seg_iters, fault):
    gadget = dict(gadget_items)
    lam, B, R = gadget["lam"], gadget["batch_size"], gadget["gossip_rounds"]
    m = y.shape[0]
    data_key, mix_key = keys
    counts_i = counts.astype(jnp.int32)
    mass0 = counts.astype(W.dtype)
    classes = y.ndim == 3

    def step(carry, _):
        W, W_sum, t = carry
        ks = jax.random.split(jax.random.fold_in(data_key, t), m)
        ids = jax.vmap(lambda k, c: jax.random.randint(k, (B,), 0, c))(ks, counts_i)
        take = jax.vmap(lambda a, i: a[i])
        rows = (tuple(take(a, ids) for a in data) if isinstance(data, tuple)
                else take(data, ids))
        yb = take(y, ids).astype(W.dtype)
        per_node = (m,) + (1,) * (W.ndim - 1)        # broadcasts a node's scalar
        if fault == "half_batch":
            yb = yb * (jnp.arange(m) < m // 2).reshape(per_node).astype(W.dtype)
        Wh = (_half_step_classes if classes else _half_step)(W, rows, yb, lam, t, B)
        if gadget["project_before_gossip"]:
            Wh = _project(Wh, lam)
        # one mix of the (m, C·d) values when there are classes
        vals, mass = (Wh * mass0.reshape(per_node)).reshape(m, -1), mass0
        if fault != "no_mix":
            kt = jax.random.fold_in(mix_key, t)
            for r in range(R):
                Bm = _neighbor_matrix(jax.random.fold_in(kt, r), m).astype(W.dtype)
                vals, mass = Bm.T @ vals, Bm.T @ mass
        W = (vals / mass[:, None]).reshape(Wh.shape)
        if gadget["project_after_gossip"]:
            W = _project(W, lam)
        return (W, W_sum + W, t + 1), None

    (W, W_sum, t), _ = jax.lax.scan(step, (W, W_sum, t0), None, length=seg_iters)
    return W, W_sum, t


@functools.partial(jax.jit, static_argnames=("lam",))
def objective(w, data, y, valid, lam):
    """Primal objective ½λ‖w‖² + mean hinge over the valid rows (float32)."""
    w = w.astype(jnp.float32)
    if isinstance(data, tuple):
        cols, vals = data
        margins = y * jnp.sum(vals.astype(jnp.float32) * w[cols], axis=-1)
    else:
        margins = y * jnp.einsum("mnd,d->mn", data.astype(jnp.float32), w)
    hinge = jnp.sum(jnp.where(valid, jnp.maximum(0.0, 1.0 - margins), 0.0))
    return 0.5 * lam * jnp.dot(w, w) + hinge / jnp.sum(valid)


OBJECTIVE_BLOCK = 4096   # rows per map step of the C-class objective


@functools.partial(jax.jit, static_argnames=("lam",))
def class_objectives(Wc, data, y, valid, lam):
    """(C,) primal objectives, one per class: Wc (C, d) the class consensus,
    y (m, n_i, C). Rows go in blocks of OBJECTIVE_BLOCK, each gathering rows
    of Wᵀ (d, C); float32 at ``highest`` precision."""
    Wc = Wc.astype(jnp.float32)
    C = Wc.shape[0]
    n = y.shape[0] * y.shape[1]
    n_blocks = -(-n // OBJECTIVE_BLOCK)

    def blocks(a):
        a = a.reshape((n,) + a.shape[2:])
        pad = [(0, n_blocks * OBJECTIVE_BLOCK - n)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, pad).reshape((n_blocks, OBJECTIVE_BLOCK) + a.shape[1:])

    rows = tuple(blocks(a) for a in data) if isinstance(data, tuple) else blocks(data)
    wt = Wc.T

    def block(args):
        x, yb, ok = args
        if isinstance(x, tuple):
            cols, vals = x
            s = jnp.einsum("rk,rkc->rc", vals.astype(jnp.float32), wt[cols],
                           precision=jax.lax.Precision.HIGHEST)
        else:
            s = jnp.matmul(x.astype(jnp.float32), wt, precision=jax.lax.Precision.HIGHEST)
        hinge = jnp.maximum(0.0, 1.0 - yb * s)
        return jnp.sum(jnp.where(ok[:, None], hinge, 0.0), axis=0)

    hinge = jnp.sum(jax.lax.map(block, (rows, blocks(y), blocks(valid))), axis=0)
    return 0.5 * lam * jnp.sum(Wc * Wc, axis=1) + hinge / jnp.sum(valid)


def train_segments(data, y, counts, seed, gadget, d, seg_iters, n_segments,
                   dtype=jnp.float32, fault=None):
    """Run ``n_segments`` segments of ``seg_iters`` iterations from W = 0.

    Returns one ``(W, W_sum, objective)`` per segment, W and W_sum as float64
    host arrays and the objective of the segment's consensus as a float; with
    a class axis on ``y`` (m, n_i, C), W and W_sum are (m, C, d) and the
    objective a float64 array of the C classes' objectives.
    """
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    with jax.default_matmul_precision("highest"):
        m = y.shape[0]
        n_i = y.shape[1]
        counts_f = jnp.asarray(counts, jnp.float32)
        valid = jnp.arange(n_i)[None, :] < counts_f[:, None]
        keys = tuple(jax.random.split(jax.random.PRNGKey(seed)))
        data_c = (tuple([data[0], data[1].astype(dtype)]) if isinstance(data, tuple)
                  else data.astype(dtype))
        shape = (m, y.shape[2], d) if y.ndim == 3 else (m, d)
        W = jnp.zeros(shape, dtype)
        W_sum = jnp.zeros(shape, dtype)
        t = jnp.int32(1)
        items = tuple(sorted((k, v) for k, v in gadget.items()
                             if isinstance(v, (int, float, bool, str))))
        out = []
        for _ in range(n_segments):
            W, W_sum, t = _segment(data_c, y, counts_f, keys, W, W_sum, t,
                                   gadget_items=items,
                                   seg_iters=seg_iters, fault=fault)
            per_node = counts_f.reshape((m,) + (1,) * (W.ndim - 1))
            w_cons = (jnp.sum(W.astype(jnp.float32) * per_node, axis=0) / jnp.sum(counts_f))
            if y.ndim == 3:
                obj = np.asarray(class_objectives(w_cons, data, y, valid, gadget["lam"]),
                                 np.float64)
            else:
                obj = float(objective(w_cons, data, y, valid, gadget["lam"]))
            out.append((np.asarray(W, np.float64), np.asarray(W_sum, np.float64), obj))
        return out


def scores(w, cols, vals, dtype=np.float64):
    """Σ_k vals[k]·w[cols[k]] per query row; ``dtype`` bfloat16 is the control."""
    if dtype == "bfloat16":
        wb = np.asarray(w, np.float32).astype(ml_dtypes.bfloat16)
        prod = (np.asarray(vals, np.float32).astype(ml_dtypes.bfloat16) * wb[cols])
        return prod.astype(np.float32).sum(axis=-1, dtype=np.float32).astype(np.float64)
    w = np.asarray(w, np.float64)
    return np.einsum("rk,rk->r", np.asarray(vals, np.float64), w[np.asarray(cols)])
