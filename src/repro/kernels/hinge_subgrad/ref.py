"""Pure-jnp oracle for the fused Pegasos hinge-subgradient step.

Matches repro.core.svm_objective.pegasos_update exactly (same math, one
function) — the kernel is the paper's per-iteration compute hot-spot:
margins = X w;  L = X^T (1[margin<1] * y) / B;
w' = (1 - lam*alpha) w + alpha L;  project to the 1/sqrt(lam) ball.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# f32 contractions on every backend (a TPU's default f32 matmul is one bf16
# pass); the oracles must not be less exact than the kernels they check.
F32 = jax.lax.Precision.HIGHEST


def half_step_ref(w: jax.Array, X: jax.Array, y: jax.Array, lam: float, t: jax.Array,
                  project: bool = True) -> jax.Array:
    """Oracle for ops.local_half_step: Pegasos half-step, optional projection,
    no loss scalar — the per-node body of GADGET's device-resident loop."""
    margins = y * jnp.matmul(X, w, precision=F32)
    viol = (margins < 1.0).astype(X.dtype)
    L = jnp.matmul(X.T, viol * y, precision=F32) / X.shape[0]
    alpha = 1.0 / (lam * t)
    w_half = (1.0 - lam * alpha) * w + alpha * L
    if project:
        with jax.named_scope("gadget.project"):
            norm = jnp.linalg.norm(w_half)
            scale = jnp.minimum(1.0, (1.0 / jnp.sqrt(lam)) / jnp.maximum(norm, 1e-30))
            w_half = w_half * scale
    return w_half


def fleet_half_step_ref(W: jax.Array, X: jax.Array, y: jax.Array, lam: float,
                        t: jax.Array, project: bool = True) -> jax.Array:
    """Oracle for the fused fleet kernel: steps (a)-(e) for all m nodes at
    once. X: (m, B, d) minibatch tiles, W: (m, d), y: (m, B) with padded rows
    carrying y=0. Same per-node math as half_step_ref, batched over the node
    axis — this is also the fused jnp path GADGET uses where the Pallas
    kernels would only interpret (CPU).

    One-vs-rest: W (m, C, d) and y (m, B, C) ±1 run every class's half-step
    on the node's same rows, each (node, class) row projected on its own."""
    B = X.shape[1]
    if W.ndim == 3:
        margins = y * jnp.einsum("mbd,mcd->mbc", X, W, precision=F32)
        coeff = jnp.where(margins < 1.0, y, 0.0)
        L = jnp.einsum("mbc,mbd->mcd", coeff, X, precision=F32) / B
    else:
        margins = y * jnp.einsum("mbd,md->mb", X, W, precision=F32)
        coeff = jnp.where(margins < 1.0, y, 0.0)
        L = jnp.einsum("mb,mbd->md", coeff, X, precision=F32) / B
    alpha = 1.0 / (lam * t)
    W_half = (1.0 - lam * alpha) * W + alpha * L
    return _project_rows(W_half, lam) if project else W_half


def _project_rows(W: jax.Array, lam: float) -> jax.Array:
    """Each row (last axis) of W onto the 1/sqrt(lam) ball."""
    with jax.named_scope("gadget.project"):
        norms = jnp.linalg.norm(W, axis=-1, keepdims=True)
        scale = jnp.minimum(1.0, (1.0 / jnp.sqrt(lam)) / jnp.maximum(norms, 1e-30))
        return W * scale


# --------------------------------------------------------------------- sparse
# Padded-ELL oracles (repro.sparse.formats layout: pad entries (col=0, val=0),
# pad rows y=0 — inert in every gather-dot / scatter-add below).


def ell_margins_ref(w: jax.Array, cols: jax.Array, vals: jax.Array,
                    y: jax.Array) -> jax.Array:
    """y * (X @ w) over one node's ELL minibatch planes: (B, k) cols/vals."""
    return y * jnp.sum(vals * jnp.take(w, cols, axis=0), axis=-1)


def ell_matvec_flat(w: jax.Array, cols: jax.Array, vals: jax.Array) -> jax.Array:
    """X @ w for flat (N, k) ELL planes — the full-data pass the objective
    trace uses (never materializes dense X)."""
    return jnp.sum(vals * jnp.take(w, cols, axis=0), axis=-1)


def ell_fleet_half_step_ref(W: jax.Array, cols: jax.Array, vals: jax.Array,
                            y: jax.Array, lam: float, t: jax.Array,
                            project: bool = True) -> jax.Array:
    """Oracle for the sparse fleet half-step: GADGET steps (a)-(e) for all m
    nodes over ELL minibatch planes. cols/vals: (m, B, k), W: (m, d),
    y: (m, B). Margins are a gather-dot against each node's resident w; the
    subgradient is a scatter-add of the violator-weighted values — same math
    as fleet_half_step_ref with X = dense(cols, vals). Also the fused jnp path
    GADGET's sparse mode uses where Pallas would only interpret (CPU).

    One-vs-rest: W (m, C, d) and y (m, B, C) ±1; one gather of each row's
    columns serves every class, each (node, class) row projected on its own."""
    B = cols.shape[1]
    d = W.shape[-1]
    if W.ndim == 3:
        margins = y * jax.vmap(
            lambda w, c, v: jnp.sum(v[:, None, :] * jnp.take(w, c, axis=1)
                                    .transpose(1, 0, 2), axis=-1)
        )(W, cols, vals)
        coeff = jnp.where(margins < 1.0, y, 0.0)
        L = jax.vmap(
            lambda c, v, cf: jnp.zeros((cf.shape[1], d), jnp.float32)
            .at[:, c.reshape(-1)].add((cf.T[:, :, None] * v[None]).reshape(cf.shape[1], -1))
        )(cols, vals, coeff) / B
    else:
        margins = y * jax.vmap(
            lambda w, c, v: jnp.sum(v * jnp.take(w, c, axis=0), axis=-1)
        )(W, cols, vals)
        coeff = jnp.where(margins < 1.0, y, 0.0)
        L = jax.vmap(
            lambda c, v, cf: jnp.zeros(d, jnp.float32)
            .at[c.reshape(-1)].add((cf[:, None] * v).reshape(-1))
        )(cols, vals, coeff) / B
    alpha = 1.0 / (lam * t)
    W_half = (1.0 - lam * alpha) * W + alpha * L
    return _project_rows(W_half, lam) if project else W_half


# -------------------------------------------------------------------- predict
# Serving-side oracles (repro.serve / ops.dense_predict / ops.ell_predict):
# scores S = X @ W^T against a (C, d) class-weight matrix, labels = argmax_c.


def predict_scores_ref(W: jax.Array, X: jax.Array) -> jax.Array:
    """S = X @ W^T. W: (C, d) class weights (C=1 for binary), X: (B, d)."""
    return jnp.matmul(X, W.T, precision=F32)


def predict_labels_ref(W: jax.Array, X: jax.Array) -> jax.Array:
    """argmax_c S[b, c] — first occurrence, the convention the fused kernel's
    masked max/min argmax reproduces."""
    return jnp.argmax(predict_scores_ref(W, X), axis=-1).astype(jnp.int32)


def ell_predict_scores_ref(W: jax.Array, cols: jax.Array,
                           vals: jax.Array) -> jax.Array:
    """Sparse twin: scores for one (B, k) padded-ELL query batch as a
    gather-dot against every class row — S[b, c] = Σ_k vals[b,k]·W[c, cols[b,k]].
    Pad entries (val=0) are inert; an all-pad row scores 0 for every class."""
    return jnp.einsum("bk,cbk->bc", vals, jnp.take(W, cols, axis=1),
                      precision=F32)


def pegasos_step_ref(w: jax.Array, X: jax.Array, y: jax.Array, lam: float, t: jax.Array):
    """Returns (w_new (d,), mean_hinge_loss ()). X: (B, d); y: (B,) in {-1,+1}."""
    margins = y * jnp.matmul(X, w, precision=F32)
    viol = (margins < 1.0).astype(X.dtype)
    L = jnp.matmul(X.T, viol * y, precision=F32) / X.shape[0]
    alpha = 1.0 / (lam * t)
    w_half = (1.0 - lam * alpha) * w + alpha * L
    norm = jnp.linalg.norm(w_half)
    scale = jnp.minimum(1.0, (1.0 / jnp.sqrt(lam)) / jnp.maximum(norm, 1e-30))
    loss = jnp.mean(jnp.maximum(0.0, 1.0 - margins))
    return w_half * scale, loss
