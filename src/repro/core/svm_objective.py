"""Primal linear-SVM objective, hinge loss, and Pegasos sub-gradient.

This module is the pure-jnp oracle for ``repro.kernels.hinge_subgrad`` and the
shared math for both the centralized Pegasos baseline and GADGET.

Objective (paper Eq. 1):
    f(w) = (lambda/2) ||w||^2 + (1/N) sum_j max{0, 1 - y_j <w, x_j>}
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Full f32 contractions: a TPU's default f32 matmul is one bf16 pass, which
# would round margins and gradients (no effect on the CPU backend).
F32 = jax.lax.Precision.HIGHEST

__all__ = [
    "hinge_loss",
    "primal_objective",
    "primal_objective_masked",
    "primal_objective_masked_ell",
    "class_objectives",
    "hinge_subgradient",
    "pegasos_update",
    "project_ball",
    "accuracy",
]


def hinge_loss(w: jax.Array, X: jax.Array, y: jax.Array) -> jax.Array:
    """Mean hinge loss (1/N) sum max(0, 1 - y <w, x>). X: (N, d), y: (N,) in {-1,+1}."""
    margins = y * jnp.matmul(X, w, precision=F32)
    return jnp.mean(jnp.maximum(0.0, 1.0 - margins))


def primal_objective(w: jax.Array, X: jax.Array, y: jax.Array, lam: float) -> jax.Array:
    return 0.5 * lam * jnp.dot(w, w) + hinge_loss(w, X, y)


def primal_objective_masked(w: jax.Array, X: jax.Array, y: jax.Array,
                            lam: float, valid: jax.Array,
                            total: jax.Array) -> jax.Array:
    """Primal objective over the ``valid`` rows of a padded sample matrix.

    Non-uniform GADGET partitions pad every node to the same n_i; padded rows
    carry y=0 and would each contribute a spurious hinge of 1 under the
    unmasked mean. ``total`` is the true sample count (sum of per-node
    n_counts), so for an all-true mask this reduces to ``primal_objective``.
    """
    margins = y * jnp.matmul(X, w, precision=F32)
    hinge = jnp.sum(jnp.where(valid, jnp.maximum(0.0, 1.0 - margins), 0.0)) / total
    return 0.5 * lam * jnp.dot(w, w) + hinge


def primal_objective_masked_ell(w: jax.Array, cols: jax.Array, vals: jax.Array,
                                y: jax.Array, lam: float, valid: jax.Array,
                                total: jax.Array) -> jax.Array:
    """``primal_objective_masked`` over padded-ELL planes (N, k) — margins as
    a gather-dot against w, never materializing dense X. Pad entries
    (col=0, val=0) are inert; pad *rows* are excluded via ``valid``."""
    margins = y * jnp.sum(vals * jnp.take(w, cols, axis=0), axis=-1)
    hinge = jnp.sum(jnp.where(valid, jnp.maximum(0.0, 1.0 - margins), 0.0)) / total
    return 0.5 * lam * jnp.dot(w, w) + hinge


# Rows of each node per step of ``class_objectives``' scan over row blocks.
CLASS_OBJECTIVE_ROWS = 512


def class_objectives(W: jax.Array, X, y: jax.Array, n_counts: jax.Array,
                     lam: float, total: jax.Array) -> jax.Array:
    """(C,) one-vs-rest primal objectives of the class planes W (C, d): for
    each class, ½λ‖w_c‖² + Σ hinge(y_c · ⟨w_c, x⟩) / total over the first
    n_counts[i] rows of every node.

    X: dense (m, n_i, d) partitions or the (cols, vals) (m, n_i, k) ELL
    planes; y: (m, n_i, C) ±1. The pass scans row blocks of
    ``CLASS_OBJECTIVE_ROWS`` a node: an ELL block gathers rows of Wᵀ (d, C)
    for its slots, so no (rows, k, C) array of the whole split is made. The
    last block is taken from the end and masks the rows its predecessor
    counted. Each block's hinge sums are added up at the end, as one
    reduction over the blocks rather than a running sum."""
    m, n_i = y.shape[:2]
    rows = min(CLASS_OBJECTIVE_ROWS, n_i)
    wt = W.T

    def block(b):
        start = jnp.minimum(b * rows, n_i - rows)
        take = functools.partial(jax.lax.dynamic_slice_in_dim, start_index=start,
                                 slice_size=rows, axis=1)
        if isinstance(X, tuple):
            cols, vals = take(X[0]), take(X[1])
            s = jnp.sum(vals[..., None] * wt[cols], axis=2)
        else:
            s = jnp.einsum("mrd,dc->mrc", take(X), wt, precision=F32)
        row = start + jnp.arange(rows)
        ok = (row >= b * rows)[None, :] & (row[None, :] < n_counts[:, None])
        hinge = jnp.where(ok[..., None], jnp.maximum(0.0, 1.0 - take(y) * s), 0.0)
        return jnp.sum(hinge, axis=(0, 1))

    hinge = jnp.sum(jax.lax.map(block, jnp.arange(-(-n_i // rows))), axis=0)
    return 0.5 * lam * jnp.sum(W * W, axis=1) + hinge / total


def hinge_subgradient(w: jax.Array, X: jax.Array, y: jax.Array) -> jax.Array:
    """Sub-gradient of the mean hinge loss term only (the paper's L̂ direction
    is the *negative* of this: L̂ = mean over violators of y·x).

    Returns (1/B) sum_{j: margin_j < 1} (-y_j x_j), shape (d,).
    """
    margins = y * jnp.matmul(X, w, precision=F32)
    viol = (margins < 1.0).astype(X.dtype)
    return -jnp.matmul(X.T, viol * y, precision=F32) / X.shape[0]


def pegasos_update(w: jax.Array, X: jax.Array, y: jax.Array, lam: float, t: jax.Array) -> jax.Array:
    """One Pegasos step on mini-batch (X, y) at iteration t (1-based):
        alpha_t = 1/(lambda t)
        w <- (1 - lambda alpha_t) w + alpha_t * mean_{violators} y x
    followed by projection onto the 1/sqrt(lambda) ball.
    """
    alpha = 1.0 / (lam * t)
    L_hat = -hinge_subgradient(w, X, y)  # paper's L̂ = mean violator y·x
    w_half = (1.0 - lam * alpha) * w + alpha * L_hat
    return project_ball(w_half, lam)


def project_ball(w: jax.Array, lam: float) -> jax.Array:
    """min{1, (1/sqrt(lam)) / ||w||} * w — Pegasos ball projection (paper steps f/h)."""
    norm = jnp.linalg.norm(w)
    scale = jnp.minimum(1.0, (1.0 / jnp.sqrt(lam)) / jnp.maximum(norm, 1e-30))
    return w * scale


def accuracy(w: jax.Array, X: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.mean((jnp.sign(jnp.matmul(X, w, precision=F32)) == y)
                    .astype(jnp.float32))
