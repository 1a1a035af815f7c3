"""Primal linear-SVM objective, hinge loss, and Pegasos sub-gradient.

This module is the pure-jnp oracle for ``repro.kernels.hinge_subgrad`` and the
shared math for both the centralized Pegasos baseline and GADGET.

Objective (paper Eq. 1):
    f(w) = (lambda/2) ||w||^2 + (1/N) sum_j max{0, 1 - y_j <w, x_j>}
"""
from __future__ import annotations

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
