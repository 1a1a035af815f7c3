"""Multi-class GADGET SVM (paper §5 future work: "extension to multi-class
variants of SVMs").

One-vs-rest over the binary GADGET solver: class c gets its own weight
vector trained on (x, +1 if y==c else -1); prediction is argmax_c <w_c, x>.
All classes train in ONE run of the normal device path: ``gadget_train``
takes the (m, n_i, C) ±1 label plane and carries W as (m, C, d) through the
same half-step, Push-Sum mix and ε-check (``repro.core.gadget`` module
docstring), so gossip cost is shared across classes.

Prediction dispatches the serving-side fused scores+argmax kernel
(``hinge_subgrad.ops.dense_predict`` — one launch for margins AND argmax),
the same path ``repro.serve.SvmServer`` scores multiclass checkpoints with;
the pure-jnp argmax stays available as ``predict_multiclass(use_kernels=False)``
and remains the oracle the kernel is tested against.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.gadget import GadgetConfig, gadget_train
from repro.kernels.hinge_subgrad import ops as hinge_ops

__all__ = ["MulticlassResult", "gadget_train_multiclass", "predict_multiclass"]


class MulticlassResult(NamedTuple):
    W: jax.Array            # (m, C, d) per-node per-class weights
    w_consensus: jax.Array  # (C, d)
    iters: int


def gadget_train_multiclass(X_parts: jax.Array, y_parts: jax.Array, n_classes: int,
                            cfg: GadgetConfig = GadgetConfig()) -> MulticlassResult:
    """X_parts: (m, n_i, d); y_parts: (m, n_i) int labels in [0, C), turned
    into the (m, n_i, C) ±1 one-vs-rest plane ``gadget_train`` takes."""
    y = jnp.asarray(y_parts)[..., None] == jnp.arange(n_classes)
    res = gadget_train(X_parts, jnp.where(y, 1.0, -1.0).astype(jnp.float32), cfg)
    return MulticlassResult(W=res.W, w_consensus=res.w_consensus, iters=res.iters)


def predict_multiclass(w_consensus: jax.Array, X: jax.Array, *,
                       use_kernels: bool | None = None) -> jax.Array:
    """argmax_c <w_c, x> per row. ``use_kernels=None`` follows the package
    convention (fused kernel wherever it compiles natively, interpret-mode
    kernel when forced via True, jnp oracle via False)."""
    if use_kernels is None:
        use_kernels = not hinge_ops.default_interpret()
    if use_kernels:
        _, labels = hinge_ops.dense_predict(w_consensus, X)
        return labels
    return jnp.argmax(X @ w_consensus.T, axis=-1).astype(jnp.int32)
