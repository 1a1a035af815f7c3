"""Pallas TPU kernels for the fused Pegasos hinge-subgradient step.

The paper's per-iteration hot-spot is `margins = X w` followed by the
violator-weighted gradient `X^T (1[m<1] y)` — two passes over the minibatch
block X. Two kernels, both VMEM-tiled:

  * ``margins_kernel``  — blocked mat-vec, grid (B/blk_b, d/blk_d), partial
    dot-products accumulated in a VMEM scratch across the d (arbitrary) axis.
  * ``update_kernel``   — blocked transposed mat-vec fused with the Pegasos
    axpy: grid (d/blk_d, B/blk_b); per d-block accumulates g = X^T c over B
    blocks in VMEM scratch and, on the last B block, writes
    w_half = (1 - lam*alpha) w + (alpha/B) g.

``fleet_half_step`` fuses both phases for *all m nodes* in one ``pallas_call``:
the node axis is a parallel grid dimension (replacing ``jax.vmap`` over the
two kernels above), each node's (B, d) minibatch tile is read from HBM once
and stays in VMEM across both phases, and margins → violator coefficients →
gradient → the Pegasos axpy never touch HBM — only w_half is written back.
One kernel launch per GADGET iteration instead of 2m.

Layout: Mosaic requires every block's last two dims to be (8, 128)
multiples or the array's own extents, so vectors travel as 2-D slabs — a
weight vector as a (1, d) row (per node: an (m, 1, d) array whose leading
axis the grid walks), per-row quantities (y, margins, coefficients) as
(B, 1) columns. Both mat-vecs are VPU multiply-and-reduce (lanes for X w,
sublanes for X^T c): exact f32 on the chip, with no rank-1 MXU operand.
The public functions take and return the natural shapes and do the
reshapes outside the kernel.

The ball projection needs a global ||w_half|| reduction and lives in the
ops.py wrapper (O(d), bandwidth-trivial). d and B are padded by the wrapper
to block multiples when needed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

__all__ = ["margins", "grad_update", "fleet_half_step",
           "DEFAULT_BLK_B", "DEFAULT_BLK_D"]

DEFAULT_BLK_B = 128
DEFAULT_BLK_D = 512


def _margins_kernel(x_ref, w_ref, y_ref, m_ref, acc):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.sum(x_ref[...] * w_ref[...], axis=1, keepdims=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        m_ref[...] = y_ref[...] * acc[...]


def margins(X: jax.Array, w: jax.Array, y: jax.Array, *,
            blk_b: int = DEFAULT_BLK_B, blk_d: int = DEFAULT_BLK_D,
            interpret: bool = False) -> jax.Array:
    """y * (X @ w) via the blocked mat-vec kernel. X: (B, d), w: (d,),
    y: (B,) → (B,)."""
    B, d = X.shape
    blk_b, blk_d = min(blk_b, B), min(blk_d, d)
    assert B % blk_b == 0 and d % blk_d == 0, "wrapper must pad"
    out = pl.pallas_call(
        _margins_kernel,
        name="local_half_step_margins",
        grid=(B // blk_b, d // blk_d),
        in_specs=[
            pl.BlockSpec((blk_b, blk_d), lambda i, j: (i, j)),
            pl.BlockSpec((1, blk_d), lambda i, j: (0, j)),
            pl.BlockSpec((blk_b, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((blk_b, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((blk_b, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(X, w.reshape(1, d), y.reshape(B, 1))
    return out.reshape(B)


def _fleet_kernel(x_ref, w_ref, y_ref, mask_ref, scal_ref, o_ref):
    x = x_ref[0]       # (B, d) — the node's minibatch tile, resident in VMEM
    w = w_ref[0]       # (1, d)
    yv = y_ref[0]      # (B, 1)
    m = yv * jnp.sum(x * w, axis=1, keepdims=True)     # phase 1: margins
    coeff = jnp.where(m < 1.0, yv, 0.0) * mask_ref[...]  # violator selection
    g = jnp.sum(coeff * x, axis=0, keepdims=True)      # phase 2: X^T c, same tile
    o_ref[0] = (1.0 - scal_ref[0]) * w + scal_ref[1] * g


def fleet_half_step(X: jax.Array, W: jax.Array, y: jax.Array,
                    row_mask: jax.Array, scal: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """Fused GADGET steps (a)-(e) for all m nodes in one launch.

    X: (m, B, d) per-node minibatch tiles; W: (m, d); y: (m, B);
    row_mask: (B,) validity of padded rows (shared across nodes —
    ops.padded_row_mask); scal: (2,) = [lam*alpha, alpha/B] in SMEM.
    Returns W_half (m, d) = (1 - scal[0]) W + scal[1] * (coeff @ X).

    Grid is the node axis only (fully parallel); each program keeps its whole
    (B, d) tile in VMEM across the margins and gradient phases, so X is read
    from HBM exactly once and no intermediate (margins, coefficients) ever
    round-trips through HBM. The wrapper bounds B*d so the tile fits VMEM.
    """
    m, B, d = X.shape
    out = pl.pallas_call(
        _fleet_kernel,
        name="fleet_half_step",
        grid=(m,),
        in_specs=[
            pl.BlockSpec((1, B, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, B, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((B, 1), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(X, W.reshape(m, 1, d), y.reshape(m, B, 1), row_mask.reshape(B, 1), scal)
    return out.reshape(m, d)


def _update_kernel(x_ref, w_ref, c_ref, scal_ref, o_ref, gacc):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        gacc[...] = jnp.zeros_like(gacc)

    # g_d += X[b_blk, d_blk]^T c[b_blk]
    gacc[...] += jnp.sum(c_ref[...] * x_ref[...], axis=0, keepdims=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        lam_alpha = scal_ref[0]      # lam * alpha
        alpha_over_b = scal_ref[1]   # alpha / B
        o_ref[...] = (1.0 - lam_alpha) * w_ref[...] + alpha_over_b * gacc[...]


def grad_update(X: jax.Array, w: jax.Array, coeff: jax.Array, scal: jax.Array, *,
                blk_b: int = DEFAULT_BLK_B, blk_d: int = DEFAULT_BLK_D,
                interpret: bool = False) -> jax.Array:
    """w_half = (1 - scal[0]) w + scal[1] * (coeff @ X).

    coeff: (B,) = 1[margin<1] * y (violator selection, computed by wrapper);
    scal: (2,) = [lam*alpha, alpha/B] in SMEM. Returns (d,).
    """
    B, d = X.shape
    blk_b, blk_d = min(blk_b, B), min(blk_d, d)
    assert B % blk_b == 0 and d % blk_d == 0, "wrapper must pad"
    out = pl.pallas_call(
        _update_kernel,
        name="local_half_step_update",
        grid=(d // blk_d, B // blk_b),
        in_specs=[
            pl.BlockSpec((blk_b, blk_d), lambda i, j: (j, i)),
            pl.BlockSpec((1, blk_d), lambda i, j: (0, i)),
            pl.BlockSpec((blk_b, 1), lambda i, j: (j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, blk_d), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, blk_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(X, w.reshape(1, d), coeff.reshape(B, 1), scal)
    return out.reshape(d)
