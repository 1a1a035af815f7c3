"""Pallas TPU kernels for serving-side SVM prediction (scores + argmax).

Training's hot loop updates w; serving's hot loop *applies* it: scores
S = X @ W^T for a (B, d) query batch against a (C, d) model (C = 1 for the
paper's binary SVMs, C > 1 for the one-vs-rest multiclass extension), then
``argmax_c S[b, c]``. Two kernels, mirroring the training-side pair:

  * ``dense_scores`` — blocked (B, d)·(C, d)^T matmul, grid
    (B/blk_b, d/blk_d), per-query partial scores accumulated in VMEM scratch
    across the d axis; the final d-block writes BOTH the scores tile and the
    argmax labels, so one launch produces everything a serving response
    needs (no separate O(B·C) argmax pass over HBM).
  * ``ell_scores_prefetch`` — the sparse twin for padded-ELL query planes
    (B, k): the *query-side* reuse of the training prefetch machinery. A
    compact touched-block-id map (repro.sparse.formats.block_map over the
    query batch) rides in as a ``PrefetchScalarGridSpec`` scalar operand, the
    W ``index_map`` DMAs exactly one live (C, blk_d) block per program. The
    in-block step densifies each query row's entries that land in the block
    with the training kernels' transposed one-hot (``sparse.onehot_t``) —
    a (B, blk_d) slab of the query rows — and scores it like the dense
    kernel: slab @ W_blk^T in one MXU pass. Sentinel slots alias the
    all-zero pad block appended after W's last real block and skip the
    contraction under ``pl.when`` — scoring a sparse batch touches
    O(live · C · blk_d) weight lanes instead of O(C · d).

Class-lane convention: C is padded to a 128-lane multiple (``Cp``) by the
ops.py wrapper with all-zero rows; their score is exactly 0, which can exceed
a real class's negative score, so the argmax masks lanes ≥ n_classes to -inf
in-kernel (first-occurrence tie-breaking, matching ``jnp.argmax``). Pad
convention for the ELL planes is unchanged: (col=0, val=0) entries and
all-pad rows are inert — a pad query row scores 0 for every class. Every
matmul contracts f32 operands at ``Precision.HIGHEST``, so chip scores agree
with an f32 ``X @ W^T``.
Interpret mode off-TPU as everywhere else in this package.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.hinge_subgrad.sparse import contract_last, onehot_t

__all__ = ["dense_scores", "ell_scores_prefetch"]


def _argmax_lanes(scores: jax.Array, n_classes: int) -> jax.Array:
    """First-occurrence argmax over the class-lane axis with pad lanes
    (≥ n_classes) masked out — jnp.argmax semantics built from max/min
    reductions only (Mosaic-safe, no 1D argmax lowering needed)."""
    Cp = scores.shape[-1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, scores.shape, scores.ndim - 1)
    masked = jnp.where(lanes < n_classes, scores, -jnp.inf)
    best = jnp.max(masked, axis=-1, keepdims=True)
    return jnp.min(jnp.where(masked == best, lanes, Cp), axis=-1).astype(jnp.int32)


def _dense_scores_kernel(x_ref, w_ref, s_ref, l_ref, acc, *, n_classes):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    # (blk_b, blk_d) @ (Cp, blk_d)^T — partial scores for this d block
    acc[...] += contract_last(x_ref[...], w_ref[...])

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        s_ref[...] = acc[...]
        l_ref[...] = _argmax_lanes(acc[...], n_classes)


def dense_scores(X: jax.Array, W: jax.Array, *, n_classes: int,
                 blk_b: int, blk_d: int,
                 interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Fused scores-and-argmax: X (B, d) queries against W (Cp, d) class
    weights → (scores (B, Cp) f32, labels (B,) int32). B/d must be block
    multiples and Cp a 128-lane multiple (ops.dense_predict pads); rows of W
    beyond ``n_classes`` must be zero and are excluded from the argmax."""
    B, d = X.shape
    Cp = W.shape[0]
    assert B % blk_b == 0 and d % blk_d == 0 and Cp % 128 == 0, "wrapper must pad"
    kern = functools.partial(_dense_scores_kernel, n_classes=n_classes)
    return pl.pallas_call(
        kern,
        name="dense_predict",
        grid=(B // blk_b, d // blk_d),
        in_specs=[
            pl.BlockSpec((blk_b, blk_d), lambda i, j: (i, j)),
            pl.BlockSpec((Cp, blk_d), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((blk_b, Cp), lambda i, j: (i, 0)),
            pl.BlockSpec((blk_b,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Cp), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((blk_b, Cp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(X, W)


def _ell_scores_prefetch_kernel(bids_ref, cols_ref, vals_ref, w_ref,
                                s_ref, l_ref, acc, slab, *, blk_d, n_d_blocks,
                                n_classes):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    bid = bids_ref[j]

    @pl.when(bid < n_d_blocks)  # sentinel slots: DMA aliases the pad block,
    def _():                    # contraction skipped — work tracks live blocks
        def row(b, carry):
            # the query row's entries that land in this block, densified
            oh = onehot_t(cols_ref[pl.ds(b, 1), :], bid * blk_d, blk_d)
            slab[pl.ds(b, 1), :] = contract_last(vals_ref[pl.ds(b, 1), :], oh)
            return carry

        jax.lax.fori_loop(0, slab.shape[0], row, 0)
        acc[...] += contract_last(slab[...], w_ref[...])

    @pl.when(j == pl.num_programs(0) - 1)
    def _():
        s_ref[...] = acc[...]
        l_ref[...] = _argmax_lanes(acc[...], n_classes)


def ell_scores_prefetch(cols: jax.Array, vals: jax.Array, W: jax.Array,
                        block_ids: jax.Array, *, blk_d: int, n_d_blocks: int,
                        n_classes: int,
                        interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Touched-block twin of :func:`dense_scores` for one ELL query batch.

    cols/vals: (B, k) padded query planes; ``block_ids``: (n_blocks_max,)
    compact touched-block-id map for the *whole batch* (live ids ascending,
    then the sentinel ``n_d_blocks`` — formats.block_map with m=1). W must
    carry the sentinel's landing pad: (Cp, (n_d_blocks + 1)·blk_d) with the
    last block all-zero. Returns (scores (B, Cp), labels (B,))."""
    B, k = cols.shape
    Cp = W.shape[0]
    assert W.shape[1] == (n_d_blocks + 1) * blk_d, "caller pads W + zero block"
    n_blocks_max = block_ids.shape[0]
    kern = functools.partial(_ell_scores_prefetch_kernel, blk_d=blk_d,
                             n_d_blocks=n_d_blocks, n_classes=n_classes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks_max,),
        in_specs=[
            pl.BlockSpec((B, k), lambda j, b: (0, 0)),
            pl.BlockSpec((B, k), lambda j, b: (0, 0)),
            pl.BlockSpec((Cp, blk_d), lambda j, b: (0, b[j])),
        ],
        out_specs=[
            pl.BlockSpec((B, Cp), lambda j, b: (0, 0)),
            pl.BlockSpec((B,), lambda j, b: (0,)),
        ],
        scratch_shapes=[pltpu.VMEM((B, Cp), jnp.float32),
                        pltpu.VMEM((B, blk_d), jnp.float32)],
    )
    return pl.pallas_call(
        kern,
        name="ell_predict",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Cp), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_ids, cols, vals, W)
