"""Pallas TPU kernels for the hinge-subgradient step over padded-ELL planes.

Sparse counterpart of ``hinge_subgrad.py``: the minibatch is two (m, B, k)
planes — column indices and values — instead of an (m, B, d) dense tile, so
at CCAT sparsity (0.16%) the per-iteration bytes drop ~600×. The dense weight
vector w stays resident; only the feature matrix is sparse (mixing/Push-Sum
are over weights and never see the ELL planes).

Both kernels run over grid (m, d/blk_d) and express the irregular access as
an on-the-fly one-hot contraction against the current d-block — the
MXU-friendly form of gather/scatter on TPU (compare iota, then matmul). The
one-hot is built per minibatch row, transposed: ``onehot_t`` compares the
row's (1, k) column indices against a (blk_d, k) sublane iota, so the
planes stay lane-dense in their natural (B, k) layout and no index ever
moves from lanes to sublanes. Rows are walked by a ``fori_loop`` over the
real (unpadded) rows only; padded rows are inert.

  * ``ell_margins``    — margins m_b = y_b · Σ_k vals[b,k] · w[cols[b,k]].
    Per d-block and row, w_blk (1, blk_d) @ onehot_t (blk_d, k) gathers the
    in-block weight entries (out-of-block indices match no sublane and
    contribute 0 — no explicit mask needed), accumulated over blocks in the
    resident (B, k) output. Each entry receives exactly one nonzero term, so
    the gather is exact; the per-row Σ_k vals·w[cols] runs outside the
    kernel, in the oracle's order.
  * ``ell_grad_update`` — the scatter-add g += Σ_b coeff_b · vals[b,:] onto
    the violator columns, fused with the Pegasos axpy
    w_half = (1 - lam·alpha) w + (alpha/B) g. Each d-block owns its output
    slice, so the grid is embarrassingly parallel — no cross-block scratch.

Layout: w travels as (m, 1, d) rows whose leading axis the grid walks, so
every block's last two dims are (8, 128) multiples or the array's own
extents, as Mosaic requires.

One-vs-rest classes ride in the same ``pallas_call``s: W is (m, C_pad, d),
C_pad = C rounded up to a sublane multiple (``class_pad``; 1 stays 1, so a
single class traces the binary shapes), and a program's weight block is
(1, C_pad, blk_d). One one-hot per row then serves every class: the gather
is (C_pad, blk_d) @ (blk_d, k), C_pad rows of the MXU's M dimension where
the binary kernel fills one, written to rows [b·C_pad, (b + 1)·C_pad) of a
(m, B·C_pad, k) output; the scatter is (C_pad, k) @ (k, blk_d) from the
matching rows of the (m, B·C_pad, k) class coefficients. Both contractions run at
``Precision.HIGHEST``: the one-hot operand is exact in any precision, and
f32 weights then come through unrounded on the chip.

Pad convention (repro.sparse.formats.ELL): pad entries carry (col=0, val=0),
pad *rows* carry y=0 — both are inert in the contraction, so the kernels take
no validity plane. VMEM per program is the planes plus one (blk_d, k)
one-hot: callers bound k·blk_d (ops.ell_fleet_half_step picks blk_d).
Interpret mode off-TPU as everywhere else in this package.

Two schedules per op:

  * **sweep** (``ell_margins`` / ``ell_grad_update``) — grid (m, d/blk_d):
    every node walks *all* d-blocks every launch. Data-oblivious, and the
    parity oracle for the schedule below.
  * **touched-block** (``ell_margins_prefetch`` / ``ell_grad_update_prefetch``)
    — grid (m, n_blocks_max) over a compact per-node touched-block-id map
    (repro.sparse.formats.block_map; ops.ell_block_map is the on-device twin).
    The map rides in as a ``PrefetchScalarGridSpec`` scalar-prefetch operand so
    the ``index_map`` can steer each program's DMA to exactly one *live* w
    block. Empty slots carry the sentinel id ``n_d_blocks`` and alias the
    all-zero pad block appended after w's last real block — inert on read, and
    ``pl.when`` skips their contraction so FLOPs track live blocks too.
    Sentinel slots are contiguous at the map's tail (the map is sorted), so
    Mosaic's revisit logic collapses their DMAs into one. Per-node cost
    becomes O(touched · B·k·blk_d) instead of O(B·k·d) — proportional to the
    node's own nonzero structure, which is the GADGET paper's per-node-local
    cost model.

The full-data objective pass (``ell_objective``) is a third kernel: the hinge
sum over every row of every node's whole (n_i, k) partition, not a minibatch.
Its w stays resident in VMEM as (⌈d/128⌉, 128) rows and each column id is
resolved on chip: ``hi = col >> 7`` picks the row and ``lo = col & 127`` the
lane, through an in-register lane gather (``take_along_axis``, Mosaic's
``tpu.dynamic_gather``) of every w row, kept where ``hi`` matches. The scan
visits all ⌈d/128⌉ rows for every block of 8 slots, so its cost depends on
the shapes alone, not on the order of a row's slots. XLA stores a (m, n_i, k) plane with n_i as its
lane dimension (k = 76 would pad to 128 lanes), so the kernel reads it through
``swapaxes``, a bitcast rather than a copy, as (m, k, n_i) blocks: slots on
sublanes, rows on lanes. A row's Σ_k is then a sublane reduction that lands
lane-major beside its label.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

__all__ = ["ell_margins", "ell_grad_update", "ell_margins_prefetch",
           "ell_grad_update_prefetch", "ell_objective", "onehot_t",
           "contract_last", "class_pad", "DEFAULT_BLK_D_SPARSE"]

DEFAULT_BLK_D_SPARSE = 512

# Rows per ``ell_objective`` grid program (a lane multiple) and w rows per
# step of its w-row scan: a step gathers OBJECTIVE_UNROLL rows for every
# (8, 128) vreg of the tile's slot group, enough independent gathers to hide
# a loop step's latency. On a v5e at CCAT's shape, 2,048 rows and 8 a step
# take 115 ms a pass; 1,024 rows take 125 ms.
OBJECTIVE_TILE = 2048
OBJECTIVE_UNROLL = 8
_LANES = 128


def onehot_t(cols_row, base, blk_d: int):
    """One row's (1, k) column indices → the (blk_d, k) f32 transposed
    one-hot of the d-block starting at ``base``: entry e sets sublane
    ``cols[e] - base`` when that lies in the block, and no sublane
    otherwise."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (blk_d, cols_row.shape[1]), 0)
    return ((cols_row - base) == rows).astype(jnp.float32)


def contract_last(a, b):
    """a (r, n) · b (c, n)^T → (r, c), f32 on the MXU at full precision."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def class_pad(C: int) -> int:
    """Class rows a weight block holds: C rounded up to a sublane multiple,
    except that one class stays one row (the binary kernels' shapes)."""
    return C if C == 1 else -(-C // 8) * 8


def _class_rows(b, c_pad: int):
    """Row b's slice of the (B·C_pad, k) gathered / coefficient planes."""
    if c_pad == 1:
        return pl.ds(b, 1)
    return pl.ds(pl.multiple_of(b * c_pad, 8), c_pad)


def _as_class_plane(W):
    """(m, d) binary weights as (m, 1, d); (m, C_pad, d) as they are."""
    return W.reshape(W.shape[0], -1, W.shape[-1])


def _class_contrib(coeff, vals):
    """The scatter's (m, B·C_pad, k) rows coeff·vals: (m, B) binary
    coefficients give (m, B, k); (m, B, C) class ones C_pad rows a row."""
    if coeff.ndim == 2:
        return coeff[:, :, None] * vals
    m, B, C = coeff.shape
    cp = jnp.pad(coeff, ((0, 0), (0, 0), (0, class_pad(C) - C)))
    return (cp[..., None] * vals[:, :, None, :]).reshape(m, B * class_pad(C), -1)


def _gather_rows(cols_ref, w, base, g_ref, *, blk_d, n_rows):
    """g[b] += w_blk[:, cols[b] - base] for the first ``n_rows`` rows; w is
    the (C_pad, blk_d) block, every class gathered by one one-hot."""
    c_pad = w.shape[0]

    def row(b, carry):
        oh = onehot_t(cols_ref[0, pl.ds(b, 1), :], base, blk_d)
        g_ref[0, _class_rows(b, c_pad), :] += jnp.dot(
            w, oh, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_rows, row, 0)


def _scatter_rows(cols_ref, contrib_ref, base, *, blk_d, n_rows, c_pad):
    """(C_pad, blk_d) Σ_b Σ_k contrib[b, c, k] over the entries in the d-block."""
    def row(b, acc):
        oh = onehot_t(cols_ref[0, pl.ds(b, 1), :], base, blk_d)
        return acc + contract_last(contrib_ref[0, _class_rows(b, c_pad), :], oh)

    return jax.lax.fori_loop(0, n_rows, row,
                             jnp.zeros((c_pad, blk_d), jnp.float32))


def _margins_from_gathered(gathered, vals, y):
    """y · Σ_k vals·w[cols] from the kernel's gathered weights: (m, B, k)
    for (m, B) binary labels, (m, B·C_pad, k) for (m, B, C) class labels."""
    if y.ndim == 2:
        return y * jnp.sum(vals * gathered, axis=-1)
    m, B, C = y.shape
    g = gathered.reshape(m, B, -1, gathered.shape[-1])[:, :, :C]
    return y * jnp.sum(vals[:, :, None, :] * g, axis=-1)


def _ell_gather_kernel(cols_ref, w_ref, g_ref, *, blk_d, n_rows):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)

    _gather_rows(cols_ref, w_ref[0], j * blk_d, g_ref, blk_d=blk_d,
                 n_rows=n_rows)


def ell_margins(cols: jax.Array, vals: jax.Array, W: jax.Array, y: jax.Array, *,
                blk_d: int = DEFAULT_BLK_D_SPARSE, n_rows: int | None = None,
                interpret: bool = False) -> jax.Array:
    """y * (X @ w) per node over ELL planes. cols/vals: (m, B, k) int32/f32,
    W: (m, d), y: (m, B) → (m, B) margins; or W (m, C_pad, d), y (m, B, C)
    → (m, B, C). d must be a blk_d multiple; rows past ``n_rows`` (default:
    all) are padding and score 0."""
    m, B, k = cols.shape
    W3 = _as_class_plane(W)
    c_pad, d = W3.shape[1:]
    assert d % blk_d == 0, "wrapper must pad d"
    kern = functools.partial(_ell_gather_kernel, blk_d=blk_d,
                             n_rows=B if n_rows is None else n_rows)
    gathered = pl.pallas_call(
        kern,
        name="ell_fleet_half_step_sweep_gather",
        grid=(m, d // blk_d),
        in_specs=[
            pl.BlockSpec((1, B, k), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, c_pad, blk_d), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, B * c_pad, k), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, B * c_pad, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cols, W3)
    return _margins_from_gathered(gathered, vals, y)


def _ell_grad_kernel(cols_ref, contrib_ref, w_ref, scal_ref, o_ref, *, blk_d,
                     n_rows):
    j = pl.program_id(1)
    g = _scatter_rows(cols_ref, contrib_ref, j * blk_d, blk_d=blk_d,
                      n_rows=n_rows, c_pad=w_ref.shape[1])
    o_ref[0] = (1.0 - scal_ref[0]) * w_ref[0] + scal_ref[1] * g


def ell_grad_update(cols: jax.Array, vals: jax.Array, W: jax.Array,
                    coeff: jax.Array, scal: jax.Array, *,
                    blk_d: int = DEFAULT_BLK_D_SPARSE,
                    n_rows: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """W_half = (1 - scal[0]) W + scal[1] * scatter(coeff · vals → cols), per
    node. coeff: (m, B) = 1[margin<1]·y with W (m, d), or (m, B, C) with W
    (m, C_pad, d); scal: (2,) = [lam·alpha, alpha/B] in SMEM. Each (node,
    d-block) program writes its own output slice."""
    m, B, k = cols.shape
    W3 = _as_class_plane(W)
    c_pad, d = W3.shape[1:]
    assert d % blk_d == 0, "wrapper must pad d"
    kern = functools.partial(_ell_grad_kernel, blk_d=blk_d,
                             n_rows=B if n_rows is None else n_rows)
    out = pl.pallas_call(
        kern,
        name="ell_fleet_half_step_sweep_update",
        grid=(m, d // blk_d),
        in_specs=[
            pl.BlockSpec((1, B, k), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, B * c_pad, k), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, c_pad, blk_d), lambda i, j: (i, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, c_pad, blk_d), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((m, c_pad, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cols, _class_contrib(coeff, vals), W3, scal)
    return out.reshape(W.shape)


# ---------------------------------------------------------------------------
# Touched-block schedule (scalar-prefetch): grid (m, n_blocks_max)
# ---------------------------------------------------------------------------


def _ell_gather_prefetch_kernel(bids_ref, cols_ref, w_ref, g_ref, *,
                                blk_d, n_d_blocks, n_rows):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)

    bid = bids_ref[i, j]

    @pl.when(bid < n_d_blocks)  # sentinel slots: DMA aliases the pad block,
    def _():                    # contraction skipped — FLOPs track live blocks
        _gather_rows(cols_ref, w_ref[0], bid * blk_d, g_ref, blk_d=blk_d,
                     n_rows=n_rows)


def ell_margins_prefetch(cols: jax.Array, vals: jax.Array, W: jax.Array,
                         y: jax.Array, block_ids: jax.Array, *, blk_d: int,
                         n_d_blocks: int, n_rows: int | None = None,
                         interpret: bool = False) -> jax.Array:
    """Touched-block twin of :func:`ell_margins`.

    ``block_ids``: (m, n_blocks_max) compact touched-block-id map (live ids
    ascending, then the sentinel ``n_d_blocks``), scalar-prefetched so the
    w ``index_map`` DMAs exactly the one live block each program contracts
    against. W must carry the sentinel's landing pad: shape
    (m, (n_d_blocks + 1)·blk_d), or (m, C_pad, …) with classes, with the
    last block all-zero."""
    m, B, k = cols.shape
    W3 = _as_class_plane(W)
    c_pad, d = W3.shape[1:]
    assert d == (n_d_blocks + 1) * blk_d, "caller pads W + zero block"
    n_blocks_max = block_ids.shape[1]
    kern = functools.partial(_ell_gather_prefetch_kernel, blk_d=blk_d,
                             n_d_blocks=n_d_blocks,
                             n_rows=B if n_rows is None else n_rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m, n_blocks_max),
        in_specs=[
            pl.BlockSpec((1, B, k), lambda i, j, b: (i, 0, 0)),
            pl.BlockSpec((1, c_pad, blk_d), lambda i, j, b: (i, 0, b[i, j])),
        ],
        out_specs=pl.BlockSpec((1, B * c_pad, k), lambda i, j, b: (i, 0, 0)),
    )
    gathered = pl.pallas_call(
        kern,
        name="ell_fleet_half_step_gather",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, B * c_pad, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_ids, cols, W3)
    return _margins_from_gathered(gathered, vals, y)


def _ell_grad_prefetch_kernel(bids_ref, cols_ref, contrib_ref, g_ref, *,
                              blk_d, n_d_blocks, n_rows):
    i, j = pl.program_id(0), pl.program_id(1)
    bid = bids_ref[i, j]
    g_ref[0, 0] = jnp.zeros_like(g_ref[0, 0])

    @pl.when(bid < n_d_blocks)
    def _():
        g_ref[0, 0] = _scatter_rows(cols_ref, contrib_ref, bid * blk_d,
                                    blk_d=blk_d, n_rows=n_rows,
                                    c_pad=g_ref.shape[2])


def ell_grad_update_prefetch(cols: jax.Array, vals: jax.Array,
                             coeff: jax.Array, block_ids: jax.Array, *,
                             blk_d: int, n_d_blocks: int,
                             n_rows: int | None = None,
                             interpret: bool = False) -> jax.Array:
    """Touched-block twin of :func:`ell_grad_update`'s scatter phase.

    Returns the raw per-bucket scatter-adds g — (m, n_blocks_max, blk_d),
    bucket j of node i holding Σ_b coeff_b · vals[b, :] over the entries in
    d-block ``block_ids[i, j]`` (sentinel buckets are zeros); with (m, B, C)
    class coefficients (m, n_blocks_max, C_pad, blk_d), a row per class.
    Unlike the sweep kernel it neither reads w nor applies the Pegasos axpy:
    untouched blocks still need the (1 − λα) decay, so the wrapper folds the
    buckets into the decayed weights with one masked scatter — see
    ops.ell_fleet_half_step."""
    m, B, k = cols.shape
    n_blocks_max = block_ids.shape[1]
    c_pad = 1 if coeff.ndim == 2 else class_pad(coeff.shape[2])
    kern = functools.partial(_ell_grad_prefetch_kernel, blk_d=blk_d,
                             n_d_blocks=n_d_blocks,
                             n_rows=B if n_rows is None else n_rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m, n_blocks_max),
        in_specs=[
            pl.BlockSpec((1, B, k), lambda i, j, b: (i, 0, 0)),
            pl.BlockSpec((1, B * c_pad, k), lambda i, j, b: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, c_pad, blk_d), lambda i, j, b: (i, j, 0, 0)),
    )
    out = pl.pallas_call(
        kern,
        name="ell_fleet_half_step_update",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n_blocks_max, c_pad, blk_d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_ids, cols, _class_contrib(coeff, vals))
    return out if coeff.ndim == 3 else out.reshape(m, n_blocks_max, blk_d)


# ---------------------------------------------------------------------------
# Full-data objective pass: grid (m, ⌈n_i/tile⌉), w resident in VMEM
# ---------------------------------------------------------------------------


def _gather_w(w_ref, col):
    """w[col] for one slot group's (≤ 8, tile) block of column ids, as its
    (≤ 8, 128) vregs: a lane gather of every w row, kept where it is the
    entry's row. Garbage lanes past the array's end match some row or none;
    the caller masks their rows."""
    hi, lo = col >> 7, col & (_LANES - 1)
    unroll = OBJECTIVE_UNROLL
    vregs = [(hi[:, c:c + _LANES], lo[:, c:c + _LANES])
             for c in range(0, col.shape[1], _LANES)]

    def rows(q, gs):  # ``unroll`` w rows a step (Mosaic unrolls no loop)
        for u in range(unroll):
            r = q * unroll + u
            wr = jnp.broadcast_to(w_ref[pl.ds(r, 1), :], vregs[0][0].shape)
            gs = tuple(jnp.where(h == r, jnp.take_along_axis(wr, l, axis=1), g)
                       for (h, l), g in zip(vregs, gs))
        return gs

    zero = jnp.zeros(vregs[0][0].shape, jnp.float32)
    return jax.lax.fori_loop(0, w_ref.shape[0] // unroll, rows,
                             (zero,) * len(vregs))


def _ell_objective_kernel(counts_ref, cols_ref, vals_ref, y_ref, w_ref, o_ref):
    i, j = pl.program_id(0), pl.program_id(1)
    k, tile = cols_ref.shape[1:]
    lanes = [pl.ds(c, _LANES) for c in range(0, tile, _LANES)]
    s = [jnp.zeros((1, _LANES), jnp.float32)] * len(lanes)
    for g0 in range(0, k, 8):  # slot groups: the tile's rows of 8 slots
        slots = pl.ds(g0, min(8, k - g0))
        gs = _gather_w(w_ref, cols_ref[0, slots, :])
        s = [s_c + jnp.sum(vals_ref[0, slots, lane] * g, axis=0, keepdims=True)
             for s_c, lane, g in zip(s, lanes, gs)]
    hinge = jnp.zeros((1, _LANES), jnp.float32)
    for s_c, lane in zip(s, lanes):
        margin = y_ref[0, :, lane] * s_c
        row = (jax.lax.broadcasted_iota(jnp.int32, margin.shape, 1)
               + j * tile + lane.start)
        # where, never a multiply: rows past the array's end hold garbage
        hinge += jnp.where(row < counts_ref[i], jnp.maximum(0.0, 1.0 - margin),
                           0.0)
    o_ref[0, 0] = hinge


def ell_objective(cols: jax.Array, vals: jax.Array, y: jax.Array,
                  w: jax.Array, counts: jax.Array, *,
                  interpret: bool = False) -> jax.Array:
    """Σ max(0, 1 − y·⟨w, x⟩) over the first ``counts[i]`` rows of every node.

    cols/vals: the whole (m, n_i, k) int32/f32 partition planes; y: (m, n_i);
    w: (d,) f32, zero-padded here to rows of 128, one block for the whole
    grid; counts: (m,) int32 real rows per node, scalar-prefetched. Rows from
    ``counts[i]`` on (pad rows, and the tail tile's out-of-bounds lanes) are
    left out. Each (node, tile) program writes its 128 lane partials;
    returns their sum."""
    m, n_i, k = cols.shape
    step = _LANES * OBJECTIVE_UNROLL  # the scan covers w in whole steps
    w_rows = jnp.pad(w, (0, -w.shape[0] % step)).reshape(-1, _LANES)
    n_hi = w_rows.shape[0]
    tile = min(OBJECTIVE_TILE, -(-n_i // _LANES) * _LANES)
    n_tiles = -(-n_i // tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m, n_tiles),
        in_specs=[
            pl.BlockSpec((1, k, tile), lambda i, j, c: (i, 0, j)),
            pl.BlockSpec((1, k, tile), lambda i, j, c: (i, 0, j)),
            pl.BlockSpec((1, 1, tile), lambda i, j, c: (i, 0, j)),
            pl.BlockSpec((n_hi, _LANES), lambda i, j, c: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, _LANES), lambda i, j, c: (i, j, 0, 0)),
    )
    out = pl.pallas_call(
        _ell_objective_kernel,
        name="ell_objective",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n_tiles, 1, _LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(counts, jnp.swapaxes(cols, 1, 2), jnp.swapaxes(vals, 1, 2),
      y.reshape(m, 1, n_i), w_rows)
    return jnp.sum(out)
