"""Jitted public op: fused Pegasos step backed by the Pallas kernels.

Handles padding to block multiples, violator-coefficient computation, the
global-norm ball projection (O(d) in jnp), and the loss scalar.

Also the *dispatch layer* for callers that embed the kernels inside larger
jitted programs (GADGET's device-resident gossip loop): ``local_half_step``
(one node) and ``fleet_half_step`` (all m nodes, one fused launch) are
jit/vmap/scan-safe (no jit of their own) and ``default_interpret`` picks
Pallas interpret mode automatically off-TPU so CPU CI runs the same code path.
``padded_row_mask`` is the single statement of the padded-row convention all
three wrappers share.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.hinge_subgrad import hinge_subgrad as K
from repro.kernels.hinge_subgrad import predict as P
from repro.kernels.hinge_subgrad import sparse as S
from repro.sparse.formats import DEFAULT_BUCKET_BLK_D
from repro.telemetry import registry as tmr

__all__ = ["pegasos_step", "local_half_step", "fleet_half_step",
           "ell_fleet_half_step", "ell_block_map", "resolve_ell_schedule",
           "ell_objective",
           "dense_predict", "ell_predict", "resolve_block_cap",
           "padded_row_mask", "default_interpret",
           "launch_cost", "record_launch",
           "FLEET_TILE_BUDGET_BYTES", "ELL_ONEHOT_BUDGET",
           "ELL_PREFETCH_BLK_D"]

# Largest per-node (B_pad, d_pad) f32 minibatch tile the fused fleet kernel
# will keep resident in VMEM (per grid program). Above this, fleet_half_step
# falls back to the two-kernel vmapped path, which streams X in blocks.
FLEET_TILE_BUDGET_BYTES = 4 * 1024 * 1024


def default_interpret() -> bool:
    """True when the Pallas kernels should run in interpret mode: exactly
    when the backend is not a TPU, so CPU CI exercises the kernel code path
    and a TPU always compiles the kernels."""
    return jax.default_backend() != "tpu"


def launch_cost(kind: str, *, m: int = 1, B: int = 0, d: int = 0, k: int = 0,
                C: int = 1, schedule: str = "sweep", blk_d: int = 0,
                n_blocks_max: int = 0) -> dict:
    """Analytic per-call cost of one Pallas entry point, from shapes alone.

    Returns ``{"launches", "bytes", "flops"}`` (plus ``"blocks_visited"``
    for the block-scheduled sparse kinds) — the single cost model behind the
    registry's ``kernel.*`` series, shared by the dispatch wrappers, the
    training loop's host accounting, the serving engine, and the benches
    (which previously each derived their own). Bytes count f32 data planes
    crossing HBM per launch (int32 column planes count 4 bytes like values);
    FLOPs count multiply-add pairs as 2. These are *model* numbers — the
    roofline/accounting currency, not measured traffic.

    Kinds: ``local_half_step`` (two launches: margins + grad),
    ``fleet_half_step`` (one fused launch, or the 2m-launch vmapped fallback
    above ``FLEET_TILE_BUDGET_BYTES`` — the model applies the same cutover),
    ``ell_fleet_half_step`` (two launches; prefetch visits
    ``m·n_blocks_max`` w blocks, sweep visits every block; with ``C``
    one-vs-rest classes each block holds C rows and every weight counts C
    times),
    ``dense_predict`` / ``ell_predict`` (one fused launch each).
    """
    if kind == "local_half_step":
        return {"launches": 2, "bytes": 4 * (2 * B * d + 3 * d + 3 * B),
                "flops": 4 * B * d + 2 * d}
    if kind == "fleet_half_step":
        Bp, dp = -(-B // 8) * 8, -(-d // 128) * 128
        if Bp * dp * 4 > FLEET_TILE_BUDGET_BYTES:  # blocked two-kernel path
            per = launch_cost("local_half_step", B=B, d=d)
            return {key: m * v for key, v in per.items()}
        return {"launches": 1, "bytes": 4 * m * (B * d + 2 * d + 2 * B),
                "flops": m * (4 * B * d + 2 * d)}
    if kind == "ell_fleet_half_step":
        entry_bytes = 16 * m * B * k  # cols+vals, read by both passes
        if schedule == "prefetch":
            blocks = m * n_blocks_max
            w_bytes = 12 * blocks * blk_d + 8 * m * d  # 2R+1W blocks + axpy
        else:
            n_d_blocks = -(-d // max(blk_d, 1))
            blocks = m * n_d_blocks
            w_bytes = 12 * m * n_d_blocks * max(blk_d, 1)
        return {"launches": 2, "bytes": entry_bytes + C * w_bytes,
                "flops": C * m * (4 * B * k + 2 * d), "blocks_visited": blocks}
    if kind == "dense_predict":
        return {"launches": 1, "bytes": 4 * (B * d + C * d + B * C + B),
                "flops": 2 * B * C * d}
    if kind == "ell_predict":
        blocks = n_blocks_max
        return {"launches": 1,
                "bytes": 8 * B * k + 4 * (blocks * blk_d * C + B * C + B),
                "flops": 2 * C * B * k, "blocks_visited": blocks}
    raise ValueError(f"unknown kernel kind {kind!r}")


def record_launch(kind: str, n: int = 1, *, registry=None,
                  blocks_visited: float | None = None, **shape) -> dict:
    """Account ``n`` executions of a Pallas entry point on the registry.

    Increments ``kernel.launches`` / ``kernel.bytes`` / ``kernel.flops``
    (and ``kernel.blocks_visited`` for block-scheduled kinds — pass
    ``blocks_visited`` to override the static cap with a measured live
    count), all labeled ``kernel=<kind>``, using :func:`launch_cost` for the
    per-call numbers. Host-side bookkeeping only; returns the per-call cost
    dict. Jitted callers account at their host boundary (the wrappers only
    self-record when executed eagerly — tracing must stay side-effect-free
    so retraces don't double-count)."""
    reg = tmr.default_registry() if registry is None else registry
    cost = launch_cost(kind, **shape)
    reg.counter("kernel.launches", kernel=kind).inc(n * cost["launches"])
    reg.counter("kernel.bytes", kernel=kind).inc(n * cost["bytes"])
    reg.counter("kernel.flops", kernel=kind).inc(n * cost["flops"])
    bv = cost.get("blocks_visited") if blocks_visited is None else blocks_visited
    if bv is not None:
        reg.counter("kernel.blocks_visited", kernel=kind).inc(n * bv)
    return cost


def _maybe_record(kind: str, probe, **shape) -> None:
    """Self-record one eager execution of a dispatch wrapper.

    ``probe`` is any input array: when it is a tracer the wrapper is being
    traced into a caller's jit (the body runs once, not per execution), so
    recording would count compiles, not launches — the caller's host
    boundary accounts instead (``gadget_train`` post-run, the serving
    engine per score call)."""
    if isinstance(probe, jax.core.Tracer):
        return
    record_launch(kind, **shape)


def _project_ball(w: jax.Array, lam: float) -> jax.Array:
    """Pegasos 1/sqrt(lam)-ball projection. Duplicates obj.project_ball on
    purpose: core imports kernels, so kernels cannot import core."""
    norm = jnp.linalg.norm(w)
    scale = jnp.minimum(1.0, (1.0 / jnp.sqrt(lam)) / jnp.maximum(norm, 1e-30))
    return w * scale


def project_rows(W: jax.Array, lam: float) -> jax.Array:
    """Each row (last axis) of an (m, d) or (m, C, d) plane onto the ball.
    Rows are mapped in place: a reshape of a class plane to (m·C, d) rows
    would relay it out in device memory, C not being a sublane multiple."""
    project = functools.partial(_project_ball, lam=lam)
    for _ in range(W.ndim - 1):
        project = jax.vmap(project)
    return project(W)


def padded_row_mask(n_padded: int, n_valid: int) -> jax.Array:
    """Validity mask for minibatch rows introduced by block padding.

    The single statement of the padded-row invariant all hinge_subgrad
    wrappers share: X/y/w are zero-padded to block multiples, so padded rows
    carry **y = 0**. A padded row therefore selects into the violator set
    (margin 0 < 1) but with coefficient ``1[m<1]·y = 0`` — consumers that only
    need the violator *coefficients* (``local_half_step``) are correct with no
    mask at all. Anything that counts, sums, or re-weights rows — the hinge
    loss in ``pegasos_step``, the explicit coefficient masking in the fused
    fleet kernel — must AND/multiply with this mask instead of re-deriving
    its own convention.
    """
    return jnp.arange(n_padded) < n_valid


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def local_half_step(w: jax.Array, X: jax.Array, y: jax.Array, *, lam: float,
                    t: jax.Array, project: bool = True,
                    blk_b: int = K.DEFAULT_BLK_B, blk_d: int = K.DEFAULT_BLK_D,
                    interpret: bool | None = None) -> jax.Array:
    """GADGET step (e)+(f): kernel-backed Pegasos half-step, no loss scalar.

    Deliberately NOT jitted — it is traced inside the caller's jit (vmapped
    over the node axis, scanned over iterations in the gossip loop). Needs no
    validity mask: per the ``padded_row_mask`` invariant, padded rows carry
    y=0 and so contribute coefficient 0 to the gradient.
    """
    B, d = X.shape
    _maybe_record("local_half_step", X, B=B, d=d)
    if interpret is None:
        interpret = default_interpret()
    blk_b_, blk_d_ = min(blk_b, B), min(blk_d, d)
    Xp = _pad_to(_pad_to(X.astype(jnp.float32), blk_b_, 0), blk_d_, 1)
    wp = _pad_to(w.astype(jnp.float32), blk_d_, 0)
    yp = _pad_to(y.astype(jnp.float32), blk_b_, 0)

    m = K.margins(Xp, wp, yp, blk_b=blk_b_, blk_d=blk_d_, interpret=interpret)
    coeff = jnp.where(m < 1.0, yp, 0.0)

    tf = jnp.asarray(t, jnp.float32)
    alpha = 1.0 / (lam * tf)
    scal = jnp.stack([lam * alpha, alpha / B])
    w_half = K.grad_update(Xp, wp, coeff, scal, blk_b=blk_b_, blk_d=blk_d_,
                           interpret=interpret)[:d]
    if project:
        with jax.named_scope("gadget.project"):
            w_half = _project_ball(w_half, lam)
    return w_half.astype(w.dtype)


def fleet_half_step(W: jax.Array, X: jax.Array, y: jax.Array, *, lam: float,
                    t: jax.Array, project: bool = True,
                    interpret: bool | None = None) -> jax.Array:
    """GADGET steps (a)-(e) for the whole node fleet in ONE kernel launch.

    W: (m, d) per-node weights, X: (m, B, d) gathered minibatch tiles,
    y: (m, B). Replaces ``vmap(local_half_step)`` — the node axis becomes the
    kernel's parallel grid dimension, so one ``pallas_call`` does the work of
    2m launches and each X tile crosses HBM once instead of twice.

    Like ``local_half_step`` this is trace-safe (no jit of its own) for use
    inside the device-resident gossip loop. Tiles larger than
    ``FLEET_TILE_BUDGET_BYTES`` fall back to the blocked two-kernel path,
    which never needs the whole tile resident.

    One-vs-rest: W (m, C, d) and y (m, B, C) run the kernel once per class
    on the same tiles (a class grid axis, by ``vmap``).
    """
    if W.ndim == 3:
        return jax.vmap(
            lambda Wc, yc: fleet_half_step(Wc, X, yc, lam=lam, t=t, project=project,
                                           interpret=interpret),
            in_axes=(1, 2), out_axes=1)(W, y)
    m, B, d = X.shape
    _maybe_record("fleet_half_step", X, m=m, B=B, d=d)
    if interpret is None:
        interpret = default_interpret()

    Bp = -(-B // 8) * 8        # f32 sublane multiple
    dp = -(-d // 128) * 128    # lane multiple
    if Bp * dp * 4 > FLEET_TILE_BUDGET_BYTES:
        return jax.vmap(
            lambda w, Xi, yi: local_half_step(w, Xi, yi, lam=lam, t=t,
                                              project=project, interpret=interpret)
        )(W, X, y)

    Xp = _pad_to(_pad_to(X.astype(jnp.float32), 8, 1), 128, 2)
    Wp = _pad_to(W.astype(jnp.float32), 128, 1)
    yp = _pad_to(y.astype(jnp.float32), 8, 1)
    mask = padded_row_mask(Bp, B).astype(jnp.float32)

    tf = jnp.asarray(t, jnp.float32)
    alpha = 1.0 / (lam * tf)
    scal = jnp.stack([lam * alpha, alpha / B])
    W_half = K.fleet_half_step(Xp, Wp, yp, mask, scal, interpret=interpret)[:, :d]
    if project:
        with jax.named_scope("gadget.project"):
            W_half = project_rows(W_half, lam)
    return W_half.astype(W.dtype)


# Cap on the (blk_d, k) f32 one-hot each sparse-kernel program materializes
# in VMEM per minibatch row; the wrapper shrinks blk_d (lane-multiple floor)
# to stay under it.
ELL_ONEHOT_BUDGET = 4 * 1024 * 1024

# Touched-block (scalar-prefetch) schedule block width: the 128-lane minimum,
# single-sourced from the formats layer so host bounds and kernel grids agree.
# Fine blocks over-fetch the least per live block; the sweep schedule makes
# the opposite trade (coarse blocks, short data-oblivious grid).
ELL_PREFETCH_BLK_D = DEFAULT_BUCKET_BLK_D


def _ell_blk_d(d_pad: int, k_pad: int) -> int:
    blk = min(S.DEFAULT_BLK_D_SPARSE, d_pad)
    while blk > 128 and k_pad * blk * 4 > ELL_ONEHOT_BUDGET:
        # shrink in 128-lane multiples only — Mosaic block shapes require it
        blk = max(128, blk // 2 // 128 * 128)
    return blk


def ell_block_map(cols: jax.Array, vals: jax.Array, *, blk_d: int,
                  n_d_blocks: int, n_blocks_max: int) -> jax.Array:
    """Compact per-node touched-block-id map, on device and trace-safe: the
    twin of ``repro.sparse.formats.block_map`` (tests pin them together).

    cols/vals: (m, B, k) minibatch planes → (m, n_blocks_max) int32 with each
    node's distinct live d-block ids ascending, then the inert sentinel
    ``n_d_blocks``. Pad entries (val = 0) mark nothing. Cost is one O(B·k)
    scatter plus an O(n_d_blocks log n_d_blocks) sort per node — noise next to
    the half-step itself.

    **Caller contract**: ``n_blocks_max`` must be ≥ the realized live count —
    use ``formats.minibatch_block_bound`` (sound for every drawable
    minibatch). Traced code cannot raise, so an undersized cap silently drops
    the highest live block ids (margins and gradients lose their
    contributions); the host twin ``formats.block_map`` raises ``ValueError``
    on the same input and is the debugging tool for suspect schedules.
    """
    m = cols.shape[0]
    blk = jnp.where(vals != 0, cols // blk_d, n_d_blocks).reshape(m, -1)
    touched = jax.vmap(
        lambda b: jnp.zeros((n_d_blocks,), jnp.bool_).at[b].set(True, mode="drop")
    )(blk)
    ids = jnp.where(touched, jnp.arange(n_d_blocks, dtype=jnp.int32)[None, :],
                    n_d_blocks)
    ids = jnp.sort(ids, axis=1).astype(jnp.int32)
    if n_d_blocks < n_blocks_max:  # fewer real blocks than map slots: all live
        pad = jnp.full((m, n_blocks_max - n_d_blocks), n_d_blocks, jnp.int32)
        return jnp.concatenate([ids, pad], axis=1)
    return ids[:, :n_blocks_max]


def resolve_ell_schedule(schedule: str, *, B: int, k: int, d: int,
                         n_blocks_max: int | None = None,
                         blk_d: int | None = None) -> tuple[str, int, int]:
    """Pin an ELL schedule request to concrete ``(schedule, blk_d, n_blocks_max)``.

    ``schedule``: "sweep", "prefetch", or "auto". Auto picks prefetch exactly
    when its worst-case w-lane footprint beats the sweep's —
    ``n_blocks_max · ELL_PREFETCH_BLK_D < d_pad`` — which needs a data-derived
    ``n_blocks_max`` (formats.minibatch_block_bound) to ever fire: the
    structural fallback cap ``min(B·k, n_d_blocks)`` is the no-information
    bound. n_blocks_max is clamped to the structural cap either way.
    """
    if schedule not in ("auto", "prefetch", "sweep"):
        raise ValueError(f"unknown ELL schedule {schedule!r}")
    kp = -(-max(k, 1) // 128) * 128
    sweep_blk = _ell_blk_d(-(-d // 128) * 128, kp)
    if schedule == "sweep":
        return "sweep", (blk_d or sweep_blk), 0
    pref_blk = blk_d or ELL_PREFETCH_BLK_D
    n_d_blocks = -(-d // pref_blk)
    cap = max(1, min(n_blocks_max or B * max(k, 1), B * max(k, 1), n_d_blocks))
    if schedule == "prefetch":
        return "prefetch", pref_blk, cap
    sweep_lanes = (-(-d // sweep_blk)) * sweep_blk
    if cap * pref_blk < sweep_lanes:
        return "prefetch", pref_blk, cap
    return "sweep", sweep_blk, 0


def ell_fleet_half_step(W: jax.Array, cols: jax.Array, vals: jax.Array,
                        y: jax.Array, *, lam: float, t: jax.Array,
                        project: bool = True,
                        interpret: bool | None = None,
                        schedule: str = "auto",
                        n_blocks_max: int | None = None,
                        blk_d: int | None = None) -> jax.Array:
    """Sparse GADGET steps (a)-(e) for the whole fleet over ELL planes.

    W: (m, d) per-node weights; cols/vals: (m, B, k) gathered ELL minibatch
    planes (repro.sparse.formats pad convention: pad entries (col=0, val=0),
    pad rows y=0); y: (m, B). Sparse counterpart of ``fleet_half_step`` — two
    kernel launches (gather-dot margins, scatter-add grad) touching O(B·k)
    feature bytes instead of O(B·d).

    One-vs-rest: W (m, C, d) and y (m, B, C) ±1. The same two launches take
    every class: a weight block is (C_pad, blk_d), so one gather of a row's
    columns serves all C classes (``sparse`` module docstring); each (node,
    class) row is projected on its own.

    ``schedule`` selects how the kernels walk w's d-blocks:

    * ``"sweep"`` — the data-oblivious grid (m, d/blk_d): every node visits
      every block (the PR 3 one-hot kernels; parity oracle).
    * ``"prefetch"`` — grid (m, n_blocks_max) over the per-minibatch compact
      touched-block-id map (computed here on device, scalar-prefetched into
      the kernels' index_map): each program DMAs one live w block, so cost
      scales with the blocks this minibatch actually touches. ``n_blocks_max``
      is the static grid bound — pass the data-derived cap from
      ``formats.minibatch_block_bound`` (falls back to min(B·k, n_d_blocks),
      correct but saving-free). The grad kernel emits raw per-bucket
      scatter-adds; the Pegasos axpy is folded here as one elementwise decay
      plus a masked scatter (untouched blocks only decay — same math).
    * ``"auto"`` — prefetch iff its worst-case w-lane footprint beats the
      sweep's (see ``resolve_ell_schedule``).

    Trace-safe (no jit of its own) for use inside the device-resident gossip
    loop. Padding: k → 128-lane multiple, B → 8-sublane multiple, d → blk_d
    multiple (+ one all-zero block, the prefetch sentinel's landing pad); all
    pads are inert under the ELL convention.
    """
    m, B, k = cols.shape
    d = W.shape[-1]
    C = W.shape[1] if W.ndim == 3 else 1
    if k == 0:  # k_max=0 planes (e.g. all rows empty after bucketing): widen
        cols = jnp.zeros((m, B, 1), jnp.int32)  # to one inert (0, 0) entry so
        vals = jnp.zeros((m, B, 1), jnp.float32)  # block shapes stay nonzero
        k = 1
    if interpret is None:
        interpret = default_interpret()
    schedule, blk_d, n_blocks_max = resolve_ell_schedule(
        schedule, B=B, k=k, d=d, n_blocks_max=n_blocks_max, blk_d=blk_d)
    _maybe_record("ell_fleet_half_step", vals, m=m, B=B, k=k, d=d, C=C,
                  schedule=schedule, blk_d=blk_d, n_blocks_max=n_blocks_max)

    colsP = _pad_to(_pad_to(cols.astype(jnp.int32), 8, 1), 128, 2)
    valsP = _pad_to(_pad_to(vals.astype(jnp.float32), 8, 1), 128, 2)
    yp = _pad_to(y.astype(jnp.float32), 8, 1)

    def pad_plane(width):
        """W zero-padded to ``width`` columns, and its classes to C_pad rows."""
        widths = [(0, 0)] * (W.ndim - 1) + [(0, width - d)]
        if W.ndim == 3:
            widths[1] = (0, S.class_pad(C) - C)
        return jnp.pad(W.astype(jnp.float32), widths)

    tf = jnp.asarray(t, jnp.float32)
    alpha = 1.0 / (lam * tf)
    scal = jnp.stack([lam * alpha, alpha / B])

    if schedule == "prefetch":
        n_d_blocks = -(-d // blk_d)
        d_pad = n_d_blocks * blk_d
        bids = ell_block_map(colsP, valsP, blk_d=blk_d, n_d_blocks=n_d_blocks,
                             n_blocks_max=n_blocks_max)
        # one extra zero block after the last real one: the sentinel's DMA pad
        Wp = pad_plane((n_d_blocks + 1) * blk_d)
        margins = S.ell_margins_prefetch(colsP, valsP, Wp, yp, bids,
                                         blk_d=blk_d, n_d_blocks=n_d_blocks,
                                         n_rows=B, interpret=interpret)
        coeff = jnp.where(margins < 1.0, yp, 0.0)
        G = S.ell_grad_update_prefetch(colsP, valsP, coeff, bids, blk_d=blk_d,
                                       n_d_blocks=n_d_blocks, n_rows=B,
                                       interpret=interpret)
        # fold buckets into the axpy: decay everywhere, scatter-add the live
        # buckets (sentinel buckets index past d_pad → dropped, and are zero)
        with jax.named_scope("gadget.decay"):
            if W.ndim == 3:
                W_half = _decay_scatter_blocks(Wp, G, bids, scal)[:, :C, :d]
            else:
                flat = (bids[:, :, None] * blk_d
                        + jnp.arange(blk_d, dtype=jnp.int32)[None, None, :]).reshape(m, -1)
                W_half = jax.vmap(
                    lambda w_row, g, fi: ((1.0 - scal[0]) * w_row)
                    .at[fi].add(scal[1] * g, mode="drop")
                )(Wp[:, :d_pad], G.reshape(m, -1), flat)[:, :d]
    else:
        Wp = pad_plane(-(-d // blk_d) * blk_d)
        margins = S.ell_margins(colsP, valsP, Wp, yp, blk_d=blk_d, n_rows=B,
                                interpret=interpret)
        # pad rows carry y=0 ⇒ coefficient 0 (padded_row_mask invariant):
        # inert in the scatter though their margin 0 selects as a violator
        coeff = jnp.where(margins < 1.0, yp, 0.0)
        W_half = S.ell_grad_update(colsP, valsP, Wp, coeff, scal, blk_d=blk_d,
                                   n_rows=B, interpret=interpret)
        W_half = W_half[:, :C, :d] if W.ndim == 3 else W_half[:, :d]
    if project:
        with jax.named_scope("gadget.project"):
            W_half = project_rows(W_half, lam)
    return W_half.astype(W.dtype)


def _decay_scatter_blocks(Wp: jax.Array, G: jax.Array, bids: jax.Array,
                          scal: jax.Array) -> jax.Array:
    """The class plane's Pegasos axpy: (1 − λα)·W everywhere, plus α/B times
    each bucket added to its whole d-block. Wp: (m, C_pad, (n_d_blocks + 1)·
    blk_d), the padded plane the kernels read; G: (m, n_blocks_max, C_pad,
    blk_d) buckets; bids: (m, n_blocks_max) block ids (a sentinel's zero
    bucket lands in the pad block). One scatter index per touched block, each
    adding a (C_pad, blk_d) window: an index per weight, as the binary path
    scatters, would update one strided column of C rows per index."""
    m, c_pad, width = Wp.shape
    blk_d = G.shape[-1]
    blocks = Wp.reshape(m, c_pad, width // blk_d, blk_d)
    return jax.vmap(
        lambda w, g, b: ((1.0 - scal[0]) * w).at[:, b].add(scal[1] * g)
    )(blocks, jnp.swapaxes(G, 1, 2), bids).reshape(m, c_pad, width)


def ell_objective(w: jax.Array, cols: jax.Array, vals: jax.Array,
                  y: jax.Array, n_counts: jax.Array, *, lam: float,
                  total: jax.Array, interpret: bool | None = None) -> jax.Array:
    """Full-data primal objective over stacked ELL partitions, kernel-backed.

    w: (d,) weights; cols/vals: the (m, n_i, k) partition planes as they are
    stored; y: (m, n_i); n_counts: (m,) real rows per node (the rest are pad
    rows); total: Σ n_counts. The same value as
    ``svm_objective.primal_objective_masked_ell`` over the flattened planes
    and the valid-row mask: ½λ‖w‖² + Σ hinge / total, with the hinge sum from
    the ``ell_objective`` kernel (w resident in VMEM, no per-slot HBM
    gather). Trace-safe, like the other wrappers."""
    m, n_i, k = cols.shape
    if k == 0:  # k_max=0 planes: one inert (0, 0) entry keeps blocks nonzero
        cols = jnp.zeros((m, n_i, 1), jnp.int32)
        vals = jnp.zeros((m, n_i, 1), jnp.float32)
    if interpret is None:
        interpret = default_interpret()
    hinge = S.ell_objective(cols.astype(jnp.int32), vals.astype(jnp.float32),
                            y.astype(jnp.float32), w.astype(jnp.float32),
                            n_counts.astype(jnp.int32), interpret=interpret)
    return 0.5 * lam * jnp.dot(w, w) + hinge / total


# ------------------------------------------------------------------- predict
# Serving-side dispatch (repro.serve): scores + argmax against a trained
# model. ``W`` is either the binary (d,) weight vector or a one-vs-rest
# (C, d) class matrix; both wrappers are trace-safe (no jit of their own) so
# the serving engine and the shard_map batch-parallel path jit them once per
# bucket shape.


def _as_class_matrix(W: jax.Array) -> tuple[jax.Array, bool]:
    W = jnp.asarray(W)
    if W.ndim == 1:
        return W[None, :], True
    if W.ndim != 2:
        raise ValueError(f"W must be (d,) or (C, d), got shape {W.shape}")
    return W, False


def _finish_predict(scores, labels, B, C, binary):
    scores, labels = scores[:B, :C], labels[:B]
    if binary:
        s = scores[:, 0]
        return s, jnp.where(s >= 0.0, 1.0, -1.0)
    return scores, labels


def resolve_block_cap(B: int, k: int, *, n_d_blocks: int,
                      n_blocks_max: int | None = None) -> int:
    """The one statement of the touched-block map width: the requested cap
    (or the no-information ``B·k``) clamped to the structural limits. The
    serving engine's jit-cache key and host-side map width must agree with
    ``ell_predict``'s internal computation — both call this."""
    return max(1, min(n_blocks_max or B * k, B * k, n_d_blocks))


def dense_predict(W: jax.Array, X: jax.Array, *,
                  interpret: bool | None = None,
                  blk_b: int = K.DEFAULT_BLK_B,
                  blk_d: int = K.DEFAULT_BLK_D) -> tuple[jax.Array, jax.Array]:
    """Fused serving scores-and-argmax in one kernel launch.

    W: (d,) binary weights or (C, d) one-vs-rest class matrix; X: (B, d)
    query batch. Returns ``(scores, labels)``: binary → ((B,) margins,
    (B,) f32 sign labels in {-1, +1}); multiclass → ((B, C) scores,
    (B,) int32 argmax). Pads B to a sublane multiple, d to blk_d, C to a
    128-lane multiple (zero class rows, masked out of the in-kernel argmax).
    """
    W2, binary = _as_class_matrix(W)
    C, d = W2.shape
    B = X.shape[0]
    _maybe_record("dense_predict", X, B=B, d=d, C=C)
    if interpret is None:
        interpret = default_interpret()
    blk_b_ = min(blk_b, -(-B // 8) * 8)
    blk_d_ = min(blk_d, -(-d // 128) * 128)
    Xp = _pad_to(_pad_to(X.astype(jnp.float32), blk_b_, 0), blk_d_, 1)
    Wp = _pad_to(_pad_to(W2.astype(jnp.float32), 128, 0), blk_d_, 1)
    scores, labels = P.dense_scores(Xp, Wp, n_classes=C, blk_b=blk_b_,
                                    blk_d=blk_d_, interpret=interpret)
    return _finish_predict(scores, labels, B, C, binary)


def ell_predict(W: jax.Array, cols: jax.Array, vals: jax.Array, *,
                n_blocks_max: int | None = None,
                blk_d: int | None = None,
                block_ids: jax.Array | None = None,
                interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """Sparse serving scores-and-argmax over one padded-ELL query batch.

    cols/vals: (B, k) query planes (formats pad convention: (col=0, val=0)
    entries and all-pad rows are inert — a pad row scores 0 every class and
    labels +1/class 0). The *query-side* touched-block schedule: the batch's
    compact touched-block-id map steers the W DMA so scoring touches only
    live d-blocks — O(live·C·blk_d) weight lanes instead of O(C·d).

    ``n_blocks_max`` is the static grid cap — per-bucket in the serving
    engine (one compile per bucket shape), from
    ``formats.minibatch_block_bound`` over the query set; defaults to the
    structural ``min(B·k, n_d_blocks)``. ``block_ids`` optionally supplies a
    host-computed map (``formats.block_map`` with m=1, shape
    (n_blocks_max,)); by default the map is computed on device
    (``ell_block_map``), keeping the wrapper trace-safe. Returns
    ``(scores, labels)`` with the same shapes/dtypes as ``dense_predict``.
    """
    W2, binary = _as_class_matrix(W)
    C, d = W2.shape
    B, k = cols.shape
    if k == 0:  # all-empty batch: widen to one inert entry (shapes nonzero)
        cols = jnp.zeros((B, 1), jnp.int32)
        vals = jnp.zeros((B, 1), jnp.float32)
        k = 1
    if interpret is None:
        interpret = default_interpret()
    blk_d = blk_d or ELL_PREFETCH_BLK_D
    n_d_blocks = -(-d // blk_d)

    colsP = _pad_to(_pad_to(cols.astype(jnp.int32), 8, 0), 128, 1)
    valsP = _pad_to(_pad_to(vals.astype(jnp.float32), 8, 0), 128, 1)
    if block_ids is not None:
        bids = jnp.asarray(block_ids, jnp.int32)
    else:
        cap = resolve_block_cap(B, k, n_d_blocks=n_d_blocks,
                                n_blocks_max=n_blocks_max)
        bids = ell_block_map(colsP[None], valsP[None], blk_d=blk_d,
                             n_d_blocks=n_d_blocks, n_blocks_max=cap)[0]
    _maybe_record("ell_predict", vals, B=B, k=k, C=C, blk_d=blk_d,
                  n_blocks_max=int(bids.shape[0]))
    # one extra zero block after the last real one: the sentinel's DMA pad
    Wp = _pad_to(_pad_to(W2.astype(jnp.float32), 128, 0),
                 (n_d_blocks + 1) * blk_d, 1)
    scores, labels = P.ell_scores_prefetch(colsP, valsP, Wp, bids,
                                           blk_d=blk_d, n_d_blocks=n_d_blocks,
                                           n_classes=C, interpret=interpret)
    return _finish_predict(scores, labels, B, C, binary)


@functools.partial(jax.jit, static_argnames=("lam", "blk_b", "blk_d", "interpret"))
def pegasos_step(w: jax.Array, X: jax.Array, y: jax.Array, *, lam: float,
                 t: jax.Array, blk_b: int = K.DEFAULT_BLK_B,
                 blk_d: int = K.DEFAULT_BLK_D, interpret: bool = False):
    """Kernel-backed equivalent of ref.pegasos_step_ref -> (w_new, loss)."""
    B, d = X.shape
    blk_b_, blk_d_ = min(blk_b, B), min(blk_d, d)
    Xp = _pad_to(_pad_to(X.astype(jnp.float32), blk_b_, 0), blk_d_, 1)
    wp = _pad_to(w.astype(jnp.float32), blk_d_, 0)
    yp = _pad_to(y.astype(jnp.float32), blk_b_, 0)

    m = K.margins(Xp, wp, yp, blk_b=blk_b_, blk_d=blk_d_, interpret=interpret)
    # the loss sums rows, so it needs the shared padded-row mask (see
    # padded_row_mask: y=0 padding alone only protects the coefficients)
    row_valid = padded_row_mask(Xp.shape[0], B)
    viol = (m < 1.0) & row_valid
    coeff = jnp.where(viol, yp, 0.0)
    loss = jnp.sum(jnp.where(row_valid, jnp.maximum(0.0, 1.0 - m), 0.0)) / B

    alpha = 1.0 / (lam * t.astype(jnp.float32))
    scal = jnp.stack([lam * alpha, alpha / B])
    w_half = K.grad_update(Xp, wp, coeff, scal, blk_b=blk_b_, blk_d=blk_d_,
                           interpret=interpret)[:d]
    return _project_ball(w_half, lam).astype(w.dtype), loss
