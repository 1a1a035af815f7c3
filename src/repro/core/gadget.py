"""GADGET SVM — Gossip-bAseD sub-GradiEnT solver (paper Algorithm 2).

Every node i holds a horizontal partition M_i (n_i × d) and a weight vector
ŵ_i. One iteration t:

  (a-c)  sample a local mini-batch, L̂_i = mean_{violators} y·x under ŵ_i
  (d)    α_t = 1 / (λ t)
  (e)    w̃_i = (1 − λ α_t) ŵ_i + α_t L̂_i          (local Pegasos half-step)
  (f)    [optional] project w̃_i onto the 1/√λ ball
  (g)    ŵ_i ← PushSum(B, w̃_i)                     (gossip consensus)
  (h)    [optional] project again
The algorithm is *anytime*: it stops when max_i ‖ŵ_i^(t+1) − ŵ_i^(t)‖ < ε.

The simulator path is **device-resident and fused** (cfg.fused, the default):
steps (a)-(e) for all m nodes run as ONE Pallas ``fleet_half_step`` launch per
iteration (node axis = parallel grid dimension, each X tile read from HBM
once), and the R Push-Sum rounds of step (g) — a linear map — are collapsed
into a single precomputed product ``P_t = (B_1 ⋯ B_R)^T`` applied as one
mix-and-renormalize matmul. ``cfg.fused=False`` keeps the PR 1 path (two
vmapped kernels per node + an R-round ``lax.scan``) for A/B benchmarking.
Either way the whole training loop — half-steps, mixing, the ε-check and the
objective trace — is one jitted ``lax.while_loop`` with donated weight
buffers. Mixing matrices never cross the host boundary inside the loop:
deterministic topologies (exponential, ring, clique/complete, torus) are
uploaded once as a stacked (period, m, m) array — the per-iteration *product*
cycle when fused, R× smaller — and the paper's random one-neighbor protocol
is drawn with ``jax.random`` inside the step (R draws folded into one (m, m)
product on device when fused). The host wrapper (`gadget_train`) syncs
exactly once, after termination, to materialize traces.

``gadget_train_reference`` keeps the seed's host-chunk loop (per-iteration
host matrix builds, per-chunk ``float(...)`` syncs) on the *same* PRNG
streams — it is the parity oracle for tests and the baseline the transfer
counter in ``benchmarks/gossip_device_bench.py`` measures against.

Sparse partitions: ``gadget_train`` / ``gadget_train_reference`` also accept
``repro.sparse.EllPartitions`` — stacked (m, n_i, k) padded-ELL column/value
planes — in place of the dense (m, n_i, d) array. The local half-step then
runs over the ELL planes (``ell_fleet_half_step`` kernels, or the jnp gather/
scatter oracle off-kernel) touching O(B·k) feature bytes per iteration instead
of O(B·d), and the objective trace does its full-data pass as a gather-dot.
``cfg.sparse_schedule`` picks how those kernels walk w: the data-oblivious
sweep over all d-blocks, or the scalar-prefetch touched-block schedule whose
per-node cost scales with the blocks its own minibatch actually hits (the
static grid cap is derived on host from the partition planes before tracing).
Gossip/Push-Sum are over the *dense* resident weights and are untouched —
mixing is linear in w, so the PR 2 collapsed-product path applies verbatim.
The sparse half-step is inherently fleet-wide (one launch for all m nodes);
``cfg.fused`` therefore only selects collapsed vs sequential mixing in sparse
mode. At CCAT sparsity (0.16%) this is the difference between a ~147 GB dense
train split and ~0.5 GB of planes — the full-shape paper scenario fits.

Weighted consensus: the paper pushes n_i·ŵ_i so the consensus target is the
data-weighted network average Σ n_i ŵ_i / N. We implement this by initializing
the Push-Sum mass weight to n_i — the v/w ratio then converges to exactly that
weighted mean for free, including under non-uniform partitions. Non-uniform
partitions are expressed by passing explicit per-node ``n_counts`` to
`gadget_train` / `gadget_train_reference`: node i's valid rows are the first
n_counts[i] of its (padded) partition, and sampling, mass weights, consensus
and the objective trace all respect them.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults as flt
from repro.core import svm_objective as obj
from repro.core import topology as topo
from repro.core.faults import FaultPlan
from repro.core.push_sum import (PushSumState, collapse_rounds, exponential_schedule,
                                 mix_collapsed, mix_rounds, push_sum_round)
from repro.kernels.hinge_subgrad import ops as hinge_ops
from repro.kernels.hinge_subgrad import ref as hinge_ref
from repro.telemetry import registry as tmr
from repro.telemetry import trace as tmtr
from repro.telemetry import train as tmt

__all__ = [
    "GadgetConfig",
    "GadgetResult",
    "NonFiniteWeightsError",
    "SegmentResult",
    "SnapshotRing",
    "TrainState",
    "gadget_train",
    "gadget_train_stream",
    "gadget_train_reference",
    "make_gadget_mesh_step",
    "transfer_stats",
    "reset_transfer_stats",
]


class NonFiniteWeightsError(FloatingPointError):
    """Typed divergence failure: the consensus weight plane went non-finite.

    Raised by ``gadget_train`` / ``gadget_train_stream`` when the on-device
    guard (checked at the ε-check / segment-boundary cadence) finds NaN/Inf
    in the consensus weights — bad input features, a zero/negative λ, or
    fault-starved Push-Sum mass can all produce it — and by
    ``TrainPublisher`` when asked to publish such a plane. ``iteration`` is
    the last completed global iteration when the guard fired; ``context``
    says which stage refused (``"training"`` or ``"publish"``). Each raise
    increments the ``train.nonfinite`` counter on the default registry, so
    a supervisor can alert on divergence without parsing tracebacks.
    """

    def __init__(self, iteration: int, context: str = "training"):
        super().__init__(
            f"non-finite consensus weight plane at iteration {iteration} "
            f"({context}) — training diverged; refusing to treat NaN/Inf "
            f"weights as a servable model")
        self.iteration = int(iteration)
        self.context = context


class GadgetConfig(NamedTuple):
    """Hyperparameters + execution knobs for one GADGET training run.

    The paper's parameters (λ, minibatch size, Push-Sum rounds R, topology,
    the two projection steps, the anytime ε) ride alongside execution
    switches (`use_kernels`, `fused`, `sparse_schedule`) that change *how*
    the same trajectory is computed, never *what* it computes — every path
    is bit- or 1e-5-level parity-checked against the host-loop reference.
    A config is hashable (NamedTuple) and is part of the jit cache key, so
    reusing one across `gadget_train` / `gadget_train_stream` calls reuses
    compiled executables."""

    lam: float = 1e-4            # λ — SVM regularization / learning parameter
    batch_size: int = 1          # local examples per sub-gradient estimate
    gossip_rounds: int = 4       # Push-Sum rounds per iteration (R)
    topology: str = "exponential"
    project_before_gossip: bool = True   # paper step (f)
    project_after_gossip: bool = True    # paper step (h)
    epsilon: float = 1e-3        # anytime stopping tolerance (paper: 0.001)
    check_every: int = 100       # ε-check / trace cadence (on device)
    max_iters: int = 5000
    seed: int = 0
    # None → Pallas half-step kernels wherever they compile natively (TPU),
    # pure-jnp where they would only interpret (CPU). True forces the kernel
    # path (interpret-mode off-TPU — what CI's device-path tests exercise).
    use_kernels: bool | None = None
    # Fused per-iteration path (default): one fleet_half_step launch for all m
    # nodes + one collapsed mix-and-renormalize matmul. False keeps the PR 1
    # path (2 vmapped kernels per node + R scanned matmuls) for A/B benches.
    fused: bool = True
    # How the sparse half-step kernels walk w's d-blocks: "sweep" visits every
    # block (PR 3 one-hot grid), "prefetch" visits only the blocks the
    # minibatch touches (scalar-prefetch schedule), "auto" picks prefetch
    # exactly when the data-derived block bound makes it cheaper in w-lanes.
    # Ignored on the dense path and on the jnp (use_kernels=False) path.
    sparse_schedule: str = "auto"
    # Fault injection (repro.core.faults.FaultPlan): per-round link/message
    # drops + dead nodes, generated on device inside the jitted step. None
    # (default) is the perfect-network path — bit-identical to pre-fault
    # builds. With faults, deterministic topologies upload the per-round
    # matrix cycle instead of the precomputed product cycle and fold the
    # faulty rounds on device per iteration (the fused path keeps its
    # one-matmul mix). Note the plan — including its fault seed — is baked
    # into the compiled step (unlike cfg.seed).
    faults: FaultPlan | None = None


class SnapshotRing(NamedTuple):
    """Anytime-export ring: the last ``slots`` consensus snapshots taken every
    ``every`` iterations *inside* the jitted training loop, plus the final
    iterate. Raw device-layout buffers — ``repro.serve.snapshot`` decodes them
    into ordered :class:`~repro.serve.snapshot.Snapshot` records; snapshot j
    (1-based, at iteration j·every) lives in slot ``(j - 1) % slots`` and
    ``count`` is the total number taken (> slots ⇒ the ring wrapped and only
    the latest ``slots`` survive)."""

    every: int
    W: np.ndarray             # (slots, d) consensus weights per snapshot
    iterations: np.ndarray    # (slots,) int32 iteration index (0 = never used)
    objectives: np.ndarray    # (slots,) primal objective of each snapshot
    count: int                # snapshots taken in total (may exceed slots)
    final_w: np.ndarray       # (d,) consensus at termination
    final_iteration: int
    final_objective: float

    @property
    def slots(self) -> int:
        return self.W.shape[0]


class GadgetResult(NamedTuple):
    W: jax.Array            # (m, d) final per-node weights
    w_consensus: jax.Array  # (d,) data-weighted network average
    iters: int
    epsilon: float          # max_i ‖Δŵ_i‖ at termination
    objective_trace: np.ndarray  # (n_checks,) primal objective of consensus w
    time_trace: np.ndarray       # iteration index per check
    eps_trace: np.ndarray        # (n_checks,) max_i ‖Δŵ_i‖ per check
    W_avg: jax.Array | None = None  # (m, d) per-node iterate averages w̄_i
    # (Pegasos' Theorem-2-style guarantee bounds the averaged iterate, not the
    # last one — same reason pegasos_train exposes w_avg)
    snapshots: SnapshotRing | None = None  # anytime export (snapshot_every=K)
    # (n_checks,) minimum per-iteration Push-Sum mass retention over each
    # ε-check chunk: sum of post-mix mass weights / sum of initial mass
    # (Σ n_i). Exactly 1.0 (to float-sum tolerance) on the perfect network and
    # under FaultPlan(drop="link"); < 1 measures the leakage of drop="message".
    mass_trace: np.ndarray | None = None
    # Decoded on-device training trace ring (telemetry=TrainTelemetry(...)):
    # per-record consensus disagreement, windowed Push-Sum mass extrema,
    # objective, fault-drop counts. None when telemetry is off — and the
    # telemetry=None trajectory is bit-identical to pre-telemetry builds.
    telemetry: tmt.TrainTrace | None = None


class SegmentResult(NamedTuple):
    """One :func:`gadget_train_stream` segment — everything a live publisher
    needs to export a servable model mid-training. ``W`` stays on device
    (per-node (m, d) weights, useful for parity checks / resuming);
    ``w_consensus`` is the host-side (d,) f32 data-weighted average —
    exactly what :class:`~repro.serve.snapshot.Snapshot` wraps."""

    iteration: int          # global iteration index reached (1-based count)
    W: jax.Array            # (m, d) per-node weights after the segment
    w_consensus: np.ndarray  # (d,) f32 consensus at the segment boundary
    objective: float        # primal objective of w_consensus
    epsilon: float          # max_i ‖Δŵ_i‖ across the segment
    done: bool              # ε-converged or cfg.max_iters reached
    # (m, d) running iterate sum — with ``iteration`` and ``W`` this is the
    # full resumable TrainState at the boundary (crash-resume support)
    W_sum: jax.Array | None = None
    # min per-iteration Push-Sum mass retention across the segment (1.0 on a
    # perfect network / link-mode faults; < 1 measures message-mode leakage)
    mass: float = float("nan")
    # Per-segment telemetry (gadget_train_stream(..., telemetry=...)):
    # boundary disagreement/objective + active-iteration mass extrema and
    # fault-drop counts. None when telemetry is off.
    telemetry: tmt.SegmentTelemetry | None = None
    # Root trace context of this segment's version-lineage trace
    # (gadget_train_stream(..., trace=True)): the publisher derives its
    # publish span from it and embeds it in the checkpoint manifest, so the
    # swap/first-serve spans downstream join the same causal chain. None
    # when tracing is off.
    trace: tmtr.TraceContext | None = None


class TrainState(NamedTuple):
    """Resumable trainer state at a segment boundary: ``iteration`` completed
    global iterations plus the (m, d) per-node weights and running iterate
    sum. Feed to ``gadget_train_stream(..., resume=...)`` to continue a run —
    because every PRNG draw keys on the *global* iteration counter, the
    resumed trajectory is bit-identical to the uninterrupted one.
    ``repro.serve.snapshot.to_checkpoint(..., train_state=...)`` persists it
    alongside the servable weights and ``train_state_from_checkpoint``
    restores it."""

    iteration: int
    W: jax.Array            # (m, d) per-node weights
    W_sum: jax.Array        # (m, d) running sum of iterates


# Host↔device traffic instrumentation, read by benchmarks/gossip_device_bench.py:
# `matrix_uploads` counts host→device transfers of mixing matrices, `host_syncs`
# counts blocking boundaries (the host waits for the device), not transfers:
# the device→host reads made at a stream's segment boundary are counted by the
# registry counter `train.host_readbacks`.
transfer_stats = {"matrix_uploads": 0, "host_syncs": 0}


def reset_transfer_stats() -> None:
    transfer_stats["matrix_uploads"] = 0
    transfer_stats["host_syncs"] = 0


def _partition_counts(y_parts: jax.Array, n_counts=None) -> jax.Array:
    """Per-node valid-row counts as f32: uniform n_i unless the caller passes
    explicit ``n_counts`` (non-uniform partitions, padded to a common n_i)."""
    m, n_i = y_parts.shape
    if n_counts is None:
        return jnp.full((m,), float(n_i), jnp.float32)
    counts = np.asarray(n_counts, np.float32)
    if counts.shape != (m,):
        raise ValueError(f"n_counts must have shape ({m},), got {counts.shape}")
    if np.any(counts < 1) or np.any(counts > n_i):
        raise ValueError(f"n_counts must lie in [1, {n_i}]")
    return jnp.asarray(counts)


def _valid_row_mask(m: int, n_i: int, n_counts: jax.Array) -> jax.Array:
    """Flat (m*n_i,) mask of real rows — the padded-partition counterpart of
    ops.padded_row_mask, shared by the device loop and the reference oracle
    so their objective traces mask identically."""
    return (jnp.arange(n_i)[None, :]
            < n_counts.astype(jnp.int32)[:, None]).reshape(m * n_i)


def _unpack_partitions(X_parts):
    """Normalize the data argument: returns ``(X, m, n_i, d, dtype)`` where X
    is the dense (m, n_i, d) device array, or the ``(cols, vals)`` tuple of
    stacked padded-ELL planes when the caller passed
    ``repro.sparse.EllPartitions`` (duck-typed on ``.cols``/``.vals``/``.d``)."""
    if hasattr(X_parts, "cols") and hasattr(X_parts, "vals"):
        cols = jnp.asarray(X_parts.cols, jnp.int32)
        vals = jnp.asarray(X_parts.vals, jnp.float32)
        m, n_i, _ = cols.shape
        return (cols, vals), m, n_i, int(X_parts.d), vals.dtype
    X = jnp.asarray(X_parts)
    m, n_i, d = X.shape
    return X, m, n_i, d, X.dtype


def _sparse_block_bound(cfg: GadgetConfig, X_parts, X) -> int | None:
    """Static n_blocks_max cap for the prefetch kernel schedule, derived on
    host from the partition planes before tracing (the traced loop needs a
    concrete grid bound). None for dense data / the jnp path / the sweep
    schedule, where no bound is consumed."""
    if not isinstance(X, tuple) or not cfg.use_kernels or cfg.sparse_schedule == "sweep":
        return None
    if hasattr(X_parts, "block_bound"):  # EllPartitions caches row counts
        return X_parts.block_bound(cfg.batch_size)
    from repro.sparse.formats import minibatch_block_bound
    cols, vals = np.asarray(X_parts.cols), np.asarray(X_parts.vals)
    return minibatch_block_bound(
        cols.reshape(cols.shape[0], -1, cols.shape[-1]), vals,
        cfg.batch_size, d=int(X_parts.d))


def _resolve_kernels(cfg: GadgetConfig) -> GadgetConfig:
    """Pin cfg.use_kernels to a concrete bool (it keys the jit cache)."""
    if cfg.use_kernels is None:
        return cfg._replace(use_kernels=not hinge_ops.default_interpret())
    return cfg


def _local_half_step(w, X_i, y_i, ids, lam, t, project, use_kernels):
    Xb, yb = X_i[ids], y_i[ids]
    if use_kernels:
        return hinge_ops.local_half_step(w, Xb, yb, lam=lam, t=t, project=project)
    alpha = 1.0 / (lam * t)
    L_hat = -obj.hinge_subgradient(w, Xb, yb)
    w_half = (1.0 - lam * alpha) * w + alpha * L_hat
    return obj.project_ball(w_half, lam) if project else w_half


# ---------------------------------------------------------------------------
# Shared PRNG / mixing-matrix derivations — the device loop and the host-loop
# reference use these verbatim so their trajectories are comparable.
# ---------------------------------------------------------------------------


def _stream_keys(seed: int):
    data_key, mix_key = jax.random.split(jax.random.PRNGKey(seed))
    return data_key, mix_key


def _batch_ids(data_key: jax.Array, t: jax.Array, n_counts: jax.Array, batch_size: int):
    """Per-node minibatch row ids, sampled from each node's first n_counts[i]
    (valid) rows — identical to the old uniform draw when counts are uniform."""
    keys = jax.random.split(jax.random.fold_in(data_key, t), n_counts.shape[0])
    bounds = n_counts.astype(jnp.int32)
    return jax.vmap(
        lambda k, c: jax.random.randint(k, (batch_size,), 0, c)
    )(keys, bounds)


def _iter_mixing(mix_key: jax.Array, B_stack: jax.Array | None, t: jax.Array,
                 m: int, R: int, topology: str, fused: bool,
                 faults: FaultPlan | None = None,
                 count_drops: bool = False, drops_node: bool = False):
    """Mixing for iteration t (1-based), fully on device: the (R, m, m)
    per-round stack, or — when ``fused`` — the single collapsed (m, m) product
    ``P_t = (B_1 ⋯ B_R)^T``. Fault-free deterministic topologies index the
    precomputed product cycle (``B_stack`` then IS
    topology.build_product_stack); the random protocol draws the same R
    matrices either way (same PRNG stream as the sequential path) and folds
    them on device. With ``faults`` the per-round matrices (``B_stack`` is
    then the *matrix* cycle) pass through :func:`repro.core.faults.
    faulty_rounds` before the fold — fault injection composes with the fused
    one-matmul mix by collapsing the faulty rounds on device per iteration,
    exactly the pattern the random topology already uses.

    ``count_drops`` (telemetry) additionally returns the iteration's faulted
    message count (:func:`repro.core.faults.count_drops` on the clean rounds
    — int32 0 when fault-free) as a second output; ``drops_node`` switches
    that output to the (m,) per-sender vector
    (:func:`repro.core.faults.count_drops_node`, rows summing to the
    scalar). The default single-output form is byte-identical to
    pre-telemetry builds."""
    def zero_drops():
        return (jnp.zeros((m,), jnp.int32) if drops_node else jnp.int32(0))

    with jax.named_scope("gadget.push_sum_mix"):
        if topology == "random":
            kt = jax.random.fold_in(mix_key, t)
            Bs = jax.vmap(
                lambda r: topo.random_neighbor_matrix_device(jax.random.fold_in(kt, r), m)
            )(jnp.arange(R))
        else:
            T = B_stack.shape[0]
            if fused and faults is None:
                P = B_stack[(t - 1) % T]
                return (P, zero_drops()) if count_drops else P
            idx = ((t - 1) * R + jnp.arange(R)) % T
            Bs = B_stack[idx]
        drops = None
        if faults is not None:
            if count_drops:
                drops = (flt.count_drops_node(Bs, faults, t) if drops_node
                         else flt.count_drops(Bs, faults, t))
            Bs = flt.faulty_rounds(Bs, faults, t)
        mix = collapse_rounds(Bs) if fused else Bs
        if count_drops:
            return mix, (zero_drops() if drops is None else drops)
        return mix


# ---------------------------------------------------------------------------
# Device-resident training loop (tentpole)
# ---------------------------------------------------------------------------


def _gossip_step(cfg: GadgetConfig, m: int,
                 X: jax.Array, y: jax.Array, n_counts: jax.Array,
                 data_key: jax.Array, W: jax.Array, W_sum: jax.Array,
                 t: jax.Array, Bs: jax.Array, sparse_block_bound: int | None = None,
                 node_mass: bool = False):
    """Steps (a)-(h) for all m nodes at iteration t. ``Bs`` is the (R, m, m)
    per-round stack (sequential path) or the collapsed (m, m) product P_t
    (``cfg.fused``). ``X`` is the dense (m, n_i, d) array or the (cols, vals)
    tuple of stacked ELL planes; ``sparse_block_bound`` is the static
    n_blocks_max cap for the prefetch kernel schedule (host-derived from the
    partition planes — formats.minibatch_block_bound). The single shared step
    body — the device loop and the host-loop reference differ only in
    orchestration (where Bs comes from, where the ε-check runs).

    Returns ``(W_new, W_sum + W_new, mass)`` where ``mass`` is this
    iteration's Push-Sum mass retention Σ post-mix weights / Σ n_i — exactly
    1.0 (to float-sum tolerance) on a perfect network or under link-mode
    faults, < 1 under message-mode leakage. With ``cfg.faults`` dead nodes
    are frozen bit-exactly: their half-step is suppressed (W_half ← W) and
    their mixing row is e_d, so W_new equals W on dead rows (project_ball is
    exact identity on an already-projected weight).

    ``node_mass`` (per-node telemetry) appends the (m,) per-node Push-Sum
    mass ratio ``wts_i / n_i`` — the node-level decomposition of ``mass``
    (its n-weighted mean is the scalar) — as a fourth output; the default
    three-output form traces the identical program."""
    with jax.named_scope("gadget.half_step"):
        W_half = _fleet_half_step(cfg, X, y, n_counts, data_key, W, t,
                                  sparse_block_bound)
    # Push-Sum: values n_i·w̃_i with mass weights n_i ⇒ weighted mean; R
    # rounds collapsed into one fused mix-and-renormalize matmul when fused.
    with jax.named_scope("gadget.push_sum_mix"):
        mix = mix_collapsed if cfg.fused else mix_rounds
        vals, wts = mix(W_half * n_counts[:, None], n_counts, Bs)
        mass = jnp.sum(wts) / jnp.sum(n_counts)
        W_new = vals / wts[:, None]
    if cfg.project_after_gossip:
        with jax.named_scope("gadget.project"):
            W_new = jax.vmap(lambda w: obj.project_ball(w, cfg.lam))(W_new)
    if cfg.faults is not None and cfg.faults.dead_nodes:
        # crashed nodes neither train nor receive: their mixing row is e_d
        # (nothing reaches the others), and the bit-exact freeze of their own
        # row happens here, after the mix's renormalizing divide
        with jax.named_scope("gadget.push_sum_mix"):
            W_new = jnp.where(flt.dead_mask(cfg.faults, m)[:, None], W, W_new)
    with jax.named_scope("gadget.average"):
        W_sum = W_sum + W_new
    if node_mass:
        return W_new, W_sum, mass, wts / n_counts
    return W_new, W_sum, mass


def _fleet_half_step(cfg: GadgetConfig, X, y: jax.Array, n_counts: jax.Array,
                     data_key: jax.Array, W: jax.Array, t: jax.Array,
                     sparse_block_bound: int | None):
    """Steps (a)-(f) for all m nodes: each node's minibatch draw and gather,
    then the Pallas or jnp half-step, whose ball projection (step (f)) runs
    under the ``gadget.project`` scope."""
    tf = t.astype(jnp.float32)
    ids = _batch_ids(data_key, t, n_counts, cfg.batch_size)

    def gather(a):
        return jax.vmap(lambda ai, ii: ai[ii])(a, ids)

    if isinstance(X, tuple):
        # sparse: per-node ELL minibatch planes; the half-step is fleet-wide
        # either way (the sparse kernels take the whole node axis), so fused
        # vs unfused only selects the mixing path below.
        Cb, Vb, yb = gather(X[0]), gather(X[1]), gather(y)
        if cfg.use_kernels:
            W_half = hinge_ops.ell_fleet_half_step(W, Cb, Vb, yb, lam=cfg.lam,
                                                   t=tf,
                                                   project=cfg.project_before_gossip,
                                                   schedule=cfg.sparse_schedule,
                                                   n_blocks_max=sparse_block_bound)
        else:
            W_half = hinge_ref.ell_fleet_half_step_ref(W, Cb, Vb, yb, cfg.lam, tf,
                                                       project=cfg.project_before_gossip)
    elif cfg.fused:
        # one gather, then steps (a)-(e) for the whole fleet in one launch
        Xb, yb = gather(X), gather(y)
        if cfg.use_kernels:
            W_half = hinge_ops.fleet_half_step(W, Xb, yb, lam=cfg.lam, t=tf,
                                               project=cfg.project_before_gossip)
        else:
            W_half = hinge_ref.fleet_half_step_ref(W, Xb, yb, cfg.lam, tf,
                                                   project=cfg.project_before_gossip)
    else:
        W_half = jax.vmap(
            lambda w, Xi, yi, ii: _local_half_step(w, Xi, yi, ii, cfg.lam, tf,
                                                   cfg.project_before_gossip, cfg.use_kernels)
        )(W, X, y, ids)
    return W_half


def _one_iteration(cfg: GadgetConfig, m: int,
                   X: jax.Array, y: jax.Array, n_counts: jax.Array,
                   data_key: jax.Array, mix_key: jax.Array, B_stack: jax.Array | None,
                   W: jax.Array, W_sum: jax.Array, t: jax.Array,
                   sparse_block_bound: int | None = None,
                   count_drops: bool = False, node_stats: bool = False):
    """One fully device-resident iteration: derive this iteration's mixing
    (stack slice, product-cycle slice, or in-step draw — faults applied on
    device when cfg.faults), then the shared step. Returns
    ``(W, W_sum, mass)`` — or ``(W, W_sum, mass, drops)`` with the
    iteration's faulted-message count when ``count_drops`` (telemetry).

    ``node_stats`` (per-node telemetry; supersedes ``count_drops``) returns
    ``(W, W_sum, mass, ndrops, nmass)`` where ``ndrops`` is the (m,) int32
    per-sender faulted-message count (zeros when fault-free) and ``nmass``
    the (m,) per-node Push-Sum mass ratio."""
    if node_stats:
        if cfg.faults is not None:
            Bs, ndrops = _iter_mixing(mix_key, B_stack, t, m, cfg.gossip_rounds,
                                      cfg.topology, cfg.fused, cfg.faults,
                                      count_drops=True, drops_node=True)
        else:
            Bs = _iter_mixing(mix_key, B_stack, t, m, cfg.gossip_rounds,
                              cfg.topology, cfg.fused, None)
            ndrops = jnp.zeros((m,), jnp.int32)
        W, W_sum, mass, nmass = _gossip_step(cfg, m, X, y, n_counts, data_key,
                                             W, W_sum, t, Bs,
                                             sparse_block_bound,
                                             node_mass=True)
        return W, W_sum, mass, ndrops, nmass
    if count_drops:
        Bs, drops = _iter_mixing(mix_key, B_stack, t, m, cfg.gossip_rounds,
                                 cfg.topology, cfg.fused, cfg.faults,
                                 count_drops=True)
        W, W_sum, mass = _gossip_step(cfg, m, X, y, n_counts, data_key, W,
                                      W_sum, t, Bs, sparse_block_bound)
        return W, W_sum, mass, drops
    Bs = _iter_mixing(mix_key, B_stack, t, m, cfg.gossip_rounds, cfg.topology,
                      cfg.fused, cfg.faults)
    return _gossip_step(cfg, m, X, y, n_counts, data_key, W, W_sum, t, Bs,
                        sparse_block_bound)


def _trace_closures(cfg: GadgetConfig, X, y: jax.Array, n_counts: jax.Array,
                    m: int, n_i: int, d: int):
    """The two traced reductions every loop variant shares: ``objective_of(w)``
    (masked full-data primal, dense or ELL gather-dot) and ``consensus_of(W)``
    (data-weighted network average). Built identically by the while-loop
    trainer, the segment trainer and the host reference so their traces agree
    bit-for-bit. With kernels on, ELL planes go whole to the
    ``ell_objective`` kernel; otherwise ``jnp.take`` over flat views, the
    kernel's oracle."""
    total_n = jnp.sum(n_counts)

    def consensus_of(W):
        with jax.named_scope("gadget.consensus"):
            return jnp.sum(W * n_counts[:, None], axis=0) / total_n

    if isinstance(X, tuple) and cfg.use_kernels:
        def objective_of(w):
            with jax.named_scope("gadget.objective"):
                return hinge_ops.ell_objective(w, *X, y, n_counts, lam=cfg.lam,
                                               total=total_n)

        return objective_of, consensus_of

    with jax.named_scope("gadget.objective"):  # the full-data pass's flat views
        y_flat = y.reshape(m * n_i)
        valid_flat = _valid_row_mask(m, n_i, n_counts)
        if isinstance(X, tuple):  # ELL planes: the pass is a gather-dot
            flat = (X[0].reshape(m * n_i, -1), X[1].reshape(m * n_i, -1))
            primal = obj.primal_objective_masked_ell
        else:
            flat = (X.reshape(m * n_i, d),)
            primal = obj.primal_objective_masked

    def objective_of(w):
        with jax.named_scope("gadget.objective"):
            return primal(w, *flat, y_flat, cfg.lam, valid_flat, total_n)

    return objective_of, consensus_of


def _eps_check(W: jax.Array, W_prev: jax.Array) -> jax.Array:
    """The stopping rule's ε = max_i ‖W_i − W_prev_i‖ over one check window."""
    with jax.named_scope("gadget.eps_check"):
        return jnp.max(jnp.linalg.norm(W - W_prev, axis=1))


def _cache_cfg(cfg: GadgetConfig) -> GadgetConfig:
    """Key for the jit-factory caches: the traced program never reads
    cfg.seed (PRNG keys are runtime arguments), so multi-seed sweeps must
    share one compiled executable."""
    return cfg._replace(seed=0)


@functools.lru_cache(maxsize=32)
def _make_device_train(cfg: GadgetConfig, m: int, n_i: int, d: int,
                       n_chunks: int, chunk: int,
                       sparse_block_bound: int | None = None,
                       snap_every: int = 0, snap_slots: int = 0,
                       tele_every: int = 0, tele_slots: int = 0,
                       tele_nodes: bool = False):
    """Jitted whole-training function: while_loop over ε-check chunks, scan
    over iterations inside each chunk, donated weight buffers, on-device
    objective/ε traces. Returns arrays only — the caller syncs once.

    ``snap_every`` > 0 additionally threads the anytime-export ring through
    the loop: every K-th iteration writes (consensus w, iteration, objective)
    into slot ``count % snap_slots`` under a ``lax.cond`` — non-snapshot
    iterations pay nothing, and the whole ring stays on device until the
    single post-termination sync.

    ``tele_every`` > 0 threads the telemetry trace ring the same way: every
    K-th active iteration records (iteration, consensus disagreement,
    windowed mass min/max, objective, windowed fault-drop count) into slot
    ``count % tele_slots``; the window accumulators reset at each record.
    With ``tele_every == 0`` the telemetry carry is the empty tuple — no
    pytree leaves, so the traced program (and the trajectory) is
    bit-identical to the telemetry-free build.

    ``tele_nodes`` appends per-node ring leaves to the telemetry carry:
    ``(tele_slots, m)`` rings of per-node disagreement-to-consensus, per-node
    Push-Sum mass ratio at the record iteration, and windowed per-node
    fault-drop counts (plus the (m,) drop window accumulator). The scalar
    rings are unchanged — the scalar disagreement is the row-max of the
    per-node record, the scalar drop window the row-sum — and
    ``tele_nodes=False`` traces the exact per-node-free program."""
    # drop counting re-draws the fault stream per iteration — only pay for
    # it when there is both a telemetry ring and a fault plan to observe
    tele_drops = bool(tele_every) and cfg.faults is not None

    def train(X, y, B_stack, data_key, mix_key, n_counts, W0, W_sum0):
        # padded rows of non-uniform partitions are masked out of the trace
        objective_of, consensus_of = _trace_closures(cfg, X, y, n_counts,
                                                     m, n_i, d)

        def disagreement_of(W_now, w_cons):
            return jnp.max(jnp.linalg.norm(W_now - w_cons[None, :], axis=1))

        def step(carry, _):
            W, W_sum, t, snaps, tele = carry
            active = t <= cfg.max_iters
            # inactive tail iterations report full mass so the per-chunk min
            # below only reflects iterations that actually gossiped
            if tele_nodes:
                W, W_sum, mass, ndrops, nmass = jax.lax.cond(
                    active,
                    lambda a: _one_iteration(cfg, m, X, y, n_counts,
                                             data_key, mix_key, B_stack, *a,
                                             sparse_block_bound=sparse_block_bound,
                                             node_stats=True),
                    lambda a: (a[0], a[1], jnp.float32(1.0),
                               jnp.zeros((m,), jnp.int32),
                               jnp.ones((m,), jnp.float32)),
                    (W, W_sum, t),
                )
                drops = jnp.sum(ndrops)
            elif tele_drops:
                W, W_sum, mass, drops = jax.lax.cond(
                    active,
                    lambda a: _one_iteration(cfg, m, X, y, n_counts,
                                             data_key, mix_key, B_stack, *a,
                                             sparse_block_bound=sparse_block_bound,
                                             count_drops=True),
                    lambda a: (a[0], a[1], jnp.float32(1.0), jnp.int32(0)),
                    (W, W_sum, t),
                )
            else:
                W, W_sum, mass = jax.lax.cond(
                    active,
                    lambda a: _one_iteration(cfg, m, X, y, n_counts,
                                             data_key, mix_key, B_stack, *a,
                                             sparse_block_bound=sparse_block_bound),
                    lambda a: (a[0], a[1], jnp.float32(1.0)),
                    (W, W_sum, t),
                )
                drops = jnp.int32(0)
            if snap_every:
                def do_snap(op):
                    (sw, si, so, sc), W_now = op
                    w_cons = consensus_of(W_now)
                    slot = sc % snap_slots
                    return (sw.at[slot].set(w_cons), si.at[slot].set(t),
                            so.at[slot].set(objective_of(w_cons)), sc + 1)

                snaps = jax.lax.cond(active & (t % snap_every == 0),
                                     do_snap, lambda op: op[0], (snaps, W))
            if tele_every and tele_nodes:
                (ti, tdis, tmn, tmx, tob, tdr, tc, wmin, wmax, wdr,
                 ndisr, nmassr, ndropr, wndr) = tele
                # window accumulators only see iterations that gossiped
                wmin = jnp.where(active, jnp.minimum(wmin, mass), wmin)
                wmax = jnp.where(active, jnp.maximum(wmax, mass), wmax)
                wdr = wdr + jnp.where(active, drops, 0)
                wndr = wndr + jnp.where(active, ndrops, 0)

                def do_rec_nodes(op):
                    ((ti, tdis, tmn, tmx, tob, tdr, tc, ndisr, nmassr,
                      ndropr), (W_now, wmin, wmax, wdr, nmass_now, wndr)) = op
                    w_cons = consensus_of(W_now)
                    node_dis = jnp.linalg.norm(W_now - w_cons[None, :], axis=1)
                    slot = tc % tele_slots
                    ring = (ti.at[slot].set(t),
                            # scalar ring = row-max of the per-node record
                            tdis.at[slot].set(jnp.max(node_dis)),
                            tmn.at[slot].set(wmin), tmx.at[slot].set(wmax),
                            tob.at[slot].set(objective_of(w_cons)),
                            tdr.at[slot].set(wdr), tc + 1,
                            ndisr.at[slot].set(node_dis),
                            nmassr.at[slot].set(nmass_now),
                            ndropr.at[slot].set(wndr))
                    # record consumed the window: reset the accumulators
                    return ring, (jnp.float32(jnp.inf), jnp.float32(-jnp.inf),
                                  jnp.int32(0), jnp.zeros((m,), jnp.int32))

                ring, (wmin, wmax, wdr, wndr) = jax.lax.cond(
                    active & (t % tele_every == 0), do_rec_nodes,
                    lambda op: (op[0], (op[1][1], op[1][2], op[1][3],
                                        op[1][5])),
                    ((ti, tdis, tmn, tmx, tob, tdr, tc, ndisr, nmassr,
                      ndropr), (W, wmin, wmax, wdr, nmass, wndr)))
                (ti, tdis, tmn, tmx, tob, tdr, tc,
                 ndisr, nmassr, ndropr) = ring
                tele = (ti, tdis, tmn, tmx, tob, tdr, tc, wmin, wmax, wdr,
                        ndisr, nmassr, ndropr, wndr)
            elif tele_every:
                ti, tdis, tmn, tmx, tob, tdr, tc, wmin, wmax, wdr = tele
                # window accumulators only see iterations that gossiped
                wmin = jnp.where(active, jnp.minimum(wmin, mass), wmin)
                wmax = jnp.where(active, jnp.maximum(wmax, mass), wmax)
                wdr = wdr + jnp.where(active, drops, 0)

                def do_rec(op):
                    (ti, tdis, tmn, tmx, tob, tdr, tc), (W_now, wmin, wmax, wdr) = op
                    w_cons = consensus_of(W_now)
                    slot = tc % tele_slots
                    ring = (ti.at[slot].set(t),
                            tdis.at[slot].set(disagreement_of(W_now, w_cons)),
                            tmn.at[slot].set(wmin), tmx.at[slot].set(wmax),
                            tob.at[slot].set(objective_of(w_cons)),
                            tdr.at[slot].set(wdr), tc + 1)
                    # record consumed the window: reset the accumulators
                    return ring, (jnp.float32(jnp.inf), jnp.float32(-jnp.inf),
                                  jnp.int32(0))

                ring, (wmin, wmax, wdr) = jax.lax.cond(
                    active & (t % tele_every == 0), do_rec,
                    lambda op: (op[0], op[1][1:]),
                    ((ti, tdis, tmn, tmx, tob, tdr, tc), (W, wmin, wmax, wdr)))
                tele = ring + (wmin, wmax, wdr)
            return (W, W_sum, jnp.where(active, t + 1, t), snaps, tele), mass

        def chunk_body(carry):
            (W, W_sum, t, snaps, tele, ci, _, obj_tr, it_tr, eps_tr, mass_tr,
             bad) = carry
            W_prev = W
            (W, W_sum, t, snaps, tele), masses = jax.lax.scan(
                step, (W, W_sum, t, snaps, tele), None, length=chunk)
            eps = _eps_check(W, W_prev)
            w_cons = consensus_of(W)
            obj_tr = obj_tr.at[ci].set(objective_of(w_cons))
            it_tr = it_tr.at[ci].set(t - 1)
            eps_tr = eps_tr.at[ci].set(eps)
            mass_tr = mass_tr.at[ci].set(jnp.min(masses))
            # Non-finite guard at the ε-check cadence: one lax.cond-gated
            # isfinite reduction on the consensus already computed for the
            # trace (a sum is NaN/±Inf iff some element is non-finite under
            # the ball-projected magnitudes). Records the first bad
            # iteration; the while cond stops the run there, and the host
            # raises a typed NonFiniteWeightsError instead of returning —
            # or publishing — a NaN plane. Pure observation: a finite
            # trajectory is bit-identical with or without the guard firing.
            bad = jax.lax.cond(
                bad == 0,
                lambda: jnp.where(jnp.isfinite(jnp.sum(w_cons)),
                                  jnp.int32(0), t - 1),
                lambda: bad)
            return (W, W_sum, t, snaps, tele, ci + 1, eps, obj_tr, it_tr,
                    eps_tr, mass_tr, bad)

        def cond(carry):
            _, _, t, _, _, ci, eps, _, _, _, _, bad = carry
            return ((ci < n_chunks) & (eps >= cfg.epsilon)
                    & (t <= cfg.max_iters) & (bad == 0))

        snaps0 = (jnp.zeros((snap_slots, d), jnp.float32),
                  jnp.zeros((snap_slots,), jnp.int32),
                  jnp.full((snap_slots,), jnp.nan, jnp.float32),
                  jnp.int32(0))
        if tele_every:
            tele0 = (jnp.zeros((tele_slots,), jnp.int32),
                     jnp.full((tele_slots,), jnp.nan, jnp.float32),
                     jnp.full((tele_slots,), jnp.nan, jnp.float32),
                     jnp.full((tele_slots,), jnp.nan, jnp.float32),
                     jnp.full((tele_slots,), jnp.nan, jnp.float32),
                     jnp.zeros((tele_slots,), jnp.int32),
                     jnp.int32(0),
                     jnp.float32(jnp.inf), jnp.float32(-jnp.inf), jnp.int32(0))
            if tele_nodes:
                tele0 = tele0 + (
                    jnp.full((tele_slots, m), jnp.nan, jnp.float32),
                    jnp.full((tele_slots, m), jnp.nan, jnp.float32),
                    jnp.zeros((tele_slots, m), jnp.int32),
                    jnp.zeros((m,), jnp.int32))
        else:
            tele0 = ()
        init = (W0, W_sum0, jnp.int32(1), snaps0, tele0, jnp.int32(0),
                jnp.float32(jnp.inf),
                jnp.full((n_chunks,), jnp.nan, jnp.float32),
                jnp.zeros((n_chunks,), jnp.int32),
                jnp.full((n_chunks,), jnp.nan, jnp.float32),
                jnp.full((n_chunks,), jnp.nan, jnp.float32),
                jnp.int32(0))
        (W, W_sum, t, snaps, tele, ci, eps, obj_tr, it_tr, eps_tr, mass_tr,
         bad) = jax.lax.while_loop(cond, chunk_body, init)
        w_cons = consensus_of(W)
        final_obj = objective_of(w_cons) if snap_every else jnp.float32(jnp.nan)
        # ONE extra reduction at the already-synced boundary — the telemetry
        # ring adds no mid-loop host traffic
        tele_out = tele + (disagreement_of(W, w_cons),) if tele_every else ()
        return (W, W_sum, w_cons, t - 1, ci, eps, obj_tr, it_tr, eps_tr,
                mass_tr, snaps, tele_out, final_obj, bad)

    # Buffer donation is a no-op (with a warning) on CPU — only request it
    # where the runtime honors it. W0 / W_sum0 are fresh zeros made by
    # _prepare_device_train, so no caller holds them.
    donate = (6, 7) if jax.default_backend() != "cpu" else ()
    return jax.jit(train, donate_argnums=donate)


def _validate_topology(cfg: GadgetConfig) -> None:
    if cfg.topology not in topo.TOPOLOGIES:
        raise ValueError(f"unknown topology {cfg.topology!r}")


def _resolve_faults(cfg: GadgetConfig, m: int) -> GadgetConfig:
    """Validate + canonicalize cfg.faults against the m-node fleet (sorted
    dead tuple, plain scalars) so equal plans key one compiled executable.
    A fully inert plan (no drops, no dead) is normalized to None — it must
    hit the bit-identical perfect-network path, not a faulty recompile."""
    if cfg.faults is None:
        return cfg
    plan = flt.validate_plan(cfg.faults, m)
    if plan.drop_prob == 0.0 and not plan.dead_nodes:
        return cfg._replace(faults=None)
    return cfg._replace(faults=plan)


# Default anytime-export ring capacity: enough history for serve-side A/B
# (previous vs current snapshot) without holding every iterate.
DEFAULT_SNAPSHOT_SLOTS = 8


def _validate_snapshotting(snapshot_every, snapshot_slots) -> int:
    if snapshot_every is None:
        return 0
    if int(snapshot_every) < 1:
        raise ValueError(f"snapshot_every must be >= 1, got {snapshot_every}")
    if int(snapshot_slots) < 1:
        raise ValueError(f"snapshot_slots must be >= 1, got {snapshot_slots}")
    return int(snapshot_every)


def _prepare_device_train(cfg: GadgetConfig, X_parts: jax.Array, y_parts: jax.Array,
                          n_counts=None, snapshot_every=None,
                          snapshot_slots: int = DEFAULT_SNAPSHOT_SLOTS,
                          telemetry: tmt.TrainTelemetry | None = None):
    """Build the exact (jitted train fn, argument tuple) pair `gadget_train`
    executes: resolved config, one stacked-matrix upload, PRNG streams, fresh
    (donatable) weight buffers. The transfer-guard benchmark calls this too,
    so the device-residency proof certifies the real path, not a replica.
    Requires cfg.max_iters > 0."""
    X, m, n_i, d, dtype = _unpack_partitions(X_parts)
    cfg = _resolve_kernels(cfg)
    cfg = _resolve_faults(cfg, m)
    snap_every = _validate_snapshotting(snapshot_every, snapshot_slots)
    n_counts = _partition_counts(y_parts, n_counts)
    data_key, mix_key = _stream_keys(cfg.seed)
    sparse_block_bound = _sparse_block_bound(cfg, X_parts, X)

    if cfg.topology == "random":
        B_stack = None
    else:
        # fused: upload the per-iteration collapsed-product cycle (R× smaller
        # per iteration consumed) instead of the per-round matrix cycle.
        # Under faults the product can no longer be precomputed on host (each
        # round's matrix mutates per iteration), so the per-round matrix
        # cycle is uploaded and the faulty product is folded on device.
        use_product = cfg.fused and cfg.faults is None
        stack = (topo.build_product_stack(cfg.topology, m, cfg.gossip_rounds)
                 if use_product else topo.build_matrix_stack(cfg.topology, m))
        B_stack = jnp.asarray(stack)
        transfer_stats["matrix_uploads"] += 1  # the only upload, ever

    tele = tmt.validate_telemetry(telemetry)
    chunk = min(cfg.check_every, cfg.max_iters)
    n_chunks = -(-cfg.max_iters // chunk)
    train = _make_device_train(_cache_cfg(cfg), m, n_i, d, n_chunks, chunk,
                               sparse_block_bound, snap_every,
                               int(snapshot_slots) if snap_every else 0,
                               tele.every if tele else 0,
                               tele.slots if tele else 0,
                               tele.per_node if tele else False)
    args = (X, jnp.asarray(y_parts), B_stack, data_key, mix_key,
            n_counts, jnp.zeros((m, d), dtype), jnp.zeros((m, d), dtype))
    return train, args


@functools.lru_cache(maxsize=64)
def _gossip_bytes_per_iter(topology: str, m: int, R: int, d: int) -> int:
    """Analytic gossip payload bytes one iteration moves: R rounds × live
    off-diagonal links per round × (d weight floats + 1 mass float) × 4.
    Deterministic topologies count their matrix cycle's mean off-diagonal
    support; the random protocol pushes to exactly one neighbor per node per
    round. Feeds the ``train.gossip_bytes`` counter."""
    if topology == "random":
        links = float(m)
    else:
        stack = np.asarray(topo.build_matrix_stack(topology, m))
        offdiag = (stack != 0).sum(axis=(1, 2)) - (
            np.diagonal(stack, axis1=1, axis2=2) != 0).sum(axis=1)
        links = float(offdiag.mean())
    return int(round(R * links * (d + 1) * 4))


def _record_train_telemetry(cfg: GadgetConfig, m: int, d: int, X,
                            sparse_block_bound, n_iters: int,
                            registry=None) -> None:
    """Registry accounting for ``n_iters`` finished training iterations.

    The jitted loop cannot count its own kernel launches, so the host mirrors
    what the traced program dispatches per iteration — iteration and
    gossip-byte counters always, kernel launch/bytes/FLOPs series when the
    Pallas path is active — onto the (default) registry. Pure host-side
    bookkeeping: it never touches the traced program or the trajectory."""
    if n_iters <= 0:
        return
    reg = tmr.default_registry() if registry is None else registry
    reg.counter("train.iterations").inc(n_iters)
    reg.counter("train.gossip_bytes").inc(
        n_iters * _gossip_bytes_per_iter(cfg.topology, m, cfg.gossip_rounds, d))
    if not cfg.use_kernels:
        return
    B = cfg.batch_size
    if isinstance(X, tuple):
        k = int(X[0].shape[-1])
        schedule, blk_d, n_blocks_max = hinge_ops.resolve_ell_schedule(
            cfg.sparse_schedule, B=B, k=k, d=d, n_blocks_max=sparse_block_bound)
        hinge_ops.record_launch("ell_fleet_half_step", n_iters, registry=reg,
                                m=m, B=B, k=k, d=d, schedule=schedule,
                                blk_d=blk_d, n_blocks_max=n_blocks_max)
    elif cfg.fused:
        hinge_ops.record_launch("fleet_half_step", n_iters, registry=reg,
                                m=m, B=B, d=d)
    else:
        hinge_ops.record_launch("local_half_step", n_iters * m, registry=reg,
                                B=B, d=d)


def gadget_train(
    X_parts: jax.Array,
    y_parts: jax.Array,
    cfg: GadgetConfig = GadgetConfig(),
    *,
    n_counts=None,
    snapshot_every: int | None = None,
    snapshot_slots: int = DEFAULT_SNAPSHOT_SLOTS,
    telemetry: tmt.TrainTelemetry | None = None,
) -> GadgetResult:
    """Simulator-path GADGET over m nodes. X_parts: (m, n_i, d) dense, or a
    ``repro.sparse.EllPartitions`` of stacked padded-ELL planes (sparse local
    half-steps; gossip stays dense in w). y_parts: (m, n_i).

    Thin host wrapper around the jitted device loop: uploads the data and (for
    deterministic topologies) one stacked mixing-matrix cycle, runs the
    entire anytime loop on device, then syncs the result and traces once.

    ``n_counts`` (optional, shape (m,)): per-node valid-row counts for
    non-uniform partitions padded to a common n_i. Padded rows (beyond
    n_counts[i]) must carry y=0; they are never sampled, carry no Push-Sum
    mass, and are excluded from the consensus weighting and objective trace.
    ``repro.data.svm_datasets.partition`` returns exactly these counts.

    ``snapshot_every=K`` (optional): anytime export — every K-th iteration
    records ``(iteration, consensus w, primal objective)`` into an on-device
    ring of ``snapshot_slots`` entries riding the jitted while_loop (GADGET is
    usable at every iteration; this is the serving tap). The ring plus the
    final iterate come back as ``result.snapshots`` (:class:`SnapshotRing`) in
    the same single post-termination sync; decode with
    ``repro.serve.snapshot.snapshots_from``. K > the realized iteration count
    simply yields the final snapshot alone.

    ``telemetry`` (optional :class:`repro.telemetry.TrainTelemetry`): thread
    the flight-recorder trace ring through the same jitted loop — consensus
    disagreement, windowed Push-Sum mass extrema, objective, and fault-drop
    counts every ``telemetry.every`` iterations into ``telemetry.slots`` ring
    slots, decoded into ``result.telemetry`` (:class:`repro.telemetry.
    TrainTrace`) in the same single sync and mirrored onto the default
    registry. ``telemetry=None`` (default) leaves the traced program — and
    therefore the trajectory — bit-identical to builds without the ring
    (asserted in tests).
    """
    _validate_topology(cfg)
    tele_cfg = tmt.validate_telemetry(telemetry)

    empty = np.zeros((0,), np.float32)
    if cfg.max_iters <= 0:  # zero-iteration call: return the initial state
        snap_every = _validate_snapshotting(snapshot_every, snapshot_slots)
        _, m, n_i, d, dtype = _unpack_partitions(X_parts)
        trace = None
        if tele_cfg:
            # W = 0 everywhere: disagreement is exactly 0, nothing recorded
            empty_i = np.zeros((0,), np.int64)
            empty_f = np.zeros((0,), np.float64)
            empty_nf = np.zeros((0, m), np.float64)
            trace = tmt.TrainTrace(
                every=tele_cfg.every, iterations=empty_i,
                disagreement=empty_f, mass_min=empty_f,
                mass_max=empty_f, objective=empty_f,
                drops=empty_i, final_iteration=0,
                final_disagreement=0.0,
                node_disagreement=empty_nf if tele_cfg.per_node else None,
                node_mass=empty_nf if tele_cfg.per_node else None,
                node_drops=(empty_nf.astype(np.int64)
                            if tele_cfg.per_node else None))
            tmt.publish_trace(trace)
        ring = None
        if snap_every:
            # empty ring, initial state as the final iterate: w = 0 scores
            # every margin 0, so the masked primal objective is exactly 1
            ring = SnapshotRing(
                every=snap_every,
                W=np.zeros((int(snapshot_slots), d), np.float32),
                iterations=np.zeros((int(snapshot_slots),), np.int32),
                objectives=np.full((int(snapshot_slots),), np.nan, np.float32),
                count=0, final_w=np.zeros((d,), np.float32),
                final_iteration=0, final_objective=1.0)
        return GadgetResult(W=jnp.zeros((m, d), dtype),
                            w_consensus=jnp.zeros((d,), dtype),
                            iters=0, epsilon=float("inf"),
                            objective_trace=empty, time_trace=empty.astype(np.int32),
                            eps_trace=empty, W_avg=jnp.zeros((m, d), dtype),
                            snapshots=ring, mass_trace=empty, telemetry=trace)

    train, args = _prepare_device_train(cfg, X_parts, y_parts, n_counts,
                                        snapshot_every, snapshot_slots,
                                        telemetry=tele_cfg)
    out = train(*args)
    (W, W_sum, w_cons, iters, n_done, eps, obj_tr, it_tr, eps_tr,
     mass_tr, snaps, tele_out, final_obj, bad) = jax.block_until_ready(out)
    transfer_stats["host_syncs"] += 1  # single post-termination sync
    if int(bad):
        # the on-device guard caught a non-finite consensus plane: typed
        # failure, never a silently-NaN GadgetResult
        tmr.default_registry().counter("train.nonfinite").inc()
        raise NonFiniteWeightsError(int(bad))

    n_done = int(n_done)
    iters = int(iters)
    trace = None
    if tele_cfg:
        ndisr = nmassr = ndropr = None
        if tele_cfg.per_node:
            (ti, tdis, tmn, tmx, tob, tdr, tc, _, _, _,
             ndisr, nmassr, ndropr, _, final_dis) = tele_out
        else:
            ti, tdis, tmn, tmx, tob, tdr, tc, _, _, _, final_dis = tele_out
        trace = tmt.decode_ring(tele_cfg.every, tele_cfg.slots, int(tc),
                                ti, tdis, tmn, tmx, tob, tdr,
                                iters, float(final_dis),
                                node_disagreement=ndisr, node_mass=nmassr,
                                node_drops=ndropr)
        tmt.publish_trace(trace)
    rcfg = _resolve_kernels(cfg)
    X_in, m_in, _, d_in, _ = _unpack_partitions(X_parts)
    _record_train_telemetry(rcfg, m_in, d_in, X_in,
                            _sparse_block_bound(rcfg, X_parts, X_in), iters)
    ring = None
    if snapshot_every:
        sw, si, so, sc = snaps
        ring = SnapshotRing(every=int(snapshot_every), W=np.asarray(sw),
                            iterations=np.asarray(si), objectives=np.asarray(so),
                            count=int(sc), final_w=np.asarray(w_cons),
                            final_iteration=iters,
                            final_objective=float(final_obj))
    return GadgetResult(
        W=W,
        w_consensus=w_cons,
        iters=iters,
        epsilon=float(eps),
        objective_trace=np.asarray(obj_tr)[:n_done],
        time_trace=np.asarray(it_tr)[:n_done],
        eps_trace=np.asarray(eps_tr)[:n_done],
        W_avg=W_sum / max(iters, 1),
        snapshots=ring,
        mass_trace=np.asarray(mass_tr)[:n_done],
        telemetry=trace,
    )


# ---------------------------------------------------------------------------
# Segmented streaming trainer — the live train-to-serve tap
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _make_segment_train(cfg: GadgetConfig, m: int, n_i: int, d: int,
                        seg_len: int, sparse_block_bound: int | None = None,
                        tele: bool = False):
    """Jitted ``seg_len``-iteration training segment, compiled once per
    (cfg, shape, seg_len): a ``lax.scan`` over the same ``_one_iteration``
    body as the while-loop trainer, with the global iteration counter ``t0``
    as a *runtime* argument. Every segment of a run — tail included — reuses
    this one executable: iterations past ``cfg.max_iters`` are masked inactive
    under ``lax.cond`` (exactly the while-loop trainer's tail handling), and
    because the PRNG streams are keyed on the global ``t``
    (``fold_in(data_key, t)``), a segmented run's trajectory is bit-identical
    to one uninterrupted ``gadget_train`` call.

    ``tele`` additionally returns per-segment telemetry extras — boundary
    consensus disagreement, Push-Sum mass extrema over the segment's *active*
    iterations (NaN when the whole segment sat past ``cfg.max_iters``), and
    the segment's fault-drop count. ``tele=False`` traces the exact
    pre-telemetry program (bit-identity pinned by tests)."""
    tele_drops = tele and cfg.faults is not None

    def segment(X, y, B_stack, data_key, mix_key, n_counts, W, W_sum, t0):
        objective_of, consensus_of = _trace_closures(cfg, X, y, n_counts,
                                                     m, n_i, d)

        def step(carry, _):
            W, W_sum, t = carry
            active = t <= cfg.max_iters
            if tele_drops:
                W, W_sum, mass, drops = jax.lax.cond(
                    active,
                    lambda a: _one_iteration(cfg, m, X, y, n_counts,
                                             data_key, mix_key, B_stack, *a,
                                             sparse_block_bound=sparse_block_bound,
                                             count_drops=True),
                    lambda a: (a[0], a[1], jnp.float32(1.0), jnp.int32(0)),
                    (W, W_sum, t),
                )
                ys = (mass, drops)
            else:
                W, W_sum, mass = jax.lax.cond(
                    active,
                    lambda a: _one_iteration(cfg, m, X, y, n_counts,
                                             data_key, mix_key, B_stack, *a,
                                             sparse_block_bound=sparse_block_bound),
                    lambda a: (a[0], a[1], jnp.float32(1.0)),
                    (W, W_sum, t),
                )
                ys = (mass, jnp.int32(0)) if tele else mass
            return (W, W_sum, jnp.where(active, t + 1, t)), ys

        W_prev = W
        (W, W_sum, t), ys = jax.lax.scan(step, (W, W_sum, t0), None,
                                         length=seg_len)
        masses, drops = ys if tele else (ys, None)
        eps = _eps_check(W, W_prev)
        w_cons = consensus_of(W)
        base = (W, W_sum, t, w_cons, objective_of(w_cons), eps,
                jnp.min(masses))
        if not tele:
            return base
        # telemetry extras mask out the inactive tail (iterations clamped
        # past cfg.max_iters report a dummy mass of 1.0)
        n_active = jnp.clip(cfg.max_iters - (t0 - 1), 0, seg_len)
        act = jnp.arange(seg_len) < n_active
        any_act = n_active > 0
        mass_min = jnp.where(any_act,
                             jnp.min(jnp.where(act, masses, jnp.inf)), jnp.nan)
        mass_max = jnp.where(any_act,
                             jnp.max(jnp.where(act, masses, -jnp.inf)), jnp.nan)
        dis = jnp.max(jnp.linalg.norm(W - w_cons[None, :], axis=1))
        return base + (dis, mass_min, mass_max,
                       jnp.sum(jnp.where(act, drops, 0)))

    # No buffer donation: W / W_sum are the arrays the previous segment
    # yielded (or the caller's resume state), which the caller may still
    # hold — donating them would delete them under the caller on a TPU.
    return jax.jit(segment)


def gadget_train_stream(
    X_parts: jax.Array,
    y_parts: jax.Array,
    cfg: GadgetConfig = GadgetConfig(),
    *,
    segment_iters: int,
    n_counts=None,
    resume: TrainState | None = None,
    telemetry: tmt.TrainTelemetry | None = None,
    trace: bool = False,
    trace_link: str | None = None,
    trace_registry=None,
):
    """Generator twin of :func:`gadget_train`: yield a :class:`SegmentResult`
    every ``segment_iters`` iterations while training stays device-resident.

    This is the live train-to-serve tap (``repro.serve.publisher`` runs it in
    a background thread): the trajectory is **bit-identical** to a single
    ``gadget_train`` call on the same config — segments reuse one compiled
    executable with the global iteration counter as a runtime argument, and
    all PRNG draws key on that global counter — but control returns to the
    host at every segment boundary, where the current consensus model can be
    published. ``segment_iters`` is also the ε-check cadence (it plays the
    role ``cfg.check_every`` plays in ``gadget_train``); the stream ends after
    the segment where ``ε < cfg.epsilon`` or ``cfg.max_iters`` is reached
    (that last result carries ``done=True``). Accepts the same dense
    (m, n_i, d) / ``EllPartitions`` data and ``n_counts`` conventions as
    ``gadget_train``. The host blocks once per segment, at its boundary, then
    reads the segment's outputs back one array at a time (five reads, nine
    with ``telemetry``), each counted on the registry counter
    ``train.host_readbacks``.

    ``resume`` (optional :class:`TrainState`, e.g. from
    ``repro.serve.snapshot.train_state_from_checkpoint``): continue a
    previous run from its last completed iteration. Because every PRNG draw
    keys on the *global* iteration counter and segments reuse one compiled
    executable with that counter as a runtime argument, a killed-and-resumed
    run's trajectory is **bit-identical** to the uninterrupted one — the
    crash-recovery half of the fault story (tests pin this).

    ``telemetry`` (optional :class:`repro.telemetry.TrainTelemetry`): attach
    per-segment flight-recorder readings — boundary consensus disagreement,
    active-iteration Push-Sum mass extrema, fault-drop counts — to each
    yielded ``SegmentResult.telemetry`` and mirror them onto the default
    registry (``every``/``slots`` are ring parameters and don't apply here:
    the segment boundary IS the cadence). ``telemetry=None`` (default)
    traces the exact pre-telemetry program: trajectories stay bit-identical.

    Every segment is one ``train.segment`` span on ``trace_registry``
    (default: the process default registry) — segment wall seconds,
    iteration, objective, ε — holding the child spans
    ``train.segment.dispatch`` (the jitted call), ``train.segment.wait``
    (``block_until_ready``), one ``train.readback`` per read (field ``what``)
    and ``train.segment.account`` (finite check, telemetry, gauges). Each is
    also a profiler annotation, so a profiler trace shows the boundary on the
    device operations' clock.

    ``trace=True`` starts one causal trace per segment (the version-lineage
    root): the ``train.segment`` span and its children carry its ids, and
    the root :class:`~repro.telemetry.trace.TraceContext` rides out on
    ``SegmentResult.trace`` for the publisher to extend (explicit
    propagation across the thread boundary; host-side only, the traced
    device program is untouched). ``trace_link`` (the prior run's trace_id,
    e.g. recovered from a checkpoint manifest by the publisher on
    ``resume="latest"``) is stamped onto the first segment's span as a
    ``resumed_from_trace`` attr, linking the fresh traces to the
    pre-crash lineage.
    """
    _validate_topology(cfg)
    tele_cfg = tmt.validate_telemetry(telemetry)
    if int(segment_iters) < 1:
        raise ValueError(f"segment_iters must be >= 1, got {segment_iters}")
    if cfg.max_iters <= 0:
        raise ValueError("gadget_train_stream needs cfg.max_iters > 0 "
                         "(use gadget_train for the zero-iteration case)")
    X, m, n_i, d, dtype = _unpack_partitions(X_parts)
    cfg = _resolve_kernels(cfg)
    cfg = _resolve_faults(cfg, m)
    y = jnp.asarray(y_parts)
    n_counts = _partition_counts(y, n_counts)
    data_key, mix_key = _stream_keys(cfg.seed)
    sparse_block_bound = _sparse_block_bound(cfg, X_parts, X)

    if cfg.topology == "random":
        B_stack = None
    else:
        use_product = cfg.fused and cfg.faults is None
        stack = (topo.build_product_stack(cfg.topology, m, cfg.gossip_rounds)
                 if use_product else topo.build_matrix_stack(cfg.topology, m))
        B_stack = jnp.asarray(stack)
        transfer_stats["matrix_uploads"] += 1  # one upload, same as gadget_train

    segment = _make_segment_train(_cache_cfg(cfg), m, n_i, d,
                                  int(segment_iters), sparse_block_bound,
                                  tele=tele_cfg is not None)
    if resume is not None:
        W = jnp.asarray(resume.W, dtype)
        W_sum = jnp.asarray(resume.W_sum, dtype)
        if W.shape != (m, d) or W_sum.shape != (m, d):
            raise ValueError(
                f"resume state shape {W.shape}/{W_sum.shape} does not match "
                f"the ({m}, {d}) fleet")
        if int(resume.iteration) < 0:
            raise ValueError(f"resume iteration must be >= 0, got {resume.iteration}")
        t = jnp.int32(int(resume.iteration) + 1)
    else:
        W = jnp.zeros((m, d), dtype)
        W_sum = jnp.zeros((m, d), dtype)
        t = jnp.int32(1)
    reg = trace_registry if trace_registry is not None else tmr.default_registry()
    iteration = int(resume.iteration) if resume is not None else 0
    first_segment = True
    while True:
        prev_iteration = iteration
        # one fresh trace per segment: this span is the lineage root the
        # publisher/server chain hangs off (via SegmentResult.trace)
        seg_ctx = tmtr.TraceContext.new() if trace else None
        with reg.span("train.segment", ctx=seg_ctx) as seg_span:
            with reg.span("train.segment.dispatch", ctx=_child(seg_ctx)):
                out = segment(X, y, B_stack, data_key, mix_key, n_counts, W, W_sum, t)
            with reg.span("train.segment.wait", ctx=_child(seg_ctx)):
                out = jax.block_until_ready(out)
            transfer_stats["host_syncs"] += 1  # one blocking boundary per segment
            if tele_cfg:
                (W, W_sum, t, w_cons, objective, eps, mass,
                 dis, seg_mn, seg_mx, seg_drops) = out
            else:
                W, W_sum, t, w_cons, objective, eps, mass = out
            iteration = _readback(reg, seg_ctx, "t", t, int) - 1
            w_host = _readback(reg, seg_ctx, "w_consensus", w_cons, np.asarray)
            eps_f = _readback(reg, seg_ctx, "epsilon", eps, float)
            objective_f = _readback(reg, seg_ctx, "objective", objective, float)
            mass_f = _readback(reg, seg_ctx, "mass", mass, float)
            seg_tele = None
            if tele_cfg:
                seg_tele = tmt.SegmentTelemetry(
                    disagreement=_readback(reg, seg_ctx, "disagreement", dis, float),
                    mass_min=_readback(reg, seg_ctx, "mass_min", seg_mn, float),
                    mass_max=_readback(reg, seg_ctx, "mass_max", seg_mx, float),
                    objective=objective_f,
                    drops=_readback(reg, seg_ctx, "drops", seg_drops, int))
            with reg.span("train.segment.account", ctx=_child(seg_ctx)):
                if not np.all(np.isfinite(w_host)):
                    # segment boundaries ARE the stream's check cadence and
                    # the consensus is already on the host here, so the guard
                    # is a free host-side reduction — same typed failure as
                    # the device loop, and it fires before a publisher could
                    # flush the segment
                    tmr.default_registry().counter("train.nonfinite").inc()
                    raise NonFiniteWeightsError(iteration)
                _record_train_telemetry(cfg, m, d, X, sparse_block_bound,
                                        iteration - prev_iteration)
                if seg_tele is not None:
                    greg = tmr.default_registry()
                    greg.gauge("train.final_disagreement").set(seg_tele.disagreement)
                    greg.gauge("train.objective").set(seg_tele.objective)
                    if np.isfinite(seg_tele.mass_min):
                        greg.gauge("train.mass_min").set(seg_tele.mass_min)
                        greg.gauge("train.mass_max").set(seg_tele.mass_max)
                    greg.counter("train.fault_drops").inc(seg_tele.drops)
                done = eps_f < cfg.epsilon or iteration >= cfg.max_iters
            seg_span.fields.update(
                iteration=iteration, objective=objective_f, epsilon=eps_f,
                done=done,
                resumed_from_trace=trace_link if first_segment else None)
        first_segment = False
        yield SegmentResult(iteration=iteration, W=W, w_consensus=w_host,
                            objective=objective_f, epsilon=eps_f,
                            done=done, W_sum=W_sum, mass=mass_f,
                            telemetry=seg_tele, trace=seg_ctx)
        if done:
            return


def _child(ctx: tmtr.TraceContext | None) -> tmtr.TraceContext | None:
    return None if ctx is None else ctx.child()


def _readback(reg, seg_ctx, what: str, x: jax.Array, convert):
    """``convert(x)``: one device→host read at a segment boundary, timed in a
    ``train.readback`` span (field ``what``) and counted on the registry
    counter ``train.host_readbacks``. Called once per array and segment: a
    later read of the same array hits JAX's host copy and transfers nothing."""
    with reg.span("train.readback", ctx=_child(seg_ctx), what=what):
        reg.counter("train.host_readbacks").inc()
        return convert(x)


# ---------------------------------------------------------------------------
# Host-loop reference (seed semantics) — parity oracle and transfer baseline
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _make_reference_step(cfg: GadgetConfig, m: int, n_i: int, d: int,
                         sparse_block_bound: int | None = None):
    """One jitted GADGET iteration for the host-loop reference, compiled once
    per (cfg, shape): data/keys are runtime arguments, not baked-in constants.
    Deterministic topologies receive this iteration's matrices via ``Bs``
    (the per-iteration host upload being measured); the random protocol draws
    them in-step like the device path and ignores ``Bs``. The sparse block
    bound rides along so the oracle resolves the *same* kernel schedule as
    the device loop — otherwise ``sparse_schedule="auto"`` could pick prefetch
    on one side and sweep on the other and the trajectories would differ in
    float rounding."""

    def step(X, y, n_counts, data_key, mix_key, W, W_sum, t, Bs):
        if cfg.topology == "random":
            Bs = _iter_mixing(mix_key, None, t, m, cfg.gossip_rounds,
                              cfg.topology, cfg.fused, cfg.faults)
        elif cfg.faults is not None:
            # host-uploaded clean rounds, device-applied faults — the same
            # (seed, t, r) fault stream the fused path consumes
            Bs = flt.faulty_rounds(Bs, cfg.faults, t)
        return _gossip_step(cfg, m, X, y, n_counts, data_key, W, W_sum, t, Bs,
                            sparse_block_bound)

    return jax.jit(step)


def gadget_train_reference(
    X_parts: jax.Array,
    y_parts: jax.Array,
    cfg: GadgetConfig = GadgetConfig(),
    *,
    n_counts=None,
    snapshot_every: int | None = None,
    snapshot_slots: int = DEFAULT_SNAPSHOT_SLOTS,
) -> GadgetResult:
    """Seed-style host chunk loop on the same PRNG streams as `gadget_train`:
    mixing matrices cross the host boundary every iteration (deterministic
    topologies) and every ε-check is a blocking ``float(...)`` sync. Always
    runs *unfused* (two kernels per node, R sequential Push-Sum rounds) —
    it is the seed-semantics parity oracle the fused device path is accepted
    against, and the baseline for the transfer-counter benchmark.

    ``snapshot_every=K`` mirrors the device loop's anytime-export ring on the
    host, slot for slot — the reference trace the device snapshots are
    accepted against (tests/test_serve.py sweeps K).
    """
    X, m, n_i, d, dtype = _unpack_partitions(X_parts)
    _validate_topology(cfg)
    cfg = _resolve_kernels(cfg)._replace(fused=False)
    cfg = _resolve_faults(cfg, m)
    n_counts = _partition_counts(y_parts, n_counts)
    data_key, mix_key = _stream_keys(cfg.seed)
    stack = None if cfg.topology == "random" else topo.build_matrix_stack(cfg.topology, m)
    R = cfg.gossip_rounds

    y = jnp.asarray(y_parts)
    total_n = jnp.sum(n_counts)
    objective_of, _ = _trace_closures(cfg, X, y, n_counts, m, n_i, d)
    one_iter = _make_reference_step(_cache_cfg(cfg), m, n_i, d,
                                    _sparse_block_bound(cfg, X_parts, X))
    snap_every = _validate_snapshotting(snapshot_every, snapshot_slots)
    if snap_every:  # host twin of the device ring, slot for slot
        snap_w = np.zeros((snapshot_slots, d), np.float32)
        snap_it = np.zeros((snapshot_slots,), np.int32)
        snap_obj = np.full((snapshot_slots,), np.nan, np.float32)
        snap_count = 0

    W = jnp.zeros((m, d), dtype)
    W_sum = jnp.zeros((m, d), dtype)
    obj_trace, time_trace, eps_trace, mass_trace = [], [], [], []
    eps = float("inf")
    it = 0
    while it < cfg.max_iters:
        chunk = min(cfg.check_every, cfg.max_iters - it)
        W_prev = W
        chunk_masses = []
        for s in range(chunk):
            t = jnp.int32(it + s + 1)
            if stack is not None:
                idx = ((it + s) * R + np.arange(R)) % stack.shape[0]
                Bs = jnp.asarray(stack[idx])  # host→device upload, every iteration
                transfer_stats["matrix_uploads"] += 1
            else:
                Bs = None  # drawn in-step, same as the device path
            W, W_sum, mass = one_iter(X, y, n_counts, data_key, mix_key,
                                      W, W_sum, t, Bs)
            chunk_masses.append(mass)  # device scalar; min'd at the ε-check
            if snap_every and (it + s + 1) % snap_every == 0:
                w_snap = jnp.sum(W * n_counts[:, None], axis=0) / total_n
                slot = snap_count % snapshot_slots
                snap_w[slot] = np.asarray(w_snap)
                snap_it[slot] = it + s + 1
                snap_obj[slot] = float(objective_of(w_snap))
                snap_count += 1
        it += chunk
        eps = float(jnp.max(jnp.linalg.norm(W - W_prev, axis=1)))  # blocking sync
        transfer_stats["host_syncs"] += 1
        w_cons = jnp.sum(W * n_counts[:, None], axis=0) / total_n
        obj_trace.append(float(objective_of(w_cons)))
        transfer_stats["host_syncs"] += 1  # objective pull is a second blocking sync
        time_trace.append(it)
        eps_trace.append(eps)
        mass_trace.append(float(jnp.min(jnp.stack(chunk_masses))))
        if eps < cfg.epsilon:
            break

    w_cons = jnp.sum(W * n_counts[:, None], axis=0) / jnp.sum(n_counts)
    ring = None
    if snap_every:
        ring = SnapshotRing(every=snap_every, W=snap_w, iterations=snap_it,
                            objectives=snap_obj, count=snap_count,
                            final_w=np.asarray(w_cons), final_iteration=it,
                            final_objective=float(objective_of(w_cons)))
    return GadgetResult(
        W=W,
        w_consensus=w_cons,
        iters=it,
        epsilon=eps,
        objective_trace=np.asarray(obj_trace),
        time_trace=np.asarray(time_trace),
        eps_trace=np.asarray(eps_trace),
        W_avg=W_sum / max(it, 1),
        snapshots=ring,
        mass_trace=np.asarray(mass_trace, np.float32),
    )


# ---------------------------------------------------------------------------
# Mesh path: one GADGET iteration as a shard_map-able step
# ---------------------------------------------------------------------------


def make_gadget_mesh_step(cfg: GadgetConfig, axis_sizes: dict[str, int],
                          sparse_block_bound: int | None = None):
    """Build a per-node GADGET step for use inside ``shard_map``.

    The returned ``step(w, X_local, y_local, t, key)`` runs the local Pegasos
    half-step (kernel-backed when ``cfg.use_kernels``) then
    ``cfg.gossip_rounds`` ppermute Push-Sum rounds over the given mesh axes.
    ``t`` is a traced scalar; the gossip hop schedule is rotated by the
    *python-level* step index captured at trace time via closure — callers jit
    once per schedule offset or (default) keep the full exponential schedule
    per step so rotation is unnecessary.

    ``X_local`` is the node's dense (n_local, d) shard **or** a
    ``(cols_local, vals_local)`` tuple of its (n_local, k) padded-ELL planes —
    the node-sharded sparse layout: each shard of the mesh holds only its own
    rows' planes, the half-step runs the ELL kernels on them
    (``cfg.sparse_schedule`` picks sweep vs touched-block, with
    ``sparse_block_bound`` as the prefetch grid cap — derive it on host with
    ``formats.minibatch_block_bound`` over the full planes so every shard
    traces the same grid), and only the dense w crosses the mesh in gossip.
    Kernel-backed steps need ``jax.shard_map(..., check_vma=False)`` — jax
    has no replication rule for ``pallas_call`` yet (tests pin this).

    ``cfg.faults`` injects the same fault model as the simulator path, as
    masked ``ppermute`` sends: each round every node draws a fail bit from
    the plan's salted ``(seed, t, round, node)`` stream and its outgoing
    share is zeroed before the permute (kept locally in ``"link"`` mode,
    dropped in ``"message"`` mode); sends to or from a dead node always
    fail, and dead nodes are frozen entirely. Node ids in
    ``plan.dead_nodes`` index the *linearized* position over ``axis_sizes``
    in dict order (row-major), matching the simulator's node axis for a
    single-axis mesh.
    """
    cfg = _resolve_kernels(cfg)
    sched = exponential_schedule(axis_sizes)
    R = len(sched) if cfg.gossip_rounds is None else cfg.gossip_rounds
    if not sched:
        R = 0  # single-node mesh: no neighbors to gossip with

    n_total = 1
    for n_ax in axis_sizes.values():
        n_total *= int(n_ax)
    faults = None
    if cfg.faults is not None:
        faults = flt.validate_plan(cfg.faults, n_total)
        if faults.drop_prob == 0.0 and not faults.dead_nodes:
            faults = None  # inert plan: keep the unmasked collective path
    dead_ids = (jnp.asarray(faults.dead_nodes, jnp.int32)
                if faults is not None and faults.dead_nodes else None)
    axes = list(axis_sizes)
    strides = {}
    acc = 1
    for ax in reversed(axes):  # row-major linearization over axis_sizes order
        strides[ax] = acc
        acc *= int(axis_sizes[ax])

    def _is_dead(lin):
        if dead_ids is None:
            return jnp.bool_(False)
        return jnp.any(lin == dead_ids)

    def step(w: jax.Array, X_local, y_local: jax.Array,
             t: jax.Array, key: jax.Array) -> jax.Array:
        sparse = isinstance(X_local, tuple)
        n_local = (X_local[0] if sparse else X_local).shape[0]
        ids = jax.random.randint(key, (cfg.batch_size,), 0, n_local)
        tf = t.astype(jnp.float32)
        if sparse:
            cols_l, vals_l = X_local
            Cb, Vb, yb = cols_l[ids], vals_l[ids], y_local[ids]
            # the sparse kernels are fleet-shaped: one-node fleet per shard
            if cfg.use_kernels:
                w_half = hinge_ops.ell_fleet_half_step(
                    w[None], Cb[None], Vb[None], yb[None], lam=cfg.lam, t=tf,
                    project=cfg.project_before_gossip,
                    schedule=cfg.sparse_schedule,
                    n_blocks_max=sparse_block_bound)[0]
            else:
                w_half = hinge_ref.ell_fleet_half_step_ref(
                    w[None], Cb[None], Vb[None], yb[None], cfg.lam, tf,
                    project=cfg.project_before_gossip)[0]
        else:
            w_half = _local_half_step(w, X_local, y_local, ids, cfg.lam,
                                      tf, cfg.project_before_gossip,
                                      cfg.use_kernels)
        state = PushSumState(values=(w_half,), weight=jnp.float32(1.0))
        if faults is not None:
            coords = {ax: jax.lax.axis_index(ax) for ax in axes}
            lin = jnp.int32(0)
            for ax in axes:
                lin = lin * axis_sizes[ax] + coords[ax]
            dead = _is_dead(lin)
        for k in range(R):
            rnd = sched[k % len(sched)]
            if faults is None:
                state = push_sum_round(state, rnd)
                continue
            c = coords[rnd.axis]
            partner_lin = lin + (((c + rnd.hop) % axis_sizes[rnd.axis]) - c) * strides[rnd.axis]
            fail = jax.random.bernoulli(
                jax.random.fold_in(flt.round_fail_key(faults, t, k), lin),
                faults.drop_prob)
            fail = fail | dead | _is_dead(partner_lin)
            state = push_sum_round(state, rnd,
                                   fault=(fail, dead, faults.drop))
        (w_new,) = state.estimate()
        if cfg.project_after_gossip:
            w_new = obj.project_ball(w_new, cfg.lam)
        if faults is not None:
            w_new = jnp.where(dead, w, w_new)  # crashed nodes are frozen
        return w_new

    return step
