"""Push-Sum / Push-Vector protocol (Kempe, Dobra & Gehrke 2003).

Two execution paths, same semantics:

* **Simulator path** (`PushSumSim`): all n nodes live in one array with a
  leading node axis. One gossip round is the linear map ``x' = B^T x`` applied
  to both the value tensor and the mass weights — the exact matrix form of
  Algorithm 1 in the GADGET paper, usable with *any* mixing matrix (including
  the paper's random-neighbor draws). Runs on a single device; this is the
  path used to validate the paper's claims.

* **Mesh path** (`push_sum_round` / `push_sum_mesh`): each node is one slice of
  a mesh axis inside ``shard_map``; a round is one ``jax.lax.ppermute`` with a
  static time-varying one-peer-exponential hop. Multi-axis meshes (pod × data)
  gossip on one axis per round following ``exponential_schedule`` — a torus
  factorization of the hypercube exchange that maps 1:1 onto ICI links.

Invariant (property-tested): total mass is conserved —
``sum_i v_{t,i} = sum_i v_{0,i}`` and ``sum_i w_{t,i} = n`` for every t; the
ratio v/w at every node converges to the initial network average.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import topology as topo
from repro.core.svm_objective import F32

Pytree = Any

__all__ = [
    "PushSumState",
    "PushSumSim",
    "GossipRound",
    "exponential_schedule",
    "mix_rounds",
    "collapse_rounds",
    "mix_collapsed",
    "push_sum_round",
    "push_sum_mesh",
]


class PushSumState(NamedTuple):
    """Node-local Push-Sum mass: values pytree + scalar weight.

    Simulator path: every leaf carries a leading node axis of size n and
    ``weight`` has shape (n,). Mesh path: leaves are the node's local values
    and ``weight`` is a scalar.
    """

    values: Pytree
    weight: jax.Array

    def estimate(self) -> Pytree:
        """Current average estimate v_{t,i} / w_{t,i} at every node."""
        w = self.weight

        def _div(v):
            return (v / jnp.reshape(w, w.shape + (1,) * (v.ndim - w.ndim)).astype(v.dtype)
                    if w.ndim else v / w.astype(v.dtype))

        return jax.tree.map(_div, self.values)


# ---------------------------------------------------------------------------
# Simulator path (matrix form, any topology)
# ---------------------------------------------------------------------------


class PushSumSim:
    """Matrix-form Push-Sum over n simulated nodes.

    Mixing semantics: B[i, j] is the share of node i's mass pushed to node j,
    so one round applies ``x' = B^T x`` (columns of B^T sum to 1 => mass
    conserved even when B is only column-stochastic, e.g. the paper's random
    one-neighbor protocol).
    """

    def __init__(self, n_nodes: int, topology: str = "exponential", seed: int = 0):
        if topology not in topo.TOPOLOGIES:
            raise ValueError(f"unknown topology {topology!r}")
        self.n = int(n_nodes)
        self.topology = topology
        self.seed = int(seed)

    def matrix(self, t: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, t)) if self.topology == "random" else None
        return topo.build_matrix(self.topology, self.n, t=t, rng=rng)

    def init(self, values: Pytree) -> PushSumState:
        leaves = jax.tree.leaves(values)
        if not leaves or any(l.shape[0] != self.n for l in leaves):
            raise ValueError(f"every leaf needs leading node axis of size {self.n}")
        return PushSumState(values=values, weight=jnp.ones((self.n,), jnp.float32))

    def round(self, state: PushSumState, t: int) -> PushSumState:
        B = jnp.asarray(self.matrix(t), dtype=jnp.float32)  # (n, n)

        def _mix(v):
            flat = v.reshape(self.n, -1).astype(jnp.float32)
            out = jnp.matmul(B.T, flat, precision=F32)
            return out.reshape(v.shape).astype(v.dtype)

        values = jax.tree.map(_mix, state.values)
        weight = jnp.matmul(B.T, state.weight, precision=F32)
        return PushSumState(values, weight)

    def run(self, values: Pytree, n_rounds: int, t0: int = 0) -> PushSumState:
        state = self.init(values)
        for t in range(t0, t0 + n_rounds):
            state = self.round(state, t)
        return state

    def rounds_for_error(self, gamma: float) -> int:
        """O(tau_mix * log(1/gamma)) round count from the spectral bound."""
        tau = topo.mixing_time_bound(self.matrix(0))
        if not np.isfinite(tau):
            raise ValueError("disconnected topology: infinite mixing time")
        return max(1, int(np.ceil(tau * np.log(1.0 / gamma))))


def mix_rounds(values: jax.Array, weight: jax.Array, B_rounds: jax.Array):
    """Apply R Push-Sum rounds ``x' = B^T x`` to (n, ...) values and (n,) mass
    weights, entirely on device. ``B_rounds``: (R, n, n) — precomputed stack
    slices for deterministic topologies or fresh ``jax.random`` draws for the
    paper's random protocol. Mass-conserving for any row-stochastic B.
    """

    def body(carry, B):
        v, w = carry
        return (jnp.matmul(B.T, v, precision=F32),
                jnp.matmul(B.T, w, precision=F32)), None

    (v, w), _ = jax.lax.scan(body, (values, weight), B_rounds)
    return v, w


def collapse_rounds(B_rounds: jax.Array) -> jax.Array:
    """Fold an (R, n, n) round stack into the single matrix P = B_R^T … B_1^T.

    Push-Sum rounds are linear maps, so R sequential rounds collapse exactly:
    ``mix_rounds(v, w, Bs) == (P @ v, P @ w)``. The fold runs R-1 small
    (n, n)×(n, n) products instead of R (n, n)×(n, d) value mixes — the win
    when d ≫ n, and the device-side counterpart of
    :func:`repro.core.topology.build_product_stack` for matrices only known
    inside the jitted step (the paper's random one-neighbor draws).
    """

    def body(P, B):
        return jnp.matmul(B.T, P, precision=F32), None

    P0 = jnp.eye(B_rounds.shape[-1], dtype=B_rounds.dtype)
    P, _ = jax.lax.scan(body, P0, B_rounds)
    return P


def mix_collapsed(values: jax.Array, weight: jax.Array, P: jax.Array):
    """Apply a collapsed round product to (n, ...) values and (n,) mass
    weights: one matmul per tensor, replacing the R-round ``mix_rounds`` scan.
    ``P`` comes from :func:`collapse_rounds` or a precomputed
    ``topology.build_product_stack`` slice."""
    return (jnp.matmul(P, values, precision=F32),
            jnp.matmul(P, weight, precision=F32))


# ---------------------------------------------------------------------------
# Mesh path (shard_map + ppermute, one-peer exponential graph per axis)
# ---------------------------------------------------------------------------


class GossipRound(NamedTuple):
    axis: str  # mesh axis the exchange runs on
    hop: int   # ring distance 2^k on that axis


def exponential_schedule(axis_sizes: dict[str, int]) -> list[GossipRound]:
    """Torus factorization of the one-peer exponential exchange.

    For mesh axes {a_1: n_1, a_2: n_2, ...} emit hops 1, 2, ..., n_i/2 on each
    axis in turn: sum_i log2(n_i) rounds total, after which (with
    self_share=0.5) every node holds the exact global average. This is the
    deterministic-gossip analogue of a recursive-doubling all-reduce, but each
    round is one ppermute (one ICI neighbor hop) instead of a blocking
    collective — the property the GADGET protocol is built around.
    """
    rounds: list[GossipRound] = []
    for axis, n in axis_sizes.items():
        if n == 1:
            continue
        if n & (n - 1):
            raise ValueError(f"axis {axis!r} size {n} must be a power of two for the exponential schedule")
        hop = 1
        while hop < n:
            rounds.append(GossipRound(axis=axis, hop=hop))
            hop *= 2
    return rounds


def _ring_perm(n: int, hop: int) -> list[tuple[int, int]]:
    return [(i, (i + hop) % n) for i in range(n)]


def push_sum_round(
    state: PushSumState,
    rnd: GossipRound,
    *,
    self_share: float = 0.5,
    fault: tuple | None = None,
) -> PushSumState:
    """One Push-Sum round inside ``shard_map``: keep ``self_share`` of the
    local mass, ppermute the rest ``hop`` steps along ``rnd.axis``.

    ``fault`` (optional) injects the :mod:`repro.core.faults` model into the
    collective as a masked send: a ``(fail_send, dead, drop)`` triple where
    ``fail_send`` is this shard's scalar bool — its outgoing share this round
    is zeroed before the permute (every shard still executes the ppermute, so
    the collective stays uniform across the mesh); ``drop="link"`` folds the
    undeliverable share back into the local mass (exact conservation),
    ``drop="message"`` loses it. ``dead`` freezes this shard's values and
    weight entirely — a crashed node neither mixes nor accumulates."""
    n = jax.lax.axis_size(rnd.axis)
    if n == 1:
        return state
    pairs = _ring_perm(n, rnd.hop)
    send = 1.0 - self_share

    def _shift(x):
        return jax.lax.ppermute(x, rnd.axis, pairs)

    if fault is None:
        def _mix(v):
            v32 = v.astype(jnp.float32)
            return (v32 * self_share + _shift(v32 * send)).astype(v.dtype)

        values = jax.tree.map(_mix, state.values)
        weight = state.weight * self_share + _shift(state.weight * send)
        return PushSumState(values, weight)

    fail_send, dead, drop = fault
    fail_send = fail_send | dead  # dead nodes never deliver
    send_gate = jnp.where(fail_send, 0.0, send)
    # "link": the sender detects the failure and keeps its share; "message":
    # the share is lost in flight (value and weight mass vanish together)
    keep = self_share + (jnp.where(fail_send, send, 0.0) if drop == "link" else 0.0)

    def _mix(v):
        v32 = v.astype(jnp.float32)
        out = v32 * keep + _shift(v32 * send_gate)
        return jnp.where(dead, v32, out).astype(v.dtype)

    values = jax.tree.map(_mix, state.values)
    w = state.weight
    weight = jnp.where(dead, w, w * keep + _shift(w * send_gate))
    return PushSumState(values, weight)


def push_sum_mesh(
    values: Pytree,
    *,
    axis_sizes: dict[str, int],
    n_rounds: int | None = None,
    t0: int = 0,
    self_share: float = 0.5,
    normalize: bool = True,
) -> Pytree:
    """Run Push-Sum rounds inside shard_map and return the per-node estimate.

    ``n_rounds=None`` runs one full exponential schedule (exact averaging).
    Fewer rounds gives the paper's anytime/partial-consensus behaviour; the
    schedule is rotated by ``t0`` so successive optimizer steps continue the
    hop sequence instead of repeating hop=1 forever.
    """
    sched = exponential_schedule(axis_sizes)
    if not sched:
        return values
    total = len(sched) if n_rounds is None else int(n_rounds)
    state = PushSumState(values=values, weight=jnp.float32(1.0))
    for k in range(total):
        state = push_sum_round(state, sched[(t0 + k) % len(sched)], self_share=self_share)
    return state.estimate() if normalize else state.values
