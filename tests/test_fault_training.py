"""Fault injection through the training stack: the fused device path, the
host-loop reference, the segmented stream, crash-resume, and the mesh step all
under one FaultPlan — parity, mass accounting, frozen dead nodes, and
bit-identical kill-and-resume."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.faults import FaultPlan
from repro.core.gadget import (GadgetConfig, TrainState, gadget_train,
                               gadget_train_reference, gadget_train_stream)


def _toy_parts(m=4, n_i=16, d=24, seed=0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=d)
    X = rng.normal(size=(m * n_i, d)).astype(np.float32)
    y = np.sign(X @ w_true).astype(np.float32)
    return jnp.asarray(X.reshape(m, n_i, d)), jnp.asarray(y.reshape(m, n_i))


def _cfg(**kw):
    base = dict(lam=1e-2, batch_size=2, gossip_rounds=2, max_iters=16,
                check_every=4, epsilon=0.0, use_kernels=False)
    base.update(kw)
    return GadgetConfig(**base)


# ---------------------------------------------------------------------------
# Fused device path vs host-loop reference (the parity oracle)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topology", ["exponential", "random"])
@pytest.mark.parametrize("drop", ["link", "message"])
def test_fused_matches_reference_under_faults(topology, drop):
    """The acceptance-criteria parity: fused training with faults matches the
    host-loop reference to <= 1e-5 on the consensus weights — the fault layer
    composes with the collapsed-product gossip path without changing what is
    computed."""
    X, y = _toy_parts()
    cfg = _cfg(topology=topology,
               faults=FaultPlan(drop_prob=0.2, drop=drop, seed=5))
    dev = gadget_train(X, y, cfg)
    ref = gadget_train_reference(X, y, cfg)
    assert dev.iters == ref.iters
    diff = float(jnp.max(jnp.abs(dev.w_consensus - ref.w_consensus)))
    assert diff <= 1e-5, diff
    W_diff = float(jnp.max(jnp.abs(dev.W - ref.W)))
    assert W_diff <= 1e-5, W_diff


def test_dead_nodes_parity_and_reference_mass():
    X, y = _toy_parts()
    cfg = _cfg(faults=FaultPlan(drop_prob=0.1, drop="link",
                                dead_nodes=(1,), seed=2))
    dev = gadget_train(X, y, cfg)
    ref = gadget_train_reference(X, y, cfg)
    assert float(jnp.max(jnp.abs(dev.w_consensus - ref.w_consensus))) <= 1e-5
    # both paths account mass the same way
    np.testing.assert_allclose(dev.mass_trace, ref.mass_trace, atol=1e-5)


# ---------------------------------------------------------------------------
# Mass invariant
# ---------------------------------------------------------------------------


def test_mass_trace_conserved_without_faults_and_in_link_mode():
    X, y = _toy_parts()
    clean = gadget_train(X, y, _cfg())
    np.testing.assert_allclose(clean.mass_trace, 1.0, atol=1e-5)
    linked = gadget_train(
        X, y, _cfg(faults=FaultPlan(drop_prob=0.4, drop="link", seed=3)))
    assert linked.mass_trace.shape == clean.mass_trace.shape
    # ack'd links: exact conservation to float-sum tolerance, every check
    np.testing.assert_allclose(linked.mass_trace, 1.0, atol=1e-5)


def test_mass_trace_measures_message_leakage():
    X, y = _toy_parts()
    res = gadget_train(
        X, y, _cfg(faults=FaultPlan(drop_prob=0.4, drop="message", seed=3)))
    assert np.all(res.mass_trace <= 1.0 + 1e-6)
    assert res.mass_trace.min() < 0.999  # leakage actually observed


# ---------------------------------------------------------------------------
# Dead nodes are bit-frozen
# ---------------------------------------------------------------------------


def test_dead_node_weights_bit_frozen():
    X, y = _toy_parts()
    res = gadget_train(
        X, y, _cfg(faults=FaultPlan(dead_nodes=(0, 2), seed=1)))
    W = np.asarray(res.W)
    # dead rows never left their (zero) initialization — exactly
    np.testing.assert_array_equal(W[0], np.zeros_like(W[0]))
    np.testing.assert_array_equal(W[2], np.zeros_like(W[2]))
    # survivors trained
    assert float(np.abs(W[1]).max()) > 0
    assert float(np.abs(W[3]).max()) > 0


# ---------------------------------------------------------------------------
# Inert plans hit the perfect-network path bit-identically
# ---------------------------------------------------------------------------


def test_inert_plan_bit_identical_to_no_plan():
    X, y = _toy_parts()
    clean = gadget_train(X, y, _cfg())
    inert = gadget_train(
        X, y, _cfg(faults=FaultPlan(drop_prob=0.0, seed=99)))
    assert bool(jnp.all(clean.W == inert.W))
    np.testing.assert_array_equal(np.asarray(clean.w_consensus),
                                  np.asarray(inert.w_consensus))


def test_invalid_plan_rejected_at_train_entry():
    X, y = _toy_parts()
    with pytest.raises(ValueError):
        gadget_train(X, y, _cfg(faults=FaultPlan(drop_prob=1.5)))
    with pytest.raises(ValueError):
        gadget_train(X, y, _cfg(faults=FaultPlan(dead_nodes=(7,))))


# ---------------------------------------------------------------------------
# Stream + crash-resume under faults
# ---------------------------------------------------------------------------


def test_faulty_stream_bitmatches_train():
    X, y = _toy_parts()
    cfg = _cfg(faults=FaultPlan(drop_prob=0.3, drop="message",
                                dead_nodes=(3,), seed=8))
    ref = gadget_train(X, y, cfg)
    segs = list(gadget_train_stream(X, y, cfg, segment_iters=5))
    assert segs[-1].iteration == ref.iters
    assert bool(jnp.all(segs[-1].W == ref.W))
    np.testing.assert_array_equal(np.asarray(segs[-1].w_consensus),
                                  np.asarray(ref.w_consensus))


def test_kill_and_resume_bit_identical_under_faults():
    """The acceptance-criteria resume: stop after a segment, rebuild a
    TrainState, continue — final weights bit-match the uninterrupted faulty
    run (fault draws key on the global iteration, so the replayed stream is
    the same stream)."""
    X, y = _toy_parts()
    cfg = _cfg(faults=FaultPlan(drop_prob=0.25, drop="link", seed=4))
    full = list(gadget_train_stream(X, y, cfg, segment_iters=4))

    first = next(iter(gadget_train_stream(X, y, cfg, segment_iters=4)))
    ts = TrainState(iteration=first.iteration, W=first.W, W_sum=first.W_sum)
    resumed = list(gadget_train_stream(X, y, cfg, segment_iters=4, resume=ts))

    assert [s.iteration for s in resumed] == [s.iteration for s in full[1:]]
    assert bool(jnp.all(resumed[-1].W == full[-1].W))
    np.testing.assert_array_equal(np.asarray(resumed[-1].w_consensus),
                                  np.asarray(full[-1].w_consensus))


def test_resume_validation():
    X, y = _toy_parts()
    cfg = _cfg()
    bad_shape = TrainState(iteration=4, W=jnp.zeros((2, 3)),
                           W_sum=jnp.zeros((2, 3)))
    with pytest.raises(ValueError):
        next(gadget_train_stream(X, y, cfg, segment_iters=4, resume=bad_shape))
    m, d = X.shape[0], X.shape[-1]
    neg = TrainState(iteration=-1, W=jnp.zeros((m, d)), W_sum=jnp.zeros((m, d)))
    with pytest.raises(ValueError):
        next(gadget_train_stream(X, y, cfg, segment_iters=4, resume=neg))


# ---------------------------------------------------------------------------
# Mesh path (4 forced CPU devices, subprocess so the flag cannot leak)
# ---------------------------------------------------------------------------

MESH_FAULT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.faults import FaultPlan
from repro.core.gadget import GadgetConfig, make_gadget_mesh_step

m, n_i, d = 4, 16, 24
rng = np.random.default_rng(0)
w_true = rng.normal(size=d)
X = rng.normal(size=(m, n_i, d)).astype(np.float32)
y = np.sign(X @ w_true).astype(np.float32)
mesh = Mesh(np.array(jax.devices()), ("nodes",))
cfg = GadgetConfig(lam=1e-2, batch_size=2, gossip_rounds=2, use_kernels=False)

def runner(step):
    def per_node(w, x, yl, keys, t):
        return step(w[0], x[0], yl[0], t, keys[0])[None]
    specs = (P("nodes"),) * 4 + (P(),)
    return jax.jit(jax.shard_map(per_node, mesh=mesh, in_specs=specs,
                                 out_specs=P("nodes"), check_vma=False))

def train(step, iters=6):
    W = jnp.zeros((m, d), jnp.float32)
    run = runner(step)
    Xd, yd = jnp.asarray(X), jnp.asarray(y)
    for t in range(1, iters + 1):
        keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(0), t), m)
        W = run(W, Xd, yd, keys, jnp.int32(t))
    return np.asarray(W)

# 1. inert plan is bit-identical to the unmasked collective path
W_clean = train(make_gadget_mesh_step(cfg, {"nodes": m}))
W_inert = train(make_gadget_mesh_step(
    cfg._replace(faults=FaultPlan(drop_prob=0.0, seed=7)), {"nodes": m}))
assert np.array_equal(W_clean, W_inert), "inert plan perturbed the mesh step"

# 2. dead shard bit-frozen at init, survivors train
W_dead = train(make_gadget_mesh_step(
    cfg._replace(faults=FaultPlan(dead_nodes=(2,), seed=7)), {"nodes": m}))
assert np.array_equal(W_dead[2], np.zeros(d, np.float32)), "dead shard moved"
assert all(np.abs(W_dead[i]).max() > 0 for i in (0, 1, 3)), "survivor frozen"

# 3. faulty links: run completes, weights finite + distinct from clean
W_drop = train(make_gadget_mesh_step(
    cfg._replace(faults=FaultPlan(drop_prob=0.5, drop="message", seed=7)),
    {"nodes": m}))
assert np.all(np.isfinite(W_drop)), "faulty mesh run produced non-finite w"
assert np.abs(W_drop).max() > 0 and not np.array_equal(W_drop, W_clean)

# 4. invalid plan rejected at build time (linearized id out of range)
try:
    make_gadget_mesh_step(cfg._replace(faults=FaultPlan(dead_nodes=(4,))),
                          {"nodes": m})
    raise SystemExit("out-of-range dead node accepted")
except ValueError:
    pass
print("MESH_FAULTS_OK")
"""


class TestMeshFaults:
    def test_mesh_step_faults_multidevice(self, tmp_path):
        """The ppermute fault path on a real 4-device mesh: inert plans are
        bit-inert, dead shards freeze, link drops degrade gracefully, and
        plan validation happens at build time."""
        import os
        import subprocess
        import sys
        script = tmp_path / "mesh_faults.py"
        script.write_text(MESH_FAULT_SCRIPT)
        repo = __file__.rsplit("/tests/", 1)[0]
        env = {**os.environ, "PYTHONPATH": f"{repo}/src"}
        p = subprocess.run([sys.executable, str(script)], capture_output=True,
                           text=True, timeout=540, env=env)
        assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
        assert "MESH_FAULTS_OK" in p.stdout
