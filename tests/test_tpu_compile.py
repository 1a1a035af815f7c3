"""Ahead-of-time compiles of the Pallas entry points for a described TPU v5e.

Every other test runs the kernels in interpret mode, which accepts block
shapes and vector ops that the chip's compiler refuses. These tests hand each
dispatch wrapper to the TPU compiler for a described (not attached) v5e chip
at the deployment widths — CCAT (m = 10, B = 1, k = 76, d = 47,236; its
objective pass over the whole 781,270-row partitions), webspam (d = 254) and
rcv1.multiclass (CCAT's widths, C = 53 one-vs-rest classes) —
and assert that the compiled program holds the Mosaic kernel
(``tpu_custom_call``). A refused block layout, shape cast or VMEM overrun
fails here, without a chip. Nothing runs, so no result is checked.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.gadget_svm import PAPER_RUNS
from repro.core import gadget
from repro.kernels.hinge_subgrad import ops

WIDTHS = {
    "ccat": dict(m=10, B=1, k=76, d=47236),
    "webspam": dict(m=10, B=1, k=84, d=254),
    "rcv1_multiclass": dict(m=10, B=1, k=76, d=47236, C=53),
}
LAM, T = 1e-4, 3.0


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off: a compile for a described chip cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()


def spec(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def compile_for_chip(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("name", ["ccat", "webspam"])
def test_fleet_half_step_compiles(chip, name):
    m, B, d = (WIDTHS[name][x] for x in ("m", "B", "d"))
    compile_for_chip(
        lambda W, X, y: ops.fleet_half_step(W, X, y, lam=LAM, t=T,
                                            interpret=False),
        spec(chip, (m, d)), spec(chip, (m, B, d)), spec(chip, (m, B)))


@pytest.mark.parametrize("B,d,fused", [
    (8, ops.FLEET_TILE_BUDGET_BYTES // (4 * 8), True),  # tile exactly at budget
    (8, 200_000, False),                                # blocked local path
])
def test_fleet_half_step_at_tile_budget_compiles(chip, B, d, fused):
    dp = -(-d // 128) * 128
    assert (B * dp * 4 <= ops.FLEET_TILE_BUDGET_BYTES) == fused
    compile_for_chip(
        lambda W, X, y: ops.fleet_half_step(W, X, y, lam=LAM, t=T,
                                            interpret=False),
        spec(chip, (10, d)), spec(chip, (10, B, d)), spec(chip, (10, B)))


def test_local_half_step_compiles(chip):
    B, d = 8, 200_000
    compile_for_chip(
        lambda w, X, y: ops.local_half_step(w, X, y, lam=LAM, t=T,
                                            interpret=False),
        spec(chip, (d,)), spec(chip, (B, d)), spec(chip, (B,)))


@pytest.mark.parametrize("one_node", [False, True])  # True: a mesh step's shard
@pytest.mark.parametrize("schedule", ["sweep", "prefetch"])
@pytest.mark.parametrize("name", ["ccat", "webspam", "rcv1_multiclass"])
def test_ell_fleet_half_step_compiles(chip, name, schedule, one_node):
    m, B, k, d = (WIDTHS[name][x] for x in ("m", "B", "k", "d"))
    m = 1 if one_node else m
    classes = (WIDTHS[name]["C"],) if "C" in WIDTHS[name] else ()
    compile_for_chip(
        lambda W, c, v, y: ops.ell_fleet_half_step(
            W, c, v, y, lam=LAM, t=T, interpret=False, schedule=schedule),
        spec(chip, (m,) + classes + (d,)), spec(chip, (m, B, k), jnp.int32),
        spec(chip, (m, B, k)), spec(chip, (m, B) + classes))


def test_ell_objective_compiles(chip):
    """The full-data objective pass at CCAT's whole partitions: (m, n_i, k)
    planes with n_i = 78,127 rows a node, w resident in VMEM."""
    m, k, d, n_i = 10, 76, 47236, 78127
    compile_for_chip(
        lambda w, c, v, y, n: ops.ell_objective(w, c, v, y, n, lam=LAM,
                                                total=jnp.sum(n),
                                                interpret=False),
        spec(chip, (d,)), spec(chip, (m, n_i, k), jnp.int32),
        spec(chip, (m, n_i, k)), spec(chip, (m, n_i)), spec(chip, (m,)))


@pytest.mark.parametrize("name", ["ccat", "webspam"])
def test_dense_predict_compiles(chip, name):
    d = WIDTHS[name]["d"]
    compile_for_chip(lambda w, X: ops.dense_predict(w, X, interpret=False),
                     spec(chip, (d,)), spec(chip, (64, d)))


@pytest.mark.parametrize("rows", [8, 256])
@pytest.mark.parametrize("name", ["ccat", "webspam"])
def test_ell_predict_compiles(chip, name, rows):
    k, d = WIDTHS[name]["k"], WIDTHS[name]["d"]
    compile_for_chip(
        lambda w, c, v: ops.ell_predict(w, c, v, n_blocks_max=64,
                                        interpret=False),
        spec(chip, (d,)), spec(chip, (rows, k), jnp.int32),
        spec(chip, (rows, k)))


def test_training_chunk_compiles(chip, monkeypatch):
    """One jitted GADGET training loop at the paper's CCAT config and widths
    (rows per node cut to keep the compile short), kernels included."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    m, k, d, n_i = 10, 76, 47236, 1024
    cfg = PAPER_RUNS["ccat"].gadget._replace(use_kernels=True, max_iters=20,
                                             check_every=10)
    # __wrapped__: a fresh jit, kept out of the factory's cache so no later
    # CPU test can meet a trace made with the kernels compiled
    train = gadget._make_device_train.__wrapped__(
        gadget._cache_cfg(cfg), m, n_i, d, 2, 10, sparse_block_bound=k)
    key = spec(chip, (2,), jnp.uint32)
    compiled = train.lower(
        (spec(chip, (m, n_i, k), jnp.int32), spec(chip, (m, n_i, k))),
        spec(chip, (m, n_i)), None, key, key, spec(chip, (m,)),
        spec(chip, (m, d)), spec(chip, (m, d))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_class_segment_compiles(chip, monkeypatch):
    """One stream segment at rcv1.multiclass's widths, C = 53 classes (rows
    per node cut): the class-axis kernels, the (m, C·d) mix and the blocked
    class objective pass in one program."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    m, k, d, n_i, C = 10, 76, 47236, 1024, 53
    cfg = PAPER_RUNS["ccat"].gadget._replace(use_kernels=True, max_iters=20)
    segment = gadget._make_segment_train.__wrapped__(
        gadget._cache_cfg(cfg), m, n_i, d, 10, sparse_block_bound=k)
    key = spec(chip, (2,), jnp.uint32)
    compiled = segment.lower(
        (spec(chip, (m, n_i, k), jnp.int32), spec(chip, (m, n_i, k))),
        spec(chip, (m, n_i, C)), None, key, key, spec(chip, (m,)),
        spec(chip, (m, C, d)), spec(chip, (m, C, d)), spec(chip, (), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
