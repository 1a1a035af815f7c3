"""Multi-class GADGET (paper §5 future work): one-vs-rest over shared gossip."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gadget as G
from repro.core.gadget import GadgetConfig
from repro.core.multiclass import gadget_train_multiclass, predict_multiclass
from repro.sparse.formats import EllPartitions
from repro.telemetry import registry as tmr


def _make_multiclass(n=3000, d=16, C=4, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(C, d)) * 3.0
    y = rng.integers(0, C, size=n)
    X = centers[y] + rng.normal(size=(n, d))
    return X.astype(np.float32), y.astype(np.int32)


def test_multiclass_gadget_learns():
    m, C = 8, 4
    X, y = _make_multiclass()
    n_i = len(y) // m
    Xp = jnp.asarray(X[: m * n_i].reshape(m, n_i, -1))
    yp = jnp.asarray(y[: m * n_i].reshape(m, n_i))
    res = gadget_train_multiclass(
        Xp, yp, C, GadgetConfig(lam=1e-3, batch_size=8, gossip_rounds=3,
                                max_iters=1200, check_every=300))
    pred = predict_multiclass(res.w_consensus, jnp.asarray(X))
    acc = float(jnp.mean((pred == jnp.asarray(y)).astype(jnp.float32)))
    assert acc > 0.85, acc
    # per-node models agree with the consensus prediction on most points
    pred0 = predict_multiclass(res.W[0], jnp.asarray(X))
    agree = float(jnp.mean((pred0 == pred).astype(jnp.float32)))
    assert agree > 0.95, agree


# --------------------------------------------------- class axis on the device path
# One-vs-rest labels (m, n_i, C) carry W as (m, C, d) through gadget_train_stream:
# each class's rows must be the binary run on that class's ±1 column.

M, N_I, D, C, K = 4, 64, 512, 5, 12
SEG, ITERS = 5, 10


def _class_data(layout, seed=7):
    rng = np.random.default_rng(seed)
    cols = np.sort(rng.choice(D, (M, N_I, K)), axis=-1).astype(np.int32)
    vals = rng.random((M, N_I, K)).astype(np.float32)
    vals /= np.linalg.norm(vals, axis=-1, keepdims=True)
    y = np.where(rng.integers(0, C, (M, N_I))[..., None] == np.arange(C), 1.0, -1.0)
    if layout == "ell":
        X = EllPartitions(cols, vals, D)
    else:
        X = np.zeros((M, N_I, D), np.float32)
        np.put_along_axis(X, cols, vals, axis=-1)
    return X, y.astype(np.float32)


def _cfg(use_kernels):
    return GadgetConfig(lam=1e-2, batch_size=2, gossip_rounds=2, topology="random",
                        epsilon=0.0, max_iters=ITERS, use_kernels=use_kernels, seed=3)


def _segments(X, y, cfg, **kw):
    return list(G.gadget_train_stream(X, y, cfg, segment_iters=SEG, **kw))


@pytest.mark.parametrize("layout,use_kernels",
                         [("ell", True), ("ell", False), ("dense", True), ("dense", False)])
def test_each_class_is_its_binary_stream(layout, use_kernels):
    X, y = _class_data(layout)
    cfg = _cfg(use_kernels)
    segs = _segments(X, y, cfg)
    binary = [_segments(X, y[..., c], cfg) for c in range(C)]
    for i, seg in enumerate(segs):
        assert seg.W.shape == (M, C, D) and seg.w_consensus.shape == (C, D)
        assert seg.objective.shape == (C,)
        for c in range(C):
            b = binary[c][i]
            np.testing.assert_allclose(np.asarray(seg.W)[:, c], np.asarray(b.W),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(seg.W_sum)[:, c], np.asarray(b.W_sum),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(seg.w_consensus[c], b.w_consensus, rtol=1e-5, atol=1e-6)
            assert seg.objective[c] == pytest.approx(b.objective, rel=1e-5)


def test_one_class_plane_is_the_binary_run():
    X, y = _class_data("ell")
    cfg = _cfg(True)
    got, want = _segments(X, y[..., :1], cfg), _segments(X, y[..., 0], cfg)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g.W)[:, 0], np.asarray(w.W), rtol=1e-6, atol=1e-7)
        assert g.objective[0] == pytest.approx(w.objective, rel=1e-6)
        assert g.epsilon == pytest.approx(w.epsilon, rel=1e-6)


def test_class_resume_is_bit_identical():
    X, y = _class_data("ell")
    cfg = _cfg(False)
    whole = _segments(X, y, cfg)
    first = whole[0]
    state = G.TrainState(iteration=first.iteration, W=first.W, W_sum=first.W_sum)
    resumed = _segments(X, y, cfg, resume=state)
    assert len(resumed) == 1 and resumed[0].iteration == whole[-1].iteration
    np.testing.assert_array_equal(np.asarray(resumed[0].W), np.asarray(whole[-1].W))
    np.testing.assert_array_equal(np.asarray(resumed[0].W_sum), np.asarray(whole[-1].W_sum))
    np.testing.assert_array_equal(resumed[0].objective, whole[-1].objective)
    with pytest.raises(ValueError, match="does not match"):
        _segments(X, y, cfg, resume=state._replace(W=first.W[:, 0]))


def test_gossip_bytes_count_one_mass_per_node():
    X, y = _class_data("ell")
    counter = tmr.default_registry().counter("train.gossip_bytes")
    before = counter.value
    _segments(X, y, _cfg(False))
    # random protocol: R rounds × m one-neighbour links × (C·d + 1) floats
    assert counter.value - before == ITERS * 2 * M * (C * D + 1) * 4


def test_class_labels_refuse_what_has_no_class_axis():
    X, y = _class_data("dense")
    with pytest.raises(ValueError, match="fused=False"):
        G.gadget_train(X, y, _cfg(False)._replace(fused=False))
    with pytest.raises(ValueError, match="snapshot"):
        G.gadget_train(X, y, _cfg(False), snapshot_every=5)
    with pytest.raises(ValueError, match="class axis"):
        G.gadget_train_reference(X, y, _cfg(False))
