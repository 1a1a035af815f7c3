"""The class axis of the harness: one-vs-rest data, the C-class reference
replay and the comparison on (node, class) leaves, at a CPU test's size."""
import jax.numpy as jnp
import numpy as np
import pytest

import check
import datagen
import reference
from conftest import SMALL_TRAIN, small_cell

C = 5
SEED = 2**31 + 515


def class_cell(name):
    """The cell cut to a CPU test's size, its labels made one-vs-rest over C classes."""
    cell = small_cell(name)
    ds = cell.config["dataset"]
    ds.pop("class_balance")
    ds.update(n_train=1500, n_classes=C, class_shares=[0.5, 0.25, 0.13, 0.08, 0.04])
    return cell


@pytest.fixture(scope="module", params=["ccat_train", "webspam_train"])
def replayed(request):
    """The C-class replay and, for each class, the binary replay on its ±1 column."""
    cfg = class_cell(request.param).config
    ds, g = cfg["dataset"], cfg["gadget"]
    data = datagen.make_data(SEED, ds, g["n_nodes"])
    rows = (data["cols"], data["vals"]) if ds["layout"] == "ell" else data["X"]
    args = (data["counts"], SEED, g, ds["d"], SMALL_TRAIN["check_every"], 3)
    classes = reference.train_segments(rows, data["y"], *args)
    binary = [reference.train_segments(rows, data["y"][..., c], *args) for c in range(C)]
    return classes, binary


def test_each_class_replays_as_its_binary_fleet(replayed):
    classes, binary = replayed
    for seg, (W, W_sum, obj) in enumerate(classes):
        assert W.shape[1] == C and W_sum.shape == W.shape and obj.shape == (C,)
        for c in range(C):
            bW, bW_sum, bobj = binary[c][seg]
            for got, want in ((W[:, c], bW), (W_sum[:, c], bW_sum)):
                assert np.linalg.norm(got - want) <= 1e-6 * np.linalg.norm(want)
            assert abs(obj[c] - bobj) <= 1e-6 * abs(bobj)


def test_objective_gap_is_the_worst_class(replayed):
    classes, _ = replayed
    moved = [(W, W_sum, obj.copy()) for W, W_sum, obj in classes]
    moved[1][2][3] *= 1 + 3e-4
    assert check.training_numbers(moved, classes)["objective_gap"] == pytest.approx(3e-4)


def test_one_small_class_moved_shows_in_state_dist():
    """52 classes of norm 1 and one of norm 0.02 on each node: moving the small
    class's rows by 1e-3 of their norm reads 2e-5 on its (node, class) leaves;
    as part of a whole node's (C, d) matrix it would read 2.8e-6."""
    rng = np.random.default_rng(3)
    m, n_cls, d = 10, 53, 64

    def unit(shape):
        x = rng.standard_normal(shape)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    W = unit((m, n_cls, d))
    W[:, 7] *= 0.02
    W_sum = 3 * W
    ref = [(W, W_sum, np.ones(n_cls))]
    moved_W = W.copy()
    moved_W[:, 7] += 1e-3 * 0.02 * unit((m, d))
    got = check.training_numbers([(moved_W, W_sum, np.ones(n_cls))], ref)
    assert got["state_dist"] > 1e-5
    whole_node = check.worst_dist(list(moved_W.reshape(m, -1)), list(W.reshape(m, -1)))
    assert whole_node < 1e-5


def test_binary_leaves_are_node_rows():
    W = np.arange(12.0).reshape(3, 4)
    leaves = check._leaves(W, -W)
    assert len(leaves) == 6
    np.testing.assert_array_equal(leaves[1], W[1])
    np.testing.assert_array_equal(leaves[4], -W[1])


def test_control_and_faults_fail_the_class_replay():
    """The control and the reference's own faults run the class axis as they
    are and read far above the limits the binary cells hold."""
    cfg = class_cell("ccat_train").config
    ds, g = cfg["dataset"], cfg["gadget"]
    data = datagen.make_data(SEED + 1, ds, g["n_nodes"])
    rows = (data["cols"], data["vals"])
    args = (data["counts"], SEED, g, ds["d"], SMALL_TRAIN["check_every"], 3)
    ref = reference.train_segments(rows, data["y"], *args)
    limits = small_cell("ccat_train").limits
    for kw in (dict(dtype=jnp.bfloat16), dict(fault="no_mix"), dict(fault="half_batch")):
        got = check.training_numbers(reference.train_segments(rows, data["y"], *args, **kw), ref)
        assert not check.verdict(got, limits)[0], (kw, got)
