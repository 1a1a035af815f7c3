"""Each required-work function against hand arithmetic at the CCAT and
webspam shapes (m = 10 nodes, B = 1, check_every = 200)."""
import pytest

import bench

CCAT = bench.load_cell("ccat_train").config
WEBSPAM = bench.load_cell("webspam_train").config
PEAKS = bench.peaks_for("TPU v5 lite")


def reader_module(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), bench.HERE / "layer_metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bytes_bind(flops, nbytes):
    return nbytes / PEAKS["hbm_bytes_per_s"] > flops / PEAKS["flops_per_s"]


def test_train_step_ccat():
    flops, nbytes = reader_module("train_step_mfu").required(CCAT)
    # rows: 10 × (76 × 8 + 4); touched: 2 × 4 × 10 × 76; plane: 2 × 4 × 10 × 47,236
    # per iteration plus the ε-check's two reads of it every 200 iterations
    assert nbytes == pytest.approx(6120 + 6080 + 3_778_880 + 18_894.4)
    assert flops == 4 * 10 * 76 + 10 * 47236 * 28
    assert bytes_bind(flops, nbytes)


def test_train_step_webspam():
    flops, nbytes = reader_module("train_step_mfu").required(WEBSPAM)
    assert nbytes == pytest.approx(10 * (254 * 4 + 4) + 2 * 4 * 10 * 254 + 20_320 * 1.005)
    assert flops == 4 * 10 * 254 + 10 * 254 * 28
    assert bytes_bind(flops, nbytes)


# rcv1.multiclass (LibSVM; RCV1-v2, Lewis et al., JMLR 2004) trained on its
# 518,571-row split, one-vs-rest over 53 classes, at the paper's CCAT settings
RCV1_MULTICLASS = {
    "dataset": dict(CCAT["dataset"], n_train=518571, n_classes=53),
    "gadget": CCAT["gadget"],
}


def test_train_step_rcv1_multiclass():
    flops, nbytes = reader_module("train_step_mfu").required(RCV1_MULTICLASS)
    # rows: 10 × (76 × 8 + 4); touched: 8 × 10 × 76 × 53;
    # plane and ε-check: 8 × 10 × 53 × 47,236 × (1 + 1/200)
    assert nbytes == pytest.approx(6120 + 322_240 + 201_282_043.2)
    assert round(nbytes) == 201_610_403
    assert flops == 4 * 10 * 76 * 53 + 10 * 53 * 47236 * 28 == 701_143_360
    assert bytes_bind(flops, nbytes)


def test_objective_pass_rcv1_multiclass():
    flops, nbytes = reader_module("ell_objective_roofline").required(RCV1_MULTICLASS)
    assert nbytes == 518571 * (8 * 76 + 4) + 4 * 53 * 47236 == 327_379_484
    assert flops == 518571 * 53 * (2 * 76 + 4) == 4_287_545_028


def test_half_steps_count_every_class():
    ell = reader_module("ell_fleet_half_step_roofline").required
    assert ell(RCV1_MULTICLASS) == (4 * 10 * 76 * 53, 10 * (8 * 76 + 4) + 8 * 10 * 76 * 53)
    dense = reader_module("fleet_half_step_roofline").required
    webspam_classes = {"dataset": dict(WEBSPAM["dataset"], n_classes=53),
                       "gadget": WEBSPAM["gadget"]}
    assert dense(webspam_classes) == (4 * 10 * 254 * 53, 10 * (4 * 254 + 4) + 8 * 10 * 254 * 53)


@pytest.mark.parametrize("name", ["train_step_mfu", "ell_fleet_half_step_roofline",
                                  "fleet_half_step_roofline", "ell_objective_roofline"])
@pytest.mark.parametrize("config", [CCAT, WEBSPAM], ids=["ccat", "webspam"])
def test_one_class_is_the_binary_count(name, config):
    """``n_classes`` 1 counts exactly what a configuration without it does."""
    one = {"dataset": dict(config["dataset"], n_classes=1), "gadget": config["gadget"]}
    req = reader_module(name).required
    assert req(one) == req(config)


def test_sparse_scoring_ccat():
    import numpy as np

    flops, nbytes = reader_module("serve_step_mfu").required(np.full(1000, 76))
    assert (flops, nbytes) == (152_000, 916_000)
    assert bytes_bind(flops, nbytes)
    # queries of mixed lengths: each counts its own nonzeros
    assert reader_module("serve_step_mfu").required(np.array([10, 300])) == (620, 3_728)


def test_readers_use_only_the_traced_stretch():
    import numpy as np

    import run
    import window

    r = window.Run(cell="ccat_train", config=CCAT, traffic={"train": {}}, seconds=30)
    # two segments of 200 iterations inside the stretch [0, 2], one after it
    r.segments = [(0.0, 1.0, 200), (1.0, 2.0, 200), (2.0, 9.0, 200)]
    ctx = run.Context(r, None, (0.0, 2.0), PEAKS)
    flops, nbytes = reader_module("train_step_mfu").required(CCAT)
    want = 100.0 * 400 * nbytes / PEAKS["hbm_bytes_per_s"] / 2.0
    assert reader_module("train_step_mfu").read(ctx) == pytest.approx(want)

    r.due = np.array([0.5, 1.0, 1.95, 3.0])
    r.sent = r.due + np.array([0.001, 0.002, 0.5, 0.5])
    r.drain_start = r.due + 0.004
    r.score = np.ones(4)
    ctx = run.Context(r, None, (0.0, 2.0), PEAKS)
    assert reader_module("generator_lag_ms.serve").read(ctx) == pytest.approx(2.0)
    assert reader_module("queue_wait_ms.serve").read(ctx) == pytest.approx(4.0)
