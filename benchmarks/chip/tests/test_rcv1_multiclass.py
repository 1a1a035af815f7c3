"""The ``rcv1_multiclass_train`` cell at a CPU test's size: the program's
one-vs-rest path through the harness comes out correct, the control and each
fault read incorrect against the cell's limits, and the plane-decay roofline
reader counts the plane the decay pass moves."""
import time
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

import bench
import check
import control
import run as runner
import scopes
import window
from conftest import small_cell

SEED = 2**31 + 1653
C = 5
PEAKS = bench.peaks_for("TPU v5 lite")


def class_cell():
    """The cell with rows, d and C cut for the CPU; every setting kept."""
    cell = small_cell("rcv1_multiclass_train")
    ds = cell.config["dataset"]
    ds.update(d=2048, n_classes=C, class_shares=ds["class_shares"][:C])
    return cell


def test_sound_run_is_correct(fresh_program):
    result, _ = runner.run_cell(class_cell(), SEED, 2.0, False,
                                t_start=time.perf_counter(), require_tpu=False)
    assert result["correct"], result["checks"]
    assert result["metrics"]["train_samples_per_s"]["value"] > 0


def test_control_and_faults_are_not_correct(fresh_program):
    cell = class_cell()
    state = window.make_state(cell, SEED, fresh_program)
    got = control.training_readings(cell, state, fresh_program)
    assert check.verdict(got["program"], cell.limits)[0], got["program"]
    for variant in ("control", "no_mix", "half_batch"):
        ok, checks = check.verdict(got[variant], cell.limits)
        assert not ok, (variant, checks)


def test_frozen_step_is_not_correct(monkeypatch, fresh_program):
    monkeypatch.setattr(fresh_program, "_one_iteration",
                        lambda cfg, m, X, y, n, dk, mk, Bs, W, W_sum, t, *a, **k:
                        (W, W_sum, jnp.float32(1.0)))
    result, _ = runner.run_cell(class_cell(), SEED, 2.0, False,
                                t_start=time.perf_counter(), require_tpu=False)
    assert not result["correct"], result["checks"]


def decay_ctx(name, monkeypatch, seconds):
    """A context whose scope table holds ``seconds`` under ``gadget.decay``
    over two 200-iteration segments."""
    monkeypatch.setattr(scopes, "load", lambda ctx: object())
    table = {"gadget.half_step": 0.5}
    if seconds is not None:
        table["gadget.decay"] = seconds
    monkeypatch.setattr(scopes, "scope_seconds", lambda sc: table)
    cfg = bench.load_cell(name).config
    segs = [(0.0, 1.0, 200), (1.0, 2.0, 200)]
    return runner.Context(SimpleNamespace(config=cfg, segments=segs), object(),
                          (0.0, 2.0), PEAKS)


@pytest.mark.parametrize("name,nbytes", [("ccat_train", 8 * 10 * 47236),
                                         ("rcv1_multiclass_train", 8 * 10 * 53 * 47236)])
def test_plane_decay_roofline(name, nbytes, monkeypatch):
    read = bench.load_reader("plane_decay_roofline")
    got = read(decay_ctx(name, monkeypatch, 0.1))
    assert got == pytest.approx(100.0 * 400 * nbytes / PEAKS["hbm_bytes_per_s"] / 0.1)
    assert read(decay_ctx(name, monkeypatch, None)) is None
