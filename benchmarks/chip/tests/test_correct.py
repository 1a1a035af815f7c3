"""The comparison that decides ``correct`` fails what it has to fail.

At a CPU test's size: the control (the reference computed in bfloat16 put in
the program's place) reads not correct against each cell's limits, and a run
whose timed path is broken underneath — a step that returns its state
unchanged, half of the batch left out, the exchange between nodes left out,
an answer altered or left out — comes out with ``correct`` false, while the
same run unbroken comes out true.
"""
import time

import jax.numpy as jnp
import numpy as np
import pytest

import check
import control
import run as runner
import window
from conftest import small_cell

SEED = 2**31 + 4242


def _run(name, seconds=2.0):
    cell = small_cell(name)
    result, _ = runner.run_cell(cell, SEED, seconds, False, t_start=time.perf_counter(),
                                require_tpu=False)
    return result


@pytest.mark.parametrize("name", ["ccat_train", "webspam_train"])
def test_training_control_is_not_correct(name):
    from repro.core import gadget

    cell = small_cell(name)
    state = window.make_state(cell, SEED, gadget)
    got = control.training_readings(cell, state, gadget)
    ok, _ = check.verdict(got["program"], cell.limits)
    assert ok
    for variant in ("control", "no_mix", "half_batch"):
        ok, checks = check.verdict(got[variant], cell.limits)
        assert not ok, (variant, checks)


def test_serving_control_is_not_correct():
    from repro import serve
    from repro.core import gadget

    cell = small_cell("ccat_serve")
    state = window.make_state(cell, SEED, gadget)
    got = control.serving_readings(cell, state, serve, 2.0, 7)
    assert check.verdict(got["program"], cell.limits)[0]
    for variant in ("control", "altered", "dropped"):
        assert not check.verdict(got[variant], cell.limits)[0], variant


@pytest.mark.parametrize("name", ["ccat_train", "webspam_train", "ccat_serve",
                                  "ccat_train_serve"])
def test_sound_run_is_correct(name, fresh_program):
    result = _run(name)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"


def _frozen(monkeypatch, gadget):
    monkeypatch.setattr(gadget, "_one_iteration",
                        lambda cfg, m, X, y, n, dk, mk, Bs, W, W_sum, t, *a, **k:
                        (W, W_sum, jnp.float32(1.0)))


def _half_batch(monkeypatch, gadget):
    ref = gadget.hinge_ref

    def cut(fn):
        def half(W, *args, **kw):
            *rows, y = args[:-2]
            m = y.shape[0]
            y = y * (jnp.arange(m) < m // 2)[:, None]
            return fn(W, *rows, y, *args[-2:], **kw)
        return half

    monkeypatch.setattr(ref, "ell_fleet_half_step_ref", cut(ref.ell_fleet_half_step_ref))
    monkeypatch.setattr(ref, "fleet_half_step_ref", cut(ref.fleet_half_step_ref))


def _no_mix(monkeypatch, gadget):
    monkeypatch.setattr(gadget, "mix_collapsed", lambda values, weight, P: (values, weight))


@pytest.mark.parametrize("name", ["ccat_train", "webspam_train"])
@pytest.mark.parametrize("fault", [_frozen, _half_batch, _no_mix])
def test_broken_training_step_is_not_correct(name, fault, monkeypatch, fresh_program):
    fault(monkeypatch, fresh_program)
    result = _run(name)
    assert not result["correct"], result["checks"]


def _altered(monkeypatch):
    from repro.serve import engine

    real = engine.SvmServer.score_sparse

    def altered(self, cols, vals, **kw):
        scores, labels = real(self, cols, vals, **kw)
        scores = np.array(scores)
        scores[0] += 0.01 * max(abs(scores[0]), 1e-3)
        return scores, labels

    monkeypatch.setattr(engine.SvmServer, "score_sparse", altered)


def _dropped(monkeypatch):
    from repro.serve import engine

    real = engine.SvmServer.score_sparse

    def dropped(self, cols, vals, **kw):
        scores, labels = real(self, cols, vals, **kw)
        scores = np.array(scores)
        scores[: len(scores) // 2] = 0.0
        return scores, labels

    monkeypatch.setattr(engine.SvmServer, "score_sparse", dropped)


@pytest.mark.parametrize("fault", [_altered, _dropped])
def test_broken_scoring_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    result = _run("ccat_serve")
    assert not result["correct"], result["checks"]


def test_broken_exchange_while_serving_is_not_correct(monkeypatch, fresh_program):
    _no_mix(monkeypatch, fresh_program)
    result = _run("ccat_train_serve")
    assert not result["correct"], result["checks"]
