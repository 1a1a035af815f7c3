"""The general load generator: a fixed amount of work per seed, arrivals where
the mix's parameters put them, and an unknown process failing loudly."""
import numpy as np
import pytest

import loadgen

POISSON = {"arrivals": "poisson", "rate_qps": 300}
BURSTS = {"arrivals": "on_off", "rate_qps": 300, "burst": {"period_s": 0.4, "on_share": 0.25}}


@pytest.mark.parametrize("spec", [POISSON, BURSTS], ids=["poisson", "on_off"])
def test_every_seed_sends_the_same_count(spec):
    for seed in (1, 2**31 + 3, 2**33 + 5):
        due, rows = loadgen.schedule(spec, seed, 7.0, 100)
        assert len(due) == len(rows) == 2100
        assert np.all(np.diff(due) >= 0) and due.min() >= 0 and due.max() < 7.0
        assert rows.min() >= 0 and rows.max() < 100


def test_same_seed_same_schedule():
    a, b = loadgen.schedule(BURSTS, 2**32 + 9, 3.0, 50), loadgen.schedule(BURSTS, 2**32 + 9, 3.0, 50)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


def test_bursts_fall_only_in_their_stretches_at_the_raised_rate():
    due, _ = loadgen.schedule(BURSTS, 11, 8.0, 10)
    phase = due % 0.4
    assert np.all(phase < 0.1 + 1e-12)
    per_burst = np.bincount((due // 0.4).astype(int), minlength=20)
    assert len(per_burst) == 20 and per_burst.min() > 0
    assert abs(per_burst.mean() - 300 * 0.4) < 1e-9          # r·period per burst on average


def test_poisson_is_uniform_over_the_window():
    due, _ = loadgen.schedule(POISSON, 5, 10.0, 10)
    counts = np.histogram(due, bins=10, range=(0, 10))[0]
    assert np.all(np.abs(counts - 300) < 4 * np.sqrt(300))


def test_a_window_that_ends_inside_a_burst():
    spans = loadgen.on_stretches(BURSTS, 1.0)
    np.testing.assert_allclose(spans, [[0.0, 0.1], [0.4, 0.5], [0.8, 0.9]])
    spans = loadgen.on_stretches(dict(BURSTS, burst={"period_s": 0.4, "on_share": 0.75}), 1.0)
    np.testing.assert_allclose(spans, [[0.0, 0.3], [0.4, 0.7], [0.8, 1.0]])


def test_unknown_process_fails_loudly():
    with pytest.raises(LookupError):
        loadgen.schedule({"arrivals": "hawkes", "rate_qps": 1}, 1, 1.0, 1)
