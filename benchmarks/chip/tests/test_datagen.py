"""The device data maker against the system's own generator at a small scale:
nonzeros per row, distinct columns, the column-frequency profile, labels."""
import numpy as np
import pytest

import bench
import datagen

N = 20000


@pytest.fixture(scope="module")
def ccat():
    from repro.data.svm_datasets import make_dataset

    cfg = bench.load_cell("ccat_train").config
    spec = dict(cfg["dataset"], n_train=N, n_test=500)
    got = datagen.make_data(2**31 + 11, spec, cfg["gadget"]["n_nodes"], with_test=True)
    want = make_dataset("ccat", scale=N / 781265,
                        seed=3, sparse=True)
    return spec, got, want


def test_rows_hold_exactly_nnz_distinct_columns(ccat):
    spec, got, _ = ccat
    cols = np.asarray(got["cols"]).reshape(-1, spec["nnz_per_row"])
    vals = np.asarray(got["vals"]).reshape(-1, spec["nnz_per_row"])
    assert cols.shape == (N, 76)
    assert np.all((vals != 0).sum(axis=1) == 76)
    assert all(len(np.unique(r)) == 76 for r in cols)
    assert np.all(np.diff(cols, axis=1) > 0)            # sorted within the row
    np.testing.assert_allclose(np.linalg.norm(vals, axis=1), 1.0, rtol=1e-5)
    assert np.asarray(got["test_cols"]).shape == (500, 76)


def test_column_profile_matches_make_dataset(ccat):
    spec, got, want = ccat
    d = spec["d"]
    cols = np.asarray(got["cols"]).ravel()
    mine = np.bincount(cols, minlength=d) / cols.size
    theirs = np.bincount(want.X_train.cols.ravel(), minlength=d) / want.X_train.cols.size
    # the head carries the mass: the 200 hottest columns agree to sampling noise
    np.testing.assert_allclose(mine[:200], theirs[:200], atol=2e-3)
    assert abs(mine[:2000].sum() - theirs[:2000].sum()) < 0.01
    assert np.abs(mine - theirs).sum() < 0.15


def test_labels_follow_the_spec(ccat):
    spec, got, want = ccat
    y = np.asarray(got["y"]).ravel()
    assert set(np.unique(y)) == {-1.0, 1.0}
    assert abs((y > 0).mean() - (want.y_train > 0).mean()) < 0.02


def test_padded_rows_are_inert():
    spec = dict(bench.load_cell("ccat_train").config["dataset"], n_train=1005, n_test=10)
    got = datagen.make_data(5, spec, 10)
    counts, n_i = datagen.node_counts(1005, 10)
    assert list(counts) == [101] * 5 + [100] * 5 and n_i == 101
    for i in range(5, 10):
        assert np.asarray(got["y"])[i, 100] == 0
        assert not np.asarray(got["vals"])[i, 100].any()


def test_dense_rows_match_make_dataset():
    from repro.data.svm_datasets import make_dataset

    spec = dict(bench.load_cell("webspam_train").config["dataset"], n_train=5000)
    got = datagen.make_data(9, spec, 10)
    X = np.asarray(got["X"]).reshape(-1, spec["d"])
    want = make_dataset("webspam", scale=5000 / 234500, seed=1)
    assert np.all((X != 0).sum(axis=1) == spec["nnz_per_row"])
    assert np.all((want.X_train != 0).sum(axis=1) == spec["nnz_per_row"])
    np.testing.assert_allclose(np.linalg.norm(X, axis=1), 1.0, rtol=1e-5)
    y = np.asarray(got["y"]).ravel()
    assert abs((y > 0).mean() - (want.y_train > 0).mean()) < 0.03


def test_same_seed_same_data():
    spec = dict(bench.load_cell("webspam_train").config["dataset"], n_train=500)
    a, b = datagen.make_data(2**33 + 1, spec, 10), datagen.make_data(2**33 + 1, spec, 10)
    np.testing.assert_array_equal(np.asarray(a["X"]), np.asarray(b["X"]))


def test_query_lengths_follow_the_mix():
    """A length mix gives the test rows lognormal lengths around its median,
    distinct columns each, and leaves the training rows as they were."""
    spec = dict(bench.load_cell("ccat_train").config["dataset"], n_train=2000, n_test=4000)
    q = {"dist": "lognormal", "median": 76, "sigma": 0.7, "max": 256}
    plain = datagen.make_data(2**31 + 21, spec, 10, with_test=True)
    mixed = datagen.make_data(2**31 + 21, spec, 10, with_test=True, query_nnz=q)
    np.testing.assert_array_equal(np.asarray(plain["cols"]), np.asarray(mixed["cols"]))
    cols, vals = np.asarray(mixed["test_cols"]), np.asarray(mixed["test_vals"])
    assert cols.shape == (4000, 256)
    n = (vals != 0).sum(axis=1)
    want = np.clip(np.round(76 * np.exp(0.7 * np.random.default_rng(0).standard_normal(10**5))),
                   1, 256)
    assert abs(np.median(n) - 76) <= 3
    for p in (5, 25, 75, 95):
        assert abs(np.percentile(n, p) - np.percentile(want, p)) <= 0.06 * np.percentile(want, p) + 2
    assert all(len(np.unique(c[v != 0])) == k for c, v, k in zip(cols[:300], vals[:300], n[:300]))
    np.testing.assert_allclose(np.linalg.norm(vals, axis=1), 1.0, rtol=1e-5)
