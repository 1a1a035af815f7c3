"""The device data maker against the system's own generator at a small scale:
nonzeros per row, distinct columns, the column-frequency profile, labels."""
import hashlib

import jax
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


def _digest(out):
    h = hashlib.sha256()
    for k in sorted(out):
        a = np.asarray(out[k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("cell, size, seed, with_test, want", [
    ("ccat_train", {"n_train": 3001, "n_test": 300}, 2**31 + 77, True,
     "fa046e64cecca7bae7701cff5d5b74a2187b8d878047fc71419039a90bcd9c9a"),
    ("webspam_train", {"n_train": 2003}, 2**33 + 5, False,
     "050bfebb65d6ba343cb66a362ab4b9c0bdb364e4e4f7b5e3020bb0b6a357da6b"),
])
def test_binary_data_is_as_recorded(cell, size, seed, with_test, want):
    """Without ``n_classes`` the arrays are bit for bit those the binary
    generator made before the class axis was added (hashes recorded on a CPU)."""
    spec = dict(bench.load_cell(cell).config["dataset"], **size)
    assert _digest(datagen.make_data(seed, spec, 10, with_test=with_test)) == want


C = 5
SHARES = (0.5, 0.25, 0.13, 0.08, 0.04)   # a stated, skewed class profile


@pytest.fixture(scope="module")
def classes():
    spec = dict(bench.load_cell("ccat_train").config["dataset"], n_train=N, n_test=10)
    spec.pop("class_balance")
    spec.update(n_classes=C, class_shares=list(SHARES))
    seed = 2**31 + 99
    got = datagen.make_data(seed, spec, 10)
    counts, n_i = datagen.node_counts(N, 10)
    valid = (np.arange(n_i)[None, :] < counts[:, None]).reshape(-1)
    return spec, seed, got, valid


def test_each_valid_row_has_one_class(classes):
    spec, seed, got, valid = classes
    y = np.asarray(got["y"])
    assert y.shape == (10, N // 10, C) and y.dtype == np.float32
    y = y.reshape(-1, C)
    assert set(np.unique(y[valid])) == {-1.0, 1.0}
    assert np.all((y[valid] > 0).sum(axis=1) == 1)
    assert not y[~valid].any()
    # rows are drawn as the binary generator draws them
    binary = dict(bench.load_cell("ccat_train").config["dataset"], n_train=N, n_test=10)
    plain = datagen.make_data(seed, binary, 10)
    for k in ("cols", "vals"):
        np.testing.assert_array_equal(np.asarray(plain[k]), np.asarray(got[k]))


def test_padded_rows_carry_no_class():
    """Also: shares given as a source's row counts make the same labels as
    the same shares given as fractions."""
    spec = dict(bench.load_cell("ccat_train").config["dataset"], n_train=1005, n_test=10,
                n_classes=C, class_shares=[100 * p for p in SHARES])
    y = np.asarray(datagen.make_data(5, spec, 10)["y"])
    assert y.shape == (10, 101, C)
    assert not y[5:, 100].any()
    assert np.all((y[:5, 100] > 0).sum(axis=1) == 1)
    spec["class_shares"] = list(SHARES)
    np.testing.assert_array_equal(np.asarray(datagen.make_data(5, spec, 10)["y"]), y)


@pytest.mark.parametrize("shares", [[1, 1, 1], [1, 0, 1], [0.5, 0.5]])
def test_class_shares_are_one_positive_number_a_class(shares):
    spec = dict(bench.load_cell("ccat_train").config["dataset"], n_train=100, n_test=10,
                n_classes=3, class_shares=shares)
    if min(shares) > 0 and len(shares) == 3:
        np.testing.assert_allclose(datagen.class_shares(spec), 1 / 3)
        return
    with pytest.raises(ValueError, match="class_shares"):
        datagen.make_data(5, spec, 10)


def test_class_shares_follow_the_offsets(classes):
    """Before the noise the argmax gives each class its stated share of the
    valid rows to within a few rows; the noise then moves label_noise of each
    class's rows uniformly to the others. Within 4 sampling sigmas of the
    noise draws, and 0.2 % of the rows for the fit."""
    spec, _, got, valid = classes
    y = np.asarray(got["y"]).reshape(-1, C)[valid]
    share = (y > 0).mean(axis=0)
    p, noise = datagen.class_shares(spec), spec["label_noise"]
    want = p * (1 - noise) + (1 - p) * noise / (C - 1)
    sigma = np.sqrt(noise * (1 - noise) / len(y))
    assert np.all(np.abs(share - want) < 4 * sigma + 2e-3), (share, want)


def test_offsets_fit_stated_shares_at_the_skew_of_topics():
    """53 classes from 45 % down to 0.1 % of 100,000 standard-normal rows:
    every class within 0.1 % of the rows (100 rows) of its share, and the
    rarest within a fifth of its own."""
    rng = np.random.default_rng(7)
    p = 0.88 ** np.arange(53)
    p = np.maximum(p, p[0] / 450) / np.maximum(p, p[0] / 450).sum()
    z = rng.standard_normal((100_000, 53)).astype(np.float32)
    b = datagen.fit_offsets(z, np.ones(len(z), np.float32), p)
    got = np.bincount(np.argmax(z + np.asarray(b), axis=1), minlength=53) / len(z)
    assert np.all(np.abs(got - p) < 1e-3), np.abs(got - p).max()
    assert abs(got[-1] - p[-1]) < 0.2 * p[-1], (got[-1], p[-1])


def test_labels_are_the_planted_argmax_but_for_noise(classes):
    spec, seed, got, valid = classes
    k_w = jax.random.split(jax.random.PRNGKey(seed), 3)[0]
    w_star = np.asarray(jax.random.normal(k_w, (C, spec["d"])), np.float64)
    cols = np.asarray(got["cols"]).reshape(-1, spec["nnz_per_row"])[valid]
    vals = np.asarray(got["vals"]).reshape(-1, spec["nnz_per_row"])[valid]
    margin = np.einsum("rk,crk->rc", vals, w_star[:, cols])
    z = ((margin - margin.mean(axis=0)) / margin.std(axis=0)).astype(np.float32)
    b = np.asarray(datagen.fit_offsets(z, np.ones(len(z), np.float32), datagen.class_shares(spec)))
    y = np.asarray(got["y"]).reshape(-1, C)[valid]
    agree = (np.argmax(z + b, axis=1) == y.argmax(axis=1)).mean()
    assert agree >= 1 - spec["label_noise"] - 0.02
