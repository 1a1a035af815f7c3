"""Sparse subsystem: ELL/CSR round-trips, sparse kernel parity vs the dense
oracles (sweep AND touched-block/prefetch schedules), block-bucketed schedule
helpers, streaming LibSVM ingest, generator sparsity guarantees, and
end-to-end sparse-vs-dense GADGET consensus agreement."""
import warnings

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.gadget import (GadgetConfig, gadget_train, gadget_train_reference,
                               gadget_train_stream)
from repro.data import libsvm, svm_datasets
from repro.kernels.hinge_subgrad import ops as hinge_ops
from repro.kernels.hinge_subgrad import ref as hinge_ref
from repro.kernels.hinge_subgrad import sparse as hinge_sparse
from repro.sparse import (CSR, ELL, EllPartitions, block_map, bucket_by_block,
                          frequency_remap, minibatch_block_bound,
                          partition_rows, row_block_counts)
# shared oracle fixtures (also used by test_serve.py's predict parity tests)
from tests.sparse_utils import ell_minibatch_planes, random_sparse as _random_sparse

RNG = np.random.default_rng(0)


# ------------------------------------------------------------- containers

class TestFormats:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 12), st.integers(2, 40), st.integers(0, 6))
    def test_roundtrip_property(self, n, d, nnz_max):
        X = _random_sparse(n, d, min(nnz_max, d))
        csr = CSR.from_dense(X)
        ell = ELL.from_dense(X)
        np.testing.assert_array_equal(csr.to_dense(), X)
        np.testing.assert_array_equal(ell.to_dense(), X)
        np.testing.assert_array_equal(csr.to_ell().to_dense(), X)
        np.testing.assert_array_equal(ell.to_csr().to_dense(), X)
        assert csr.nnz == (X != 0).sum() == ell.nnz

    def test_take_rows_and_matvec(self):
        X = _random_sparse(20, 30, 5)
        w = RNG.normal(size=30).astype(np.float32)
        idx = RNG.permutation(20)[:7]
        csr, ell = CSR.from_dense(X), ELL.from_dense(X)
        np.testing.assert_array_equal(csr.take_rows(idx).to_dense(), X[idx])
        np.testing.assert_array_equal(ell.take_rows(idx).to_dense(), X[idx])
        np.testing.assert_allclose(ell.matvec(w), X @ w, atol=1e-5)

    def test_ell_k_max_validation(self):
        X = _random_sparse(5, 10, 4)
        widest = int((X != 0).sum(axis=1).max())
        if widest > 1:
            with pytest.raises(ValueError):
                CSR.from_dense(X).to_ell(k_max=widest - 1)
        padded = CSR.from_dense(X).to_ell(k_max=widest + 3)
        assert padded.k_max == widest + 3
        np.testing.assert_array_equal(padded.to_dense(), X)

    def test_bad_indices_rejected(self):
        with pytest.raises(ValueError):
            ELL(np.array([[5]], np.int32), np.array([[1.0]], np.float32), (1, 3))
        with pytest.raises(ValueError):
            CSR(np.ones(1), np.array([7], np.int32), np.array([0, 1]), (1, 4))

    def test_partition_rows_covers_everything(self):
        idx, counts, n_i = partition_rows(101, 10, seed=0)
        assert counts.sum() == 101 and n_i == 11
        valid = np.concatenate([idx[i * n_i: i * n_i + counts[i]] for i in range(10)])
        assert np.array_equal(np.sort(valid), np.arange(101))
        with pytest.raises(ValueError):
            partition_rows(3, 5)


# ------------------------------------------------------- kernels vs oracles

class TestSparseKernels:
    @pytest.mark.parametrize("m,B,d,k", [(1, 1, 64, 1), (3, 5, 300, 7),
                                         (4, 8, 1024, 40), (2, 3, 130, 129)])
    def test_fleet_parity_dense_oracle(self, m, B, d, k):
        """Sparse kernel == sparse ref == dense fleet ref on the same data."""
        X = _random_sparse(m * B, d, k).reshape(m, B, d)
        ell = ELL.from_dense(X.reshape(m * B, d))
        kw = ell.k_max
        cols = jnp.asarray(ell.cols.reshape(m, B, kw))
        vals = jnp.asarray(ell.vals.reshape(m, B, kw))
        y = jnp.asarray(np.sign(RNG.normal(size=(m, B)) + 0.1).astype(np.float32))
        W = jnp.asarray(RNG.normal(size=(m, d)).astype(np.float32) * 0.1)
        t = jnp.float32(3.0)

        want = hinge_ref.fleet_half_step_ref(W, jnp.asarray(X), y, 1e-3, t)
        got_ref = hinge_ref.ell_fleet_half_step_ref(W, cols, vals, y, 1e-3, t)
        got_kern = hinge_ops.ell_fleet_half_step(W, cols, vals, y, lam=1e-3,
                                                 t=t, interpret=True)
        np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want), atol=2e-5)
        np.testing.assert_allclose(np.asarray(got_kern), np.asarray(want), atol=2e-5)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 4), st.integers(1, 9), st.integers(2, 200),
           st.integers(1, 12))
    def test_fleet_parity_property(self, m, B, d, k):
        self.test_fleet_parity_dense_oracle(m, B, d, min(k, d))

    def test_margins_kernel_matches_ref(self):
        m, B, d, k = 3, 6, 500, 11
        X = _random_sparse(m * B, d, k)
        ell = ELL.from_dense(X)
        kw = ell.k_max
        cols = jnp.asarray(ell.cols.reshape(m, B, kw))
        vals = jnp.asarray(ell.vals.reshape(m, B, kw))
        y = jnp.asarray(np.sign(RNG.normal(size=(m, B))).astype(np.float32))
        W = jnp.asarray(RNG.normal(size=(m, d)).astype(np.float32) * 0.2)
        # kernel needs lane/sublane padding — go through a hand-padded call
        colsP = jnp.pad(cols, ((0, 0), (0, 2), (0, 128 - kw)))
        valsP = jnp.pad(vals, ((0, 0), (0, 2), (0, 128 - kw)))
        yP = jnp.pad(y, ((0, 0), (0, 2)))
        WP = jnp.pad(W, ((0, 0), (0, 512 - d)))
        got = hinge_sparse.ell_margins(colsP, valsP, WP, yP, blk_d=256,
                                       interpret=True)[:, :B]
        want = jnp.stack([
            hinge_ref.ell_margins_ref(W[i], cols[i], vals[i], y[i])
            for i in range(m)])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    # shared with test_serve.py: tests/sparse_utils.ell_minibatch_planes is
    # the one statement of the planes-plus-dense-oracle fixture
    def _ell_planes(self, m, B, d, k, localized=False):
        return ell_minibatch_planes(m, B, d, k, localized)

    @settings(max_examples=12, deadline=None)
    @given(st.integers(1, 3), st.integers(1, 6), st.integers(64, 700),
           st.integers(1, 10), st.booleans())
    def test_prefetch_parity_property(self, m, B, d, k, localized):
        """The satellite acceptance sweep: the touched-block (prefetch)
        schedule must match the one-hot sweep kernels AND the jnp oracle to
        ≤ 1e-5 on arbitrary shapes, with the data-derived grid bound."""
        X, cols, vals, y, W = self._ell_planes(m, B, d, min(k, d), localized)
        t = jnp.float32(4.0)
        want = hinge_ref.fleet_half_step_ref(W, jnp.asarray(X), y, 1e-3, t)
        bound = minibatch_block_bound(np.asarray(cols), np.asarray(vals), B,
                                      d=d)
        sweep = hinge_ops.ell_fleet_half_step(W, cols, vals, y, lam=1e-3, t=t,
                                              interpret=True, schedule="sweep")
        pref = hinge_ops.ell_fleet_half_step(W, cols, vals, y, lam=1e-3, t=t,
                                             interpret=True, schedule="prefetch",
                                             n_blocks_max=bound)
        np.testing.assert_allclose(np.asarray(pref), np.asarray(want), atol=1e-5)
        np.testing.assert_allclose(np.asarray(pref), np.asarray(sweep), atol=1e-5)

    def test_prefetch_degenerate_single_block(self):
        """All nnz inside one d-block: the map holds one live id, the rest
        sentinel; n_blocks_max=1 is a legal (tight) grid."""
        m, B, d = 2, 4, 640
        cols = jnp.asarray(128 + RNG.integers(0, 128, size=(m, B, 5)).astype(np.int32))
        vals = jnp.asarray(RNG.normal(size=(m, B, 5)).astype(np.float32))
        y = jnp.asarray(np.sign(RNG.normal(size=(m, B))).astype(np.float32))
        W = jnp.asarray(RNG.normal(size=(m, d)).astype(np.float32) * 0.1)
        t = jnp.float32(2.0)
        want = hinge_ref.ell_fleet_half_step_ref(W, cols, vals, y, 1e-2, t)
        got = hinge_ops.ell_fleet_half_step(W, cols, vals, y, lam=1e-2, t=t,
                                            interpret=True, schedule="prefetch",
                                            n_blocks_max=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    def test_prefetch_degenerate_all_pad_node(self):
        """A node whose minibatch is entirely pad rows (vals=0, y=0): its map
        is all sentinel, its half-step is pure decay (+projection)."""
        m, B, d = 3, 4, 300
        _, cols, vals, y, W = self._ell_planes(m, B, d, 6)
        cols = cols.at[1].set(0)
        vals = vals.at[1].set(0.0)
        y = y.at[1].set(0.0)
        t = jnp.float32(3.0)
        want = hinge_ref.ell_fleet_half_step_ref(W, cols, vals, y, 1e-2, t)
        got = hinge_ops.ell_fleet_half_step(W, cols, vals, y, lam=1e-2, t=t,
                                            interpret=True, schedule="prefetch")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    def test_prefetch_degenerate_k_zero(self):
        """k_max=0 planes (every row empty after bucketing) still dispatch."""
        m, B, d = 2, 3, 200
        cols = jnp.zeros((m, B, 0), jnp.int32)
        vals = jnp.zeros((m, B, 0), jnp.float32)
        y = jnp.zeros((m, B), jnp.float32)
        W = jnp.asarray(RNG.normal(size=(m, d)).astype(np.float32))
        t = jnp.float32(2.0)
        want = hinge_ref.ell_fleet_half_step_ref(
            W, jnp.zeros((m, B, 1), jnp.int32), jnp.zeros((m, B, 1), jnp.float32),
            y, 1e-2, t)
        for sched in ("sweep", "prefetch"):
            got = hinge_ops.ell_fleet_half_step(W, cols, vals, y, lam=1e-2, t=t,
                                                interpret=True, schedule=sched)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)

    def test_margins_prefetch_kernel_matches_ref(self):
        """Kernel-level check of the scalar-prefetched DMA steering."""
        m, B, d, k, blk_d = 2, 6, 500, 9, 128
        X, cols, vals, y, W = self._ell_planes(m, B, d, k)
        n_d_blocks = -(-d // blk_d)
        kw = cols.shape[2]
        colsP = jnp.pad(cols, ((0, 0), (0, 2), (0, 128 - kw)))
        valsP = jnp.pad(vals, ((0, 0), (0, 2), (0, 128 - kw)))
        yP = jnp.pad(y, ((0, 0), (0, 2)))
        WP = jnp.pad(W, ((0, 0), (0, (n_d_blocks + 1) * blk_d - d)))
        bids = jnp.asarray(block_map(np.asarray(colsP), np.asarray(valsP),
                                     blk_d, n_d_blocks, 5))
        got = hinge_sparse.ell_margins_prefetch(colsP, valsP, WP, yP, bids,
                                                blk_d=blk_d, n_d_blocks=n_d_blocks,
                                                interpret=True)[:, :B]
        want = jnp.stack([
            hinge_ref.ell_margins_ref(W[i], cols[i], vals[i], y[i])
            for i in range(m)])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_pad_entries_inert(self):
        """Extra (col=0, val=0) ELL entries change nothing — the pad
        convention the kernels rely on instead of a validity plane. (Row
        padding is NOT free: B is the batch-mean denominator, which is why
        only the wrapper pads rows, before computing scal.)"""
        m, B, d, k = 2, 4, 100, 5
        X = _random_sparse(m * B, d, k)
        ell = ELL.from_dense(X)
        kw = ell.k_max
        cols = jnp.asarray(ell.cols.reshape(m, B, kw))
        vals = jnp.asarray(ell.vals.reshape(m, B, kw))
        y = jnp.asarray(np.sign(RNG.normal(size=(m, B))).astype(np.float32))
        W = jnp.asarray(RNG.normal(size=(m, d)).astype(np.float32) * 0.1)
        t = jnp.float32(2.0)
        base = hinge_ops.ell_fleet_half_step(W, cols, vals, y, lam=1e-2, t=t,
                                             interpret=True)
        wide = hinge_ops.ell_fleet_half_step(
            W, jnp.pad(cols, ((0, 0), (0, 0), (0, 9))),
            jnp.pad(vals, ((0, 0), (0, 0), (0, 9))),
            y, lam=1e-2, t=t, interpret=True)
        np.testing.assert_allclose(np.asarray(base), np.asarray(wide), atol=1e-6)


class TestObjectiveKernel:
    @pytest.mark.parametrize("m,n_i,k,d,counts", [
        (1, 300, 3, 200, [300]),                 # m = 1, one short tile
        (3, 2500, 76, 1000, [2500, 1200, 7]),    # pad rows, a tile of none
        (2, 2100, 3, 129, [2100, 2099]),         # d one past a lane row
        (4, 640, 3, 4096, [640, 1, 600, 639]),   # d a lane multiple
        (2, 200, 0, 200, [200, 150]),            # k_max = 0: every row empty
    ])
    def test_matches_masked_ell_oracle(self, m, n_i, k, d, counts):
        """The ``ell_objective`` kernel equals ``primal_objective_masked_ell``
        over the flattened planes: n_i past the 2,048-row tile or short of
        it, pad rows past each node's count (y = 0, col 0, val 0, valid
        false) and pad slots (col 0, val 0) in every row's last two
        entries."""
        from repro.core import svm_objective as obj

        rng = np.random.default_rng(n_i)
        cols = rng.integers(0, d, (m, n_i, k)).astype(np.int32)
        vals = rng.normal(size=(m, n_i, k)).astype(np.float32)
        y = np.sign(rng.normal(size=(m, n_i))).astype(np.float32)
        cols[:, :, k - 2:], vals[:, :, k - 2:] = 0, 0.0
        valid = np.arange(n_i)[None, :] < np.asarray(counts)[:, None]
        cols[~valid], vals[~valid], y[~valid] = 0, 0.0, 0.0
        w = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.5)
        n_counts = jnp.asarray(counts, jnp.float32)
        total = jnp.sum(n_counts)

        got = hinge_ops.ell_objective(w, jnp.asarray(cols), jnp.asarray(vals),
                                      jnp.asarray(y), n_counts, lam=1e-3,
                                      total=total, interpret=True)
        want = obj.primal_objective_masked_ell(
            w, jnp.asarray(cols.reshape(m * n_i, k)),
            jnp.asarray(vals.reshape(m * n_i, k)), jnp.asarray(y.reshape(-1)),
            1e-3, jnp.asarray(valid.reshape(-1)), total)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


# ----------------------------------------------------- block-bucketed ELL

class TestBlockBucketing:
    def _planes(self, m, B, k, d, pad_frac=0.3):
        cols = RNG.integers(0, d, size=(m, B, k)).astype(np.int32)
        vals = RNG.normal(size=(m, B, k)).astype(np.float32)
        vals[RNG.random((m, B, k)) < pad_frac] = 0.0
        cols[vals == 0] = 0
        return cols, vals

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 4), st.integers(1, 5), st.integers(1, 9),
           st.integers(8, 300), st.integers(8, 64))
    def test_bucket_by_block_properties(self, m, B, k, d, blk_d):
        """Sorted planes are a permutation; every slice is block-pure; pads
        and sentinel slots are inert; blocks_visited counts live buckets."""
        cols, vals = self._planes(m, B, k, d)
        bb = bucket_by_block(cols, vals, blk_d, d=d)
        n_blk = -(-d // blk_d)
        for i in range(m):
            assert (sorted(zip(bb.cols[i], bb.vals[i]))
                    == sorted(zip(cols[i].reshape(-1), vals[i].reshape(-1))))
            for j in range(bb.n_blocks_max):
                s, e = bb.starts[i, j], bb.starts[i, j + 1]
                if bb.block_ids[i, j] < n_blk:
                    assert np.all(bb.cols[i, s:e] // blk_d == bb.block_ids[i, j])
                    assert np.all(bb.vals[i, s:e] != 0)
                else:
                    assert s == e  # sentinel slot: empty slice
            live = np.unique(cols[i][vals[i] != 0] // blk_d)
            assert bb.blocks_visited()[i] == len(live)
            np.testing.assert_array_equal(
                np.sort(bb.block_ids[i][bb.block_ids[i] < n_blk]), live)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 4), st.integers(1, 5), st.integers(1, 9),
           st.integers(8, 300), st.integers(8, 64), st.integers(1, 12))
    def test_block_map_host_device_agree(self, m, B, k, d, blk_d, extra):
        """formats.block_map and ops.ell_block_map are pinned together,
        including maps wider than the block count (all-sentinel tail)."""
        cols, vals = self._planes(m, B, k, d)
        n_blk = -(-d // blk_d)
        nbm = min(B * k, n_blk) + extra
        host = block_map(cols, vals, blk_d, n_blk, nbm)
        dev = np.asarray(hinge_ops.ell_block_map(
            jnp.asarray(cols), jnp.asarray(vals), blk_d=blk_d,
            n_d_blocks=n_blk, n_blocks_max=nbm))
        np.testing.assert_array_equal(host, dev)

    def test_minibatch_block_bound_sound(self):
        """No B-row draw (with replacement) can exceed the static cap."""
        m, n_i, k, d, blk_d, B = 3, 40, 7, 500, 64, 4
        cols, vals = self._planes(m, n_i, k, d)
        bound = minibatch_block_bound(cols, vals, B, blk_d, d=d)
        n_blk = -(-d // blk_d)
        for _ in range(200):
            i = int(RNG.integers(0, m))
            rows = RNG.integers(0, n_i, size=B)  # with replacement, like _batch_ids
            cc, vv = cols[i][rows], vals[i][rows]
            realized = len(np.unique(cc[vv != 0] // blk_d))
            assert realized <= bound <= n_blk

    def test_row_block_counts_matches_naive(self):
        cols, vals = self._planes(2, 6, 5, 200)
        got = row_block_counts(cols, vals, 32)
        for i in range(2):
            for r in range(6):
                want = len(np.unique(cols[i, r][vals[i, r] != 0] // 32))
                assert got[i, r] == want

    def test_frequency_remap_is_pure_relabeling(self):
        cols, vals = self._planes(2, 8, 6, 120, pad_frac=0.2)
        new_cols, perm = frequency_remap(cols, vals, 120)
        assert np.all(new_cols[vals == 0] == 0)  # pads stay canonical
        # dense matrices agree after permuting columns back
        def dense(c):
            X = np.zeros((16, 120), np.float32)
            np.add.at(X, (np.repeat(np.arange(16), 6),
                          c.reshape(16, 6).reshape(-1)), vals.reshape(-1))
            return X
        np.testing.assert_allclose(dense(cols)[:, perm], dense(new_cols))
        # hot columns got the leading ranks: frequencies are non-increasing
        freq = np.bincount(new_cols.reshape(-1)[vals.reshape(-1) != 0], minlength=120)
        assert np.all(np.diff(freq) <= 0) or freq.max() == freq.min()

    def test_ccat_skew_concentrates_blocks(self):
        """The CCAT spec's Zipf column profile: leading (frequency-ranked)
        columns dominate, so a single-row minibatch touches few d-blocks —
        the structure the prefetch schedule's ≤1/10 acceptance rides on."""
        ds = svm_datasets.make_dataset("ccat", scale=0.0005, seed=0, sparse=True)
        assert np.all(ds.X_train.row_nnz() == 76)  # skew keeps nnz exact
        Pe, yp, nc = svm_datasets.partition(ds.X_train, ds.y_train, 4, seed=0)
        n_blk = -(-Pe.d // 128)
        bound = Pe.block_bound(1)
        assert bound <= n_blk // 10, (bound, n_blk)


# ----------------------------------------------------------------- libsvm

class TestLibsvmStreaming:
    CONTENT = "+1 1:0.5 3:2.0\n-1 2:1.5\n# comment\n+1 3:1.0 4:-0.5\n-1 1:0.25 4:1.0\n"

    def test_csr_loader_matches_dense(self, tmp_path):
        p = tmp_path / "toy.svm"
        p.write_text(self.CONTENT)
        Xd, yd = libsvm.load_libsvm(str(p))
        csr, ys = libsvm.load_libsvm_csr(str(p))
        assert csr.shape == Xd.shape
        np.testing.assert_array_equal(csr.to_dense(), Xd)
        np.testing.assert_array_equal(ys, yd)

    def test_chunked_iter_concatenates(self, tmp_path):
        p = tmp_path / "toy.svm"
        p.write_text(self.CONTENT)
        chunks = list(libsvm.iter_libsvm_chunks(str(p), n_features=4, chunk_rows=2))
        assert len(chunks) == 2 and chunks[0][0].shape == (2, 4)
        X = np.concatenate([c.to_dense() for c, _ in chunks])
        Xd, _ = libsvm.load_libsvm(str(p), n_features=4)
        np.testing.assert_array_equal(X, Xd)
        # streaming loader with explicit d matches too
        csr, _ = libsvm.load_libsvm_csr(str(p), n_features=4, chunk_rows=2)
        np.testing.assert_array_equal(csr.to_dense(), Xd)

    def test_out_of_range_strict_raises(self, tmp_path):
        p = tmp_path / "toy.svm"
        p.write_text("+1 1:1.0 9:2.0\n-1 2:1.0 8:3.0\n")
        for loader in (libsvm.load_libsvm, libsvm.load_libsvm_csr):
            with pytest.raises(ValueError, match="exceeds"):
                loader(str(p), n_features=4, strict=True)

    def test_out_of_range_warns_once_with_count(self, tmp_path):
        p = tmp_path / "toy.svm"
        p.write_text("+1 1:1.0 9:2.0\n-1 2:1.0 8:3.0\n")
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            X, _ = libsvm.load_libsvm(str(p), n_features=4)
        assert X.shape == (2, 4)
        assert len(caught) == 1 and "dropped 2" in str(caught[0].message)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            csr, _ = libsvm.load_libsvm_csr(str(p), n_features=4)
        assert csr.shape == (2, 4) and csr.nnz == 2
        assert len(caught) == 1 and "dropped 2" in str(caught[0].message)


# ------------------------------------------------------ generator / dataset

class TestSparseDatasets:
    def test_generator_realized_nnz_exact(self):
        """Without-replacement sampling: realized nnz hits the spec exactly
        (the with-replacement draw undershot at higher densities)."""
        for name in ("reuters", "mnist"):
            spec = svm_datasets.PAPER_DATASETS[name]
            ds = svm_datasets.make_dataset(name, scale=0.003, seed=1)
            nnz_target = max(1, int(round(spec.sparsity * spec.d)))
            row_nnz = (np.asarray(ds.X_train) != 0).sum(axis=1)
            assert np.all(row_nnz == nnz_target), (name, row_nnz[:5], nnz_target)

    def test_zipf_race_matches_plain_exponential_race(self):
        """The thread-pooled, in-place skewed sampler picks the same columns
        as the plain race over one sequential ``rng.random`` draw (keys
        log(U)·(r+1)^skew, the nnz largest per row), and leaves the stream
        where that draw would (later draws — values, labels — unchanged)."""
        n, d, nnz, skew = 5000, 4096, 40, 1.25    # three 2048-row chunks
        rng = np.random.default_rng(5)
        rng.normal(size=3)
        seq = np.random.default_rng(5)
        seq.normal(size=3)
        got = svm_datasets._zipf_race(rng, n, nnz, d, skew)
        with np.errstate(divide="ignore"):
            keys = np.log(seq.random((n, d), dtype=np.float32))
        keys *= np.arange(1, d + 1, dtype=np.float32) ** np.float32(skew)
        want = np.argpartition(keys, d - nnz, axis=1)[:, d - nnz:]
        np.testing.assert_array_equal(np.sort(got, axis=1),
                                      np.sort(want, axis=1))
        np.testing.assert_array_equal(rng.random(7), seq.random(7))

    def test_sparse_dataset_emits_ell(self):
        spec = svm_datasets.PAPER_DATASETS["reuters"]
        ds = svm_datasets.make_dataset("reuters", scale=0.02, seed=0, sparse=True)
        assert ds.sparse and isinstance(ds.X_train, ELL)
        assert ds.d == spec.d
        nnz_target = max(1, int(round(spec.sparsity * spec.d)))
        assert ds.X_train.k_max == nnz_target
        assert np.all(ds.X_train.row_nnz() == nnz_target)
        assert set(np.unique(ds.y_train)) <= {-1.0, 1.0}
        norms = np.linalg.norm(ds.X_train.vals, axis=1)
        np.testing.assert_allclose(norms, 1.0, atol=1e-4)

    def test_sparse_rejected_for_dense_spec(self):
        with pytest.raises(ValueError, match="dense"):
            svm_datasets.make_dataset("usps", sparse=True)

    def test_partition_ell_matches_dense(self):
        ds = svm_datasets.make_dataset("reuters", scale=0.02, seed=0, sparse=True)
        Xd = ds.X_train.to_dense()
        Pe, yps, ncs = svm_datasets.partition(ds.X_train, ds.y_train, 4, seed=7)
        Xp, ypd, ncd = svm_datasets.partition(Xd, ds.y_train, 4, seed=7)
        assert isinstance(Pe, EllPartitions) and Pe.shape == Xp.shape
        np.testing.assert_array_equal(yps, ypd)
        np.testing.assert_array_equal(ncs, ncd)
        dense_again = np.stack([
            ELL(Pe.cols[i], Pe.vals[i], (Pe.cols.shape[1], Pe.d)).to_dense()
            for i in range(4)])
        np.testing.assert_array_equal(dense_again, Xp)

    def test_partition_csr_input(self):
        X = _random_sparse(33, 40, 6)
        y = np.sign(RNG.normal(size=33)).astype(np.float32)
        Pe, yp, nc = svm_datasets.partition(CSR.from_dense(X), y, 5, seed=1)
        assert isinstance(Pe, EllPartitions)
        assert nc.sum() == 33


# ------------------------------------------------------------- end-to-end

class TestSparseGadget:
    def _reuters_shaped(self, m=5, seed=0):
        ds = svm_datasets.make_dataset("reuters", scale=0.05, seed=seed, sparse=True)
        Pe, yp, nc = svm_datasets.partition(ds.X_train, ds.y_train, m, seed=3)
        Xp, ypd, ncd = svm_datasets.partition(ds.X_train.to_dense(), ds.y_train,
                                              m, seed=3)
        return ds, Pe, Xp, yp, nc

    @pytest.mark.parametrize("topology", ["exponential", "random"])
    def test_sparse_vs_dense_consensus(self, topology):
        """The acceptance bar: same data, same PRNG streams — the sparse path
        must land on the dense path's consensus weights to ≤ 1e-5."""
        ds, Pe, Xp, yp, nc = self._reuters_shaped()
        cfg = GadgetConfig(lam=ds.lam, batch_size=4, gossip_rounds=3,
                           topology=topology, max_iters=200, check_every=50,
                           epsilon=0.0)
        rs = gadget_train(Pe, jnp.asarray(yp), cfg, n_counts=nc)
        rd = gadget_train(jnp.asarray(Xp), jnp.asarray(yp), cfg, n_counts=nc)
        diff = float(jnp.max(jnp.abs(rs.w_consensus - rd.w_consensus)))
        assert diff <= 1e-5, diff
        np.testing.assert_allclose(rs.objective_trace, rd.objective_trace,
                                   atol=1e-5)

    def test_prefetch_schedule_consensus(self):
        """Tentpole acceptance: the touched-block schedule, run through the
        whole device-resident loop (device map + prefetch kernels + bucket
        fold), lands on the dense path's consensus to ≤ 1e-5."""
        ds, Pe, Xp, yp, nc = self._reuters_shaped(m=4)
        cfg = GadgetConfig(lam=ds.lam, batch_size=4, gossip_rounds=2,
                           max_iters=60, check_every=30, epsilon=0.0)
        rd = gadget_train(jnp.asarray(Xp), jnp.asarray(yp), cfg, n_counts=nc)
        rp = gadget_train(Pe, jnp.asarray(yp),
                          cfg._replace(use_kernels=True, sparse_schedule="prefetch"),
                          n_counts=nc)
        assert float(jnp.max(jnp.abs(rp.w_consensus - rd.w_consensus))) <= 1e-5
        # and the sweep schedule agrees with prefetch bit-for-bit-ish
        rs = gadget_train(Pe, jnp.asarray(yp),
                          cfg._replace(use_kernels=True, sparse_schedule="sweep"),
                          n_counts=nc)
        assert float(jnp.max(jnp.abs(rp.W - rs.W))) <= 1e-5

    def test_sparse_kernel_path_matches_jnp_path(self):
        ds, Pe, Xp, yp, nc = self._reuters_shaped(m=4)
        cfg = GadgetConfig(lam=ds.lam, batch_size=4, gossip_rounds=2,
                           max_iters=60, check_every=30, epsilon=0.0)
        rk = gadget_train(Pe, jnp.asarray(yp), cfg._replace(use_kernels=True),
                          n_counts=nc)
        rj = gadget_train(Pe, jnp.asarray(yp), cfg._replace(use_kernels=False),
                          n_counts=nc)
        assert float(jnp.max(jnp.abs(rk.w_consensus - rj.w_consensus))) < 1e-4

    def test_stream_objective_kernel_matches_jnp_path(self):
        """Each segment's objective from the ``ell_objective`` kernel
        (use_kernels=True) is within 1e-6 of the ``jnp.take`` pass's
        (use_kernels=False), on partitions with pad rows."""
        ds = svm_datasets.make_dataset("reuters", scale=0.05, seed=0, sparse=True)
        Pe, yp, nc = svm_datasets.partition(ds.X_train, ds.y_train, 3, seed=3)
        assert len(set(np.asarray(nc).tolist())) > 1  # a node has pad rows
        cfg = GadgetConfig(lam=ds.lam, batch_size=4, gossip_rounds=2,
                           max_iters=60, check_every=30, epsilon=0.0)
        objectives = [
            [s.objective for s in gadget_train_stream(
                Pe, jnp.asarray(yp), cfg._replace(use_kernels=uk),
                segment_iters=20, n_counts=nc)]
            for uk in (True, False)]
        assert len(objectives[0]) == 3
        np.testing.assert_allclose(objectives[0], objectives[1], rtol=1e-6)

    def test_sparse_reference_oracle_agrees(self):
        ds, Pe, Xp, yp, nc = self._reuters_shaped(m=4)
        cfg = GadgetConfig(lam=ds.lam, batch_size=4, gossip_rounds=2,
                           max_iters=80, check_every=40, epsilon=0.0)
        dev = gadget_train(Pe, jnp.asarray(yp), cfg._replace(fused=False),
                           n_counts=nc)
        ref = gadget_train_reference(Pe, jnp.asarray(yp), cfg, n_counts=nc)
        assert float(jnp.max(jnp.abs(dev.W - ref.W))) < 1e-5

    def test_sparse_training_learns(self):
        """Sanity: the sparse path actually fits the training data (at this
        tiny scale d >> n, so held-out accuracy is not meaningful)."""
        from repro.core import svm_objective as obj
        ds, Pe, Xp, yp, nc = self._reuters_shaped()
        cfg = GadgetConfig(lam=ds.lam, batch_size=8, gossip_rounds=3,
                           max_iters=500, check_every=100, epsilon=0.0)
        res = gadget_train(Pe, jnp.asarray(yp), cfg, n_counts=nc)
        Xtr = jnp.asarray(ds.X_train.to_dense())
        acc = float(obj.accuracy(res.w_consensus, Xtr, jnp.asarray(ds.y_train)))
        assert acc > 0.9, acc
        assert res.objective_trace[-1] < res.objective_trace[0]


# ------------------------------------------------------------- mesh path

MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.gadget import GadgetConfig, make_gadget_mesh_step
from repro.data import svm_datasets

m = 4
ds = svm_datasets.make_dataset("reuters", scale=0.02, seed=0, sparse=True)
Pe, yp, nc = svm_datasets.partition(ds.X_train, ds.y_train, m, seed=1)
Xd, _, _ = svm_datasets.partition(ds.X_train.to_dense(), ds.y_train, m, seed=1)
mesh = Mesh(np.array(jax.devices()), ("nodes",))
cfg = GadgetConfig(lam=ds.lam, batch_size=2, gossip_rounds=2)
step_s = make_gadget_mesh_step(
    cfg._replace(use_kernels=True, sparse_schedule="prefetch"), {"nodes": m},
    sparse_block_bound=Pe.block_bound(cfg.batch_size))
step_d = make_gadget_mesh_step(cfg._replace(use_kernels=False), {"nodes": m})

def sharded(step, sparse):
    def per_node(w, c, v, x, y, keys, t):
        X_local = (c[0], v[0]) if sparse else x[0]
        return step(w[0], X_local, y[0], t, keys[0])[None]
    specs = (P("nodes"),) * 6 + (P(),)
    # check_vma=False: no replication rule for pallas_call in shard_map yet
    return jax.shard_map(per_node, mesh=mesh, in_specs=specs,
                         out_specs=P("nodes"), check_vma=False)

cols, vals = jnp.asarray(Pe.cols), jnp.asarray(Pe.vals)
Xd, yj = jnp.asarray(Xd), jnp.asarray(yp)
Ws = Wd = jnp.zeros((m, Pe.d), jnp.float32)
run_s = jax.jit(sharded(step_s, True))
run_d = jax.jit(sharded(step_d, False))
for t in range(1, 4):
    keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(0), t), m)
    Ws = run_s(Ws, cols, vals, Xd, yj, keys, jnp.int32(t))
    Wd = run_d(Wd, cols, vals, Xd, yj, keys, jnp.int32(t))
diff = float(jnp.max(jnp.abs(Ws - Wd)))
assert diff <= 1e-5, f"sparse-vs-dense mesh step diff {diff:.2e}"
assert float(jnp.max(jnp.abs(Ws))) > 0, "mesh step produced all-zero weights"
print(f"MESH_SPARSE_OK diff={diff:.2e}")
"""


class TestMeshSparse:
    def test_mesh_step_sparse_vs_dense_multidevice(self, tmp_path):
        """Node-sharded ELL planes inside shard_map (4 forced CPU devices,
        subprocess so the flag cannot leak): the sparse prefetch-kernel mesh
        step matches the dense jnp mesh step on the same data and keys."""
        import subprocess
        import sys
        script = tmp_path / "mesh_sparse.py"
        script.write_text(MESH_SCRIPT)
        repo = __file__.rsplit("/tests/", 1)[0]
        env = {**__import__("os").environ, "PYTHONPATH": f"{repo}/src"}
        p = subprocess.run([sys.executable, str(script)], capture_output=True,
                           text=True, timeout=540, env=env)
        assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
        assert "MESH_SPARSE_OK" in p.stdout

    def test_mesh_step_single_device_axis(self):
        """Axis size 1 (this process's real device count): no neighbors, so
        the step is just the local sparse half-step — and it runs the ELL
        kernels inside shard_map without a mesh-collective in sight."""
        import jax
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core.gadget import make_gadget_mesh_step

        ds = svm_datasets.make_dataset("reuters", scale=0.02, seed=0, sparse=True)
        Pe, yp, nc = svm_datasets.partition(ds.X_train, ds.y_train, 1, seed=1)
        cfg = GadgetConfig(lam=ds.lam, batch_size=3, gossip_rounds=2,
                           use_kernels=True, sparse_schedule="prefetch")
        step = make_gadget_mesh_step(cfg, {"nodes": 1},
                                     sparse_block_bound=Pe.block_bound(3))
        mesh = Mesh(np.array(jax.devices()[:1]), ("nodes",))
        cols, vals = jnp.asarray(Pe.cols[0]), jnp.asarray(Pe.vals[0])
        y0 = jnp.asarray(yp[0])
        w0 = jnp.zeros((Pe.d,), jnp.float32)
        key = jax.random.PRNGKey(7)
        f = jax.shard_map(
            lambda w, c, v, y, k: step(w, (c, v), y, jnp.int32(1), k),
            mesh=mesh, in_specs=(P(), P(), P(), P(), P()), out_specs=P(),
            check_vma=False)
        got = jax.jit(f)(w0, cols, vals, y0, key)
        want = step(w0, (cols, vals), y0, jnp.int32(1), key)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
        assert float(jnp.max(jnp.abs(got))) > 0
