"""Flight-recorder telemetry: registry primitives, histogram properties
(hypothesis — merge associativity, quantile bounds vs a sorted-array oracle),
exporters + dump CLI, kernel launch accounting, the batcher soak (flat
memory), and the load-bearing guarantee that ``telemetry=None`` traces the
exact pre-telemetry training program (bit-identical trajectories on the
dense, sparse, faulty, and streaming paths)."""
import math
import os
import subprocess
import sys
import threading
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
from repro import telemetry as tm
from repro.core.faults import FaultPlan
from repro.core.gadget import GadgetConfig, gadget_train, gadget_train_stream
from repro.data import svm_datasets
from repro.kernels.hinge_subgrad import ops as hinge_ops
from repro.serve import batcher as bat
from repro.telemetry import dump as tm_dump
from repro.telemetry.registry import Histogram, Registry

REPO = Path(__file__).resolve().parent.parent


def _toy_parts(m=4, n_i=16, d=24, seed=0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=d)
    X = rng.normal(size=(m * n_i, d)).astype(np.float32)
    y = np.sign(X @ w_true).astype(np.float32)
    return jnp.asarray(X.reshape(m, n_i, d)), jnp.asarray(y.reshape(m, n_i))


def _cfg(**kw):
    base = dict(lam=1e-2, batch_size=2, gossip_rounds=2, max_iters=16,
                check_every=4, epsilon=0.0, use_kernels=False)
    base.update(kw)
    return GadgetConfig(**base)


def _hist(**kw):
    base = dict(base=1e-4, growth=2.0 ** 0.25, n_buckets=96)
    base.update(kw)
    return Histogram("h", {}, threading.RLock(), **base)


# ---------------------------------------------------------------------------
# Registry primitives
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_gauge_basics(self):
        reg = Registry()
        reg.counter("a").inc().inc(2.5)
        assert reg.value("a") == 3.5
        with pytest.raises(ValueError):
            reg.counter("a").inc(-1)
        reg.gauge("g").set(4.0)
        reg.gauge("g").inc(-1.5)
        assert reg.value("g") == 2.5

    def test_labels_key_distinct_series_and_identity(self):
        reg = Registry()
        a = reg.counter("kernel.launches", kernel="dense_predict").inc()
        b = reg.counter("kernel.launches", kernel="ell_predict").inc(5)
        assert a is reg.counter("kernel.launches", kernel="dense_predict")
        assert a is not b
        assert reg.values() == {
            "kernel.launches{kernel=dense_predict}": 1.0,
            "kernel.launches{kernel=ell_predict}": 5.0,
        }

    def test_kind_mismatch_rejected(self):
        reg = Registry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_value_defaults_zero_and_reset(self):
        reg = Registry()
        assert reg.value("never.touched") == 0.0
        reg.counter("x").inc()
        reg.reset()
        assert reg.get("x") is None

    def test_span_times_into_histogram_and_emits(self):
        t = [0.0]

        def clock():
            t[0] += 0.25
            return t[0]

        events = []
        reg = Registry(clock=clock)
        reg.attach_sink(type("S", (), {"emit": staticmethod(events.append)}))
        with reg.span("phase.seconds", step=3) as sp:
            pass
        assert sp.seconds == pytest.approx(0.25)
        assert reg.get("phase.seconds").count == 1
        (ev,) = events
        assert ev["kind"] == "span" and ev["fields"] == {"step": 3}
        assert "ts" in ev
        reg.detach_sink()
        with reg.span("phase.seconds"):
            pass
        assert len(events) == 1

    def test_default_registry_conveniences(self):
        tm.reset()
        tm.counter("c").inc(2)
        tm.gauge("g").set(1.0)
        assert tm.default_registry().values() == {"c": 2.0, "g": 1.0}
        tm.reset()


# ---------------------------------------------------------------------------
# Histogram properties (hypothesis)
# ---------------------------------------------------------------------------


class TestHistogram:
    def test_ladder_validation(self):
        with pytest.raises(ValueError):
            _hist(base=0.0)
        with pytest.raises(ValueError):
            _hist(growth=1.0)
        with pytest.raises(ValueError):
            _hist(n_buckets=1)

    def test_empty_reads(self):
        h = _hist()
        assert math.isnan(h.quantile(0.5)) and math.isnan(h.value)
        assert h.count == 0 and h.min == math.inf and h.max == -math.inf

    @given(st.integers(2, 90))
    def test_edges_belong_to_bucket_below(self, j):
        h = _hist()
        edge = h.upper_edge(j)
        assert h.bucket_index(edge) == j
        assert h.bucket_index(edge * 1.0001) == j + 1

    @given(st.integers(0, 2 ** 31 - 1), st.integers(1, 400))
    @settings(max_examples=30, deadline=None)
    def test_quantile_brackets_sorted_oracle(self, seed, n):
        """For every quantile: oracle <= histogram <= oracle * growth, with
        the two documented exceptions (bucket 0 reports ``base``, overflow
        reports the exact tracked max)."""
        rng = np.random.default_rng(seed)
        samples = rng.lognormal(mean=-2.0, sigma=3.0, size=n)
        h = _hist()
        for v in samples:
            h.observe(v)
        s = np.sort(samples)
        for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0):
            oracle = float(s[max(1, math.ceil(q * n)) - 1])
            got = h.quantile(q)
            assert oracle <= got * (1 + 1e-9), (q, oracle, got)
            if got == h.base:
                assert oracle <= h.base
            elif got == h.max and h.bucket_index(h.max) == h.n_buckets - 1:
                pass  # overflow: exact max, arbitrarily far above the edge
            else:
                assert got <= oracle * h.growth * (1 + 1e-9), (q, oracle, got)

    @given(st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=25, deadline=None)
    def test_merge_associative_commutative_exact(self, seed):
        rng = np.random.default_rng(seed)
        parts = []
        for _ in range(3):
            h = _hist()
            for v in rng.lognormal(mean=-1.0, sigma=2.5,
                                   size=int(rng.integers(1, 60))):
                h.observe(v)
            parts.append(h)
        a, b, c = parts
        left = a.copy().merge(b).merge(c)
        right = a.copy().merge(b.copy().merge(c))
        swapped = c.copy().merge(a).merge(b)
        for other in (right, swapped):
            assert left._counts == other._counts
            assert left.count == other.count
            assert left.min == other.min and left.max == other.max
            assert left.sum == pytest.approx(other.sum)
        assert left.count == a.count + b.count + c.count

    def test_merge_rejects_different_ladders(self):
        with pytest.raises(ValueError):
            _hist().merge(_hist(n_buckets=64))

    def test_overflow_quantile_is_exact_max(self):
        h = _hist(n_buckets=8)
        top = h.upper_edge(h.n_buckets - 2)
        h.observe(top * 1e6)
        assert h.quantile(0.99) == top * 1e6

    def test_to_dict_roundtrip_shape(self):
        h = _hist()
        for v in (1e-5, 1e-3, 1e6):
            h.observe(v)
        d = h.to_dict()
        assert d["count"] == 3 and d["max"] == 1e6
        assert sum(n for _, n in d["buckets"]) == 3
        assert d["buckets"][-1][0] is None  # overflow le


# ---------------------------------------------------------------------------
# Exporters + dump CLI
# ---------------------------------------------------------------------------


def _sample_registry():
    reg = Registry()
    reg.counter("train.iterations").inc(40)
    reg.counter("kernel.launches", kernel="dense_predict").inc(3)
    reg.gauge("train.objective").set(1.25)
    h = reg.histogram("serve.latency_seconds", bucket="all")
    for v in (1e-4, 2e-3, 0.5):
        h.observe(v)
    return reg


class TestExport:
    def test_prometheus_text(self):
        text = tm.to_prometheus(_sample_registry())
        assert "# TYPE repro_train_iterations_total counter" in text
        assert "repro_train_iterations_total 40.0" in text
        assert 'repro_kernel_launches_total{kernel="dense_predict"} 3.0' in text
        assert "repro_train_objective 1.25" in text
        assert 'le="+Inf"' in text
        assert 'repro_serve_latency_seconds_count{bucket="all"} 3' in text
        # cumulative buckets are non-decreasing
        cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
                if line.startswith("repro_serve_latency_seconds_bucket")]
        assert cums == sorted(cums) and cums[-1] == 3

    def test_jsonl_roundtrip_and_schema(self, tmp_path):
        path = tmp_path / "t.jsonl"
        n = tm.dump_jsonl(_sample_registry(), path, ts=123.0)
        recs = tm.read_jsonl(path)
        assert len(recs) == n == 4
        assert {r["kind"] for r in recs} == {"counter", "gauge", "histogram"}
        assert all(r["ts"] == 123.0 for r in recs)
        env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
        proc = subprocess.run(
            [sys.executable, str(REPO / "tools" / "check_telemetry_schema.py"),
             "--selftest", str(path)],
            capture_output=True, text=True, env=env)
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_jsonl_sink_streams_spans(self, tmp_path):
        path = tmp_path / "events.jsonl"
        reg = Registry()
        with tm.JsonlSink(path) as sink:
            reg.attach_sink(sink)
            with reg.span("publish.seconds", iteration=7):
                pass
        (rec,) = tm.read_jsonl(path)
        assert rec["kind"] == "span" and rec["fields"] == {"iteration": 7}
        assert rec["seconds"] >= 0

    def test_dump_cli(self, tmp_path, capsys):
        path = tmp_path / "t.jsonl"
        tm.dump_jsonl(_sample_registry(), path, ts=5.0)
        assert tm_dump.main([str(path)]) == 0
        out = capsys.readouterr().out
        assert "train.iterations" in out and "serve.latency_seconds" in out
        prom = tmp_path / "snap.prom"
        assert tm_dump.main([str(path), "--prometheus", str(prom)]) == 0
        assert "repro_train_iterations_total 40.0" in prom.read_text()


# ---------------------------------------------------------------------------
# Training telemetry: bit-identity + trace decoding
# ---------------------------------------------------------------------------


class TestTrainTelemetry:
    def test_validate(self):
        assert tm.validate_telemetry(None) is None
        with pytest.raises(ValueError):
            tm.validate_telemetry(tm.TrainTelemetry(every=0))
        with pytest.raises(ValueError):
            tm.validate_telemetry(tm.TrainTelemetry(slots=0))

    def _assert_bit_identical(self, r_on, r_off):
        assert np.array_equal(np.asarray(r_on.W), np.asarray(r_off.W))
        assert np.array_equal(np.asarray(r_on.w_consensus),
                              np.asarray(r_off.w_consensus))
        assert np.array_equal(np.asarray(r_on.objective_trace),
                              np.asarray(r_off.objective_trace))
        assert r_on.iters == r_off.iters

    def test_dense_bit_identical_and_trace(self):
        X, y = _toy_parts()
        cfg = _cfg()
        r_off = gadget_train(X, y, cfg)
        r_on = gadget_train(X, y, cfg, telemetry=tm.TrainTelemetry())
        self._assert_bit_identical(r_on, r_off)
        assert r_off.telemetry is None
        tr = r_on.telemetry
        assert tr.count == cfg.max_iters  # every=1, slots=256: nothing lost
        assert list(tr.iterations) == sorted(tr.iterations)
        assert np.all(np.asarray(tr.drops) == 0)  # no FaultPlan, no drops
        assert np.all(np.isfinite(np.asarray(tr.objective)))
        assert tr.final_iteration == r_on.iters
        assert tr.final_disagreement >= 0.0

    def test_faulty_bit_identical_and_leakage_visible(self):
        X, y = _toy_parts()
        cfg = _cfg(faults=FaultPlan(drop_prob=0.3, drop="message", seed=5))
        r_off = gadget_train(X, y, cfg)
        tele = tm.TrainTelemetry(every=1, slots=cfg.max_iters)
        r_on = gadget_train(X, y, cfg, telemetry=tele)
        self._assert_bit_identical(r_on, r_off)
        tr = r_on.telemetry
        assert tr.count == cfg.max_iters
        assert int(np.sum(tr.drops)) > 0
        assert float(np.min(tr.mass_min)) < 1.0  # message mode leaks mass

    def test_sparse_bit_identical(self):
        ds = svm_datasets.make_dataset("reuters", scale=0.03, seed=0,
                                       sparse=True)
        Pe, yp, nc = svm_datasets.partition(ds.X_train, ds.y_train, 4, seed=3)
        cfg = _cfg(lam=ds.lam, max_iters=8, check_every=4)
        r_off = gadget_train(Pe, jnp.asarray(yp), cfg, n_counts=nc)
        r_on = gadget_train(Pe, jnp.asarray(yp), cfg, n_counts=nc,
                            telemetry=tm.TrainTelemetry())
        self._assert_bit_identical(r_on, r_off)

    def test_stream_bit_identical_and_segment_drops_match_ring(self):
        X, y = _toy_parts()
        cfg = _cfg(faults=FaultPlan(drop_prob=0.2, drop="message", seed=9))
        segs_off = list(gadget_train_stream(X, y, cfg, segment_iters=4))
        segs_on = list(gadget_train_stream(X, y, cfg, segment_iters=4,
                                           telemetry=tm.TrainTelemetry()))
        assert len(segs_on) == len(segs_off)
        for s_on, s_off in zip(segs_on, segs_off):
            assert np.array_equal(np.asarray(s_on.W), np.asarray(s_off.W))
            assert s_off.telemetry is None and s_on.telemetry is not None
            assert s_on.telemetry.mass_min <= s_on.telemetry.mass_max <= 1.0
        ring = gadget_train(X, y, cfg,
                            telemetry=tm.TrainTelemetry(
                                every=1, slots=cfg.max_iters)).telemetry
        assert int(np.sum(ring.drops)) == sum(
            s.telemetry.drops for s in segs_on)

    def test_ring_wraps_keep_latest(self):
        X, y = _toy_parts()
        cfg = _cfg(max_iters=12)
        tr = gadget_train(X, y, cfg,
                          telemetry=tm.TrainTelemetry(every=1,
                                                      slots=5)).telemetry
        assert tr.count == 5
        assert list(tr.iterations) == [8, 9, 10, 11, 12]

    def test_publish_trace_writes_gauges(self):
        X, y = _toy_parts()
        reg = Registry()
        tr = gadget_train(X, y, _cfg(),
                          telemetry=tm.TrainTelemetry()).telemetry
        tm.publish_trace(tr, registry=reg)
        assert reg.value("train.final_disagreement") == tr.final_disagreement
        assert reg.value("train.objective") == tr.objective[-1]
        assert reg.value("train.fault_drops") == 0

    def test_train_registry_accounting(self):
        tm.reset()
        X, y = _toy_parts()
        gadget_train(X, y, _cfg(max_iters=8, check_every=8))
        reg = tm.default_registry()
        assert reg.value("train.iterations") == 8
        assert reg.value("train.gossip_bytes") > 0
        tm.reset()


# ---------------------------------------------------------------------------
# Per-node telemetry leaves (observatory inputs)
# ---------------------------------------------------------------------------


class TestPerNodeTelemetry:
    def test_default_carries_no_node_rings(self):
        X, y = _toy_parts()
        tr = gadget_train(X, y, _cfg(),
                          telemetry=tm.TrainTelemetry()).telemetry
        assert tr.node_disagreement is None
        assert tr.node_mass is None and tr.node_drops is None

    def test_per_node_bit_identical_and_decode_matches_host(self):
        """per_node=True perturbs nothing (bit-identical trajectory) and the
        decoded leaves agree with host references: row-max of the per-node
        disagreement IS the scalar ring, the final row matches
        ``||W_i - w_consensus||`` within 1e-5, and fault-free mass is
        exactly 1 everywhere."""
        X, y = _toy_parts()
        cfg = _cfg(check_every=1)
        r_off = gadget_train(X, y, cfg)
        r_on = gadget_train(X, y, cfg,
                            telemetry=tm.TrainTelemetry(
                                every=1, slots=cfg.max_iters, per_node=True))
        assert np.array_equal(np.asarray(r_on.W), np.asarray(r_off.W))
        assert np.array_equal(np.asarray(r_on.w_consensus),
                              np.asarray(r_off.w_consensus))
        tr = r_on.telemetry
        assert tr.node_disagreement.shape == (cfg.max_iters, 4)
        np.testing.assert_array_equal(tr.node_disagreement.max(axis=1),
                                      np.asarray(tr.disagreement))
        host_ref = np.linalg.norm(
            np.asarray(r_on.W, np.float64)
            - np.asarray(r_on.w_consensus, np.float64), axis=1)
        np.testing.assert_allclose(tr.node_disagreement[-1], host_ref,
                                   atol=1e-5)
        np.testing.assert_array_equal(tr.node_mass,
                                      np.ones_like(tr.node_mass))
        assert not tr.node_drops.any()

    def test_per_node_drop_rows_sum_to_scalar_ring(self):
        X, y = _toy_parts()
        cfg = _cfg(check_every=1,
                   faults=FaultPlan(drop_prob=0.3, drop="message", seed=5))
        tr = gadget_train(X, y, cfg,
                          telemetry=tm.TrainTelemetry(
                              every=1, slots=cfg.max_iters,
                              per_node=True)).telemetry
        assert int(np.sum(tr.node_drops)) > 0
        np.testing.assert_array_equal(tr.node_drops.sum(axis=1),
                                      np.asarray(tr.drops))
        # message drops destroy mass somewhere in the fleet
        assert float(tr.node_mass.min()) < 1.0


# ---------------------------------------------------------------------------
# Kernel accounting
# ---------------------------------------------------------------------------


class TestKernelAccounting:
    def test_launch_cost_local(self):
        cost = hinge_ops.launch_cost("local_half_step", B=4, d=8)
        assert cost == {"launches": 2, "bytes": 400, "flops": 144}

    def test_launch_cost_unknown_kind(self):
        with pytest.raises(ValueError):
            hinge_ops.launch_cost("warp_drive")

    def test_record_launch_increments(self):
        reg = Registry()
        hinge_ops.record_launch("local_half_step", 3, registry=reg, B=4, d=8)
        assert reg.value("kernel.launches", kernel="local_half_step") == 6
        assert reg.value("kernel.bytes", kernel="local_half_step") == 1200
        hinge_ops.record_launch("ell_predict", registry=reg,
                                blocks_visited=2, B=4, k=3, C=2, blk_d=8,
                                n_blocks_max=6)
        assert reg.value("kernel.blocks_visited", kernel="ell_predict") == 2

    def test_maybe_record_skips_under_trace(self):
        tm.reset()

        def f(x):
            hinge_ops._maybe_record("local_half_step", x, B=2, d=4)
            return x

        jax.jit(f)(jnp.ones(3))  # traced probe: no side effect
        assert tm.default_registry().get("kernel.launches",
                                         kernel="local_half_step") is None
        f(np.ones(3))  # eager probe: records
        assert tm.default_registry().value(
            "kernel.launches", kernel="local_half_step") == 2
        tm.reset()


# ---------------------------------------------------------------------------
# Batcher soak: bounded memory, histogram-backed stats
# ---------------------------------------------------------------------------


class TestBatcherSoak:
    def test_soak_flat_memory_over_10k_submits(self):
        t = [0.0]

        def clock():
            t[0] += 1e-4
            return t[0]

        buckets = (bat.Bucket(4, 4, 2), bat.Bucket(4, 8, 4))
        mb = bat.MicroBatcher(buckets, clock)

        def score_fn(b, cols, vals):
            return (np.zeros(b.rows, np.float32), np.zeros(b.rows, np.int32))

        rng = np.random.default_rng(0)

        def footprint():
            return (len(mb.registry._series),
                    tuple(len(h._counts) for _, _, h in mb.registry.series()
                          if h.kind == "histogram"))

        baseline = None
        for chunk in range(100):
            for _ in range(100):
                nnz = int(rng.integers(1, 8))
                mb.submit(np.arange(nnz), np.ones(nnz))
            mb.drain(score_fn)
            if chunk == 4:
                baseline = footprint()
        # the old bug: a per-request list grew forever. Now the only state
        # is fixed-size histograms — the series census after 10k submits is
        # identical to the one after 500.
        assert footprint() == baseline
        assert not hasattr(mb, "_done")
        assert mb.pending == 0 and not mb._undelivered
        st_ = mb.stats()
        assert st_["requests"] == 10_000
        assert 0 < st_["latency_p50_ms"] <= st_["latency_p90_ms"] \
            <= st_["latency_p99_ms"]
        per = st_["per_bucket_latency_ms"]
        assert set(per) == {"k4", "k8"}
        assert sum(v["count"] for v in per.values()) == 10_000

    def test_stats_backcompat_keys(self):
        mb = bat.MicroBatcher((bat.Bucket(2, 4, 2),))
        for key in ("requests", "batches", "padded_rows", "pad_fraction",
                    "latency_p50_ms", "latency_p99_ms", "queries_per_sec",
                    "drain_seconds"):
            assert key in mb.stats()
        assert math.isnan(mb.stats()["latency_p50_ms"])  # nothing drained

    def test_shared_registry_folds_series(self):
        reg = Registry()
        mb = bat.MicroBatcher((bat.Bucket(2, 4, 2),), registry=reg)
        mb.submit([0, 1], [1.0, 1.0])
        mb.drain(lambda b, c, v: (np.zeros(b.rows, np.float32),
                                  np.zeros(b.rows, np.int32)))
        assert reg.value("serve.batches", bucket="k4") == 1
        assert reg.get("serve.latency_seconds", bucket="all").count == 1


# ---------------------------------------------------------------------------
# Names in the chip trace: device scopes, kernel names, profiler spans
# ---------------------------------------------------------------------------

GADGET_SCOPES = ("gadget.half_step", "gadget.project", "gadget.push_sum_mix",
                 "gadget.average", "gadget.eps_check", "gadget.consensus",
                 "gadget.objective")


def _pallas_names(jaxpr) -> list:
    """Names of every ``pallas_call`` in a jaxpr, in order."""
    return [eqn.params["name"] for eqn in jaxpr.eqns
            if eqn.primitive.name == "pallas_call"]


class TestTraceNames:
    @pytest.mark.parametrize("sparse", [False, True])
    def test_lowered_segment_carries_every_scope(self, sparse):
        from repro.core import gadget as gd
        from repro.sparse.formats import EllPartitions

        X, y = _toy_parts()
        if sparse:
            rng = np.random.default_rng(1)
            cols = rng.integers(0, 24, size=(4, 16, 3)).astype(np.int32)
            X = EllPartitions(cols, rng.random((4, 16, 3)).astype(np.float32), 24)
        cfg = GadgetConfig(lam=1e-2, batch_size=2, gossip_rounds=2, max_iters=8,
                           epsilon=0.0, topology="random", use_kernels=False)
        Xd, m, n_i, d, _ = gd._unpack_partitions(X)
        seg = gd._make_segment_train(cfg, m, n_i, d, 4)
        data_key, mix_key = gd._stream_keys(0)
        W = jnp.zeros((m, d))
        text = seg.lower(Xd, y, None, data_key, mix_key, jnp.full((m,), 16.0),
                         W, W, jnp.int32(1)).as_text(debug_info=True)
        for scope in GADGET_SCOPES:
            assert scope in text, scope

    def test_kernel_entry_points_name_their_pallas_calls(self):
        t = jnp.float32(1.0)
        W = jnp.zeros((2, 300))
        X = jnp.ones((2, 1, 300))
        cols = jnp.zeros((2, 1, 6), jnp.int32)
        vals = jnp.ones((2, 1, 6))
        y = jnp.ones((2, 1))
        qc, qv = jnp.zeros((3, 6), jnp.int32), jnp.ones((3, 6))
        cases = {
            "fleet_half_step": (lambda: hinge_ops.fleet_half_step(
                W, X, y, lam=0.1, t=t, interpret=True), ["fleet_half_step"]),
            "local_half_step": (lambda: hinge_ops.local_half_step(
                W[0], X[0], y[0], lam=0.1, t=t, interpret=True),
                ["local_half_step_margins", "local_half_step_update"]),
            "ell_prefetch": (lambda: hinge_ops.ell_fleet_half_step(
                W, cols, vals, y, lam=0.1, t=t, schedule="prefetch", interpret=True),
                ["ell_fleet_half_step_gather", "ell_fleet_half_step_update"]),
            "ell_sweep": (lambda: hinge_ops.ell_fleet_half_step(
                W, cols, vals, y, lam=0.1, t=t, schedule="sweep", interpret=True),
                ["ell_fleet_half_step_sweep_gather", "ell_fleet_half_step_sweep_update"]),
            "dense_predict": (lambda: hinge_ops.dense_predict(
                W[0], X[0], interpret=True), ["dense_predict"]),
            "ell_predict": (lambda: hinge_ops.ell_predict(
                W[0], qc, qv, interpret=True), ["ell_predict"]),
        }
        for case, (fn, names) in cases.items():
            assert _pallas_names(jax.make_jaxpr(fn)().jaxpr) == names, case

    def test_segment_spans_land_in_the_profiler_trace(self, tmp_path):
        from jax.profiler import ProfileData

        X, y = _toy_parts()
        cfg = GadgetConfig(lam=1e-2, batch_size=2, gossip_rounds=2,
                           max_iters=8, epsilon=0.0, use_kernels=False)
        list(gadget_train_stream(X, y, cfg, segment_iters=4))   # compile
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            list(gadget_train_stream(X, y, cfg, segment_iters=4,
                                     trace_registry=Registry()))
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.glob("**/*.xplane.pb")
        events = [(ev.name, dict(ev.stats)) for plane in ProfileData.from_file(str(path)).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith("train.")]
        names = [n for n, _ in events]
        assert names.count("train.segment") == 2
        assert names.count("train.readback") == 10
        for child in ("train.segment.dispatch", "train.segment.wait",
                      "train.segment.account"):
            assert names.count(child) == 2
        assert {st.get("what") for n, st in events if n == "train.readback"} \
            == {"t", "w_consensus", "epsilon", "objective", "mass"}
