"""Causal tracing + gossip health observatory (docs/ARCHITECTURE.md §10).

Covers the explicit-propagation ``TraceContext`` (cross-thread linkage, no
thread-locals), exception-path span closure (score_fn raise, publisher
OSError) with the ``error`` attribute, the version-lineage chain
train.segment → publish → swap → first-score end to end (including
publisher retries keeping one trace_id, quarantined reloads closing the
swap span with ``error="quarantined"``, and kill-and-resume linking the
fresh trace to the pre-crash lineage), sampled request-fate traces with
reservoir retention, the lineage CLI, the observatory's
straggler/dead/mass-leak flags, and the top console's frames.
"""
import json
import threading

import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro import serve
from repro import telemetry as tm
from repro.checkpoint import io as ckpt_io
from repro.core.faults import FaultPlan
from repro.core.gadget import (GadgetConfig, TrainState, gadget_train,
                               gadget_train_stream)
from repro.serve import MicroBatcher, SvmServer, TrainPublisher
from repro.serve.snapshot import Snapshot, to_checkpoint
from repro.telemetry import top as tmtop
from repro.telemetry import trace as tmtr
from repro.telemetry.registry import Registry

RNG = np.random.default_rng(0)


def _toy_parts(m=3, n_i=20, d=32, seed=0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=d)
    X = rng.normal(size=(m * n_i, d)).astype(np.float32)
    y = np.sign(X @ w_true).astype(np.float32)
    return X.reshape(m, n_i, d), y.reshape(m, n_i)


def _toy_cfg(max_iters=10, **kw):
    base = dict(lam=1e-3, batch_size=3, gossip_rounds=2, max_iters=max_iters,
                check_every=5, epsilon=0.0, use_kernels=False)
    base.update(kw)
    return GadgetConfig(**base)


def _sinked_registry(tmp_path, name="trace.jsonl"):
    """Registry streaming span/event records to a JSONL file."""
    path = tmp_path / name
    reg = Registry()
    reg.attach_sink(tm.JsonlSink(path))
    return reg, path


def _records(reg, path):
    reg.detach_sink()
    return tm.read_jsonl(path)


def _buckets(rows=2, k=4):
    return (serve.Bucket(rows, k, rows * k),)


def _query(nnz=2, d=64, rng=RNG):
    cols = np.sort(rng.choice(d, size=nnz, replace=False)).astype(np.int32)
    return cols, rng.normal(size=nnz).astype(np.float32)


def _ok(b, cols, vals):
    return np.zeros(b.rows), np.ones(b.rows)


# ---------------------------------------------------------------------------
# TraceContext: explicit propagation
# ---------------------------------------------------------------------------


class TestTraceContext:
    def test_child_links_parent_same_trace(self):
        root = tmtr.TraceContext.new()
        assert root.parent_id is None
        child = root.child()
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id
        assert child.span_id != root.span_id
        grand = child.child()
        assert grand.trace_id == root.trace_id
        assert grand.parent_id == child.span_id

    def test_extra_roundtrip_and_malformed(self):
        root = tmtr.TraceContext.new()
        assert tmtr.TraceContext.from_extra(root.to_extra()) == root
        assert tmtr.TraceContext.from_extra(None) is None
        assert tmtr.TraceContext.from_extra("t1/s1") is None
        assert tmtr.TraceContext.from_extra({"trace_id": "t"}) is None
        assert tmtr.TraceContext.from_extra(
            {"trace_id": "", "span_id": "s"}) is None

    def test_cross_thread_propagation(self, tmp_path):
        """A context handed explicitly to another thread emits spans into
        the same trace with correct parent linkage — the publisher-thread /
        watch-thread / drain-loop pattern (no thread-locals to diverge)."""
        reg, path = _sinked_registry(tmp_path)
        root = tmtr.TraceContext.new()
        tmtr.emit_span(reg, "train.segment", root, 0.25, iteration=5)

        def worker(ctx):
            tmtr.emit_span(reg, "publish.seconds", ctx.child(), 0.01,
                           iteration=5)

        t = threading.Thread(target=worker, args=(root,))
        t.start()
        t.join()
        recs = _records(reg, path)
        seg = next(r for r in recs if r["name"] == "train.segment")
        pub = next(r for r in recs if r["name"] == "publish.seconds")
        assert pub["trace_id"] == seg["trace_id"] == root.trace_id
        assert pub["parent_id"] == seg["span_id"] == root.span_id


# ---------------------------------------------------------------------------
# TracedSpan: exception-path closure
# ---------------------------------------------------------------------------


class TestTracedSpan:
    def test_closes_on_exception_with_error_attr(self, tmp_path):
        reg, path = _sinked_registry(tmp_path)
        ctx = tmtr.TraceContext.new()
        with pytest.raises(RuntimeError):
            with tmtr.TracedSpan(reg, "serve.score.seconds", ctx, bucket="k4"):
                raise RuntimeError("boom")
        (rec,) = _records(reg, path)
        assert rec["kind"] == "span" and rec["seconds"] >= 0
        assert rec["fields"]["error"] == "RuntimeError: boom"
        assert rec["fields"]["bucket"] == "k4"
        # the histogram observed the failed phase too
        assert reg.histogram("serve.score.seconds").count == 1

    def test_success_has_no_error_attr(self, tmp_path):
        reg, path = _sinked_registry(tmp_path)
        with tmtr.TracedSpan(reg, "x.seconds", tmtr.TraceContext.new()) as sp:
            pass
        assert sp.seconds is not None and sp.seconds >= 0
        (rec,) = _records(reg, path)
        assert "error" not in rec["fields"]

    def test_score_fn_raise_closes_span_and_request_traces(self, tmp_path):
        """Regression: a score_fn raise inside drain still closes the batch
        span (error attr) and does not orphan the traced requests."""
        reg, path = _sinked_registry(tmp_path)
        tracer = tmtr.RequestTracer(reg, sample=1.0)
        mb = MicroBatcher(_buckets(), registry=reg, tracer=tracer)
        for _ in range(2):
            mb.submit(*_query())

        def bomb(b, cols, vals):
            raise RuntimeError("scorer exploded")

        with pytest.raises(RuntimeError):
            mb.drain(bomb)
        recs = _records(reg, path)
        span = next(r for r in recs if r["name"] == "serve.score.seconds")
        assert span["fields"]["error"] == "RuntimeError: scorer exploded"


# ---------------------------------------------------------------------------
# Segment-boundary spans of gadget_train_stream
# ---------------------------------------------------------------------------

SEGMENT_CHILDREN = ("train.segment.dispatch", "train.segment.wait",
                    "train.readback", "train.segment.account")


class TestSegmentSpans:
    def _run(self, tmp_path, **kw):
        X, y = _toy_parts()
        reg, path = _sinked_registry(tmp_path)
        segs = list(gadget_train_stream(X, y, _toy_cfg(max_iters=10),
                                        segment_iters=5, trace=True,
                                        trace_registry=reg, **kw))
        return segs, reg, _records(reg, path)

    def test_segment_holds_its_four_kinds_of_child_span(self, tmp_path):
        segs, _, recs = self._run(tmp_path)
        roots = [r for r in recs if r["name"] == "train.segment"]
        assert len(roots) == len(segs) == 2
        for seg, root in zip(segs, roots):
            assert root["trace_id"] == seg.trace.trace_id
            assert root["span_id"] == seg.trace.span_id
            assert "parent_id" not in root
            kids = [r for r in recs if r.get("parent_id") == root["span_id"]]
            assert {r["name"] for r in kids} == set(SEGMENT_CHILDREN)
            assert all(r["trace_id"] == root["trace_id"] for r in kids)
            # nested: every child's time lies inside the segment's
            assert sum(r["seconds"] for r in kids) <= root["seconds"]
            assert all(r["ts"] <= root["ts"] for r in kids)
            reads = [r["fields"]["what"] for r in kids if r["name"] == "train.readback"]
            assert reads == ["t", "w_consensus", "epsilon", "objective", "mass"]
            assert root["fields"]["iteration"] == seg.iteration

    def test_readback_counter_counts_readback_spans(self, tmp_path):
        segs, reg, recs = self._run(tmp_path)
        spans = [r for r in recs if r["name"] == "train.readback"]
        assert reg.value("train.host_readbacks") == len(spans) == 5 * len(segs)
        X, y = _toy_parts()
        reg2, path2 = _sinked_registry(tmp_path, "tele.jsonl")
        segs = list(gadget_train_stream(X, y, _toy_cfg(max_iters=10),
                                        segment_iters=5, trace_registry=reg2,
                                        telemetry=tm.TrainTelemetry()))
        spans = [r for r in _records(reg2, path2) if r["name"] == "train.readback"]
        assert reg2.value("train.host_readbacks") == len(spans) == 9 * len(segs)

    def test_spans_close_with_error_on_nonfinite_weights(self, tmp_path):
        from repro.core.gadget import NonFiniteWeightsError

        X, y = _toy_parts()
        X = np.array(X)
        X[0] = np.nan
        reg, path = _sinked_registry(tmp_path)
        with pytest.raises(NonFiniteWeightsError):
            for _ in gadget_train_stream(X, y, _toy_cfg(max_iters=10),
                                         segment_iters=5, trace=True,
                                         trace_registry=reg):
                pass
        recs = _records(reg, path)
        by_name = {r["name"]: r for r in recs}
        for name in ("train.segment", "train.segment.account"):
            assert by_name[name]["fields"]["error"].startswith(
                "NonFiniteWeightsError"), name
        assert by_name["train.segment.account"]["parent_id"] \
            == by_name["train.segment"]["span_id"]
        assert "error" not in by_name["train.segment.wait"]["fields"]


# ---------------------------------------------------------------------------
# RequestTracer: sampled fates, reservoir retention
# ---------------------------------------------------------------------------


class TestRequestTracer:
    def test_validation(self):
        with pytest.raises(ValueError):
            tmtr.RequestTracer(Registry(), sample=1.5)
        with pytest.raises(ValueError):
            tmtr.RequestTracer(Registry(), reservoir=0)

    def test_reservoir_bounded_over_soak(self):
        """A long soak holds O(reservoir) fate records while exact totals
        ride the counters — the 50k-soak memory contract (scaled down)."""
        reg = Registry()
        tracer = tmtr.RequestTracer(reg, sample=1.0, reservoir=32,
                                    clock=lambda: 0.0)
        n = 5000
        for rid in range(n):
            tracer.start(rid)
            tracer.finish(rid, "delivered")
        assert len(tracer.sampled_fates()) == 32
        assert tracer.pending == 0
        assert reg.value("trace.requests") == n
        assert tracer.fate_counts() == {"delivered": n}

    def test_sample_zero_emits_nothing(self, tmp_path):
        reg, path = _sinked_registry(tmp_path)
        tracer = tmtr.RequestTracer(reg, sample=0.0)
        tracer.start(1)
        tracer.finish(1, "delivered")
        tracer.reject()
        assert _records(reg, path) == []
        assert reg.value("trace.requests") == 0

    def test_finish_unknown_rid_is_noop(self):
        tracer = tmtr.RequestTracer(Registry())
        tracer.finish(999, "delivered")  # never started — must not throw
        assert tracer.fate_counts() == {}

    def test_batcher_fates_reconcile_exactly(self, tmp_path):
        """Every submission meets exactly one typed fate and the traced
        counters reconcile with the batcher's own accounting:
        ``trace.requests == submitted + rejected`` and per-fate counts match
        ``delivered`` / ``shed`` / ``deadline_missed``."""
        reg, path = _sinked_registry(tmp_path)
        clock = {"t": 0.0}
        tracer = tmtr.RequestTracer(reg, sample=1.0,
                                    clock=lambda: clock["t"])
        mb = MicroBatcher(_buckets(), registry=reg, tracer=tracer,
                          max_pending=3, admission="shed-oldest",
                          clock=lambda: clock["t"])
        # 5 submits into 3 slots: 2 shed-oldest
        for _ in range(5):
            mb.submit(*_query())
        # a refused-at-the-door submission (oversize for the k=4 ladder)
        with pytest.raises(serve.QueryRejected):
            mb.submit(np.arange(6, dtype=np.int32),
                      np.ones(6, np.float32))
        # one more with a deadline that expires before drain
        mb.submit(*_query(), deadline=1.0)
        clock["t"] = 2.0
        mb.drain(_ok)
        st = mb.stats()
        fates = tracer.fate_counts()
        assert fates == {"delivered": st["delivered"],
                         "shed": st["shed"],
                         "deadline": st["deadline_missed"],
                         "rejected": st["rejected"]}
        assert reg.value("trace.requests") == st["submitted"] + st["rejected"]
        assert (st["submitted"] == st["delivered"] + st["shed"]
                + st["deadline_missed"] + st["pending"])
        recs = _records(reg, path)
        req_spans = [r for r in recs if r["name"] == "serve.request"]
        assert len(req_spans) == reg.value("trace.requests")
        delivered = [r for r in req_spans
                     if r["fields"]["fate"] == "delivered"]
        assert delivered and all(
            r["fields"]["bucket"] == "k4" and r["fields"]["rung"] == 0
            for r in delivered)


# ---------------------------------------------------------------------------
# Version lineage: publisher, engine, resume
# ---------------------------------------------------------------------------


class TestLineage:
    def test_publish_retry_keeps_trace_with_attempt_spans(
            self, tmp_path, monkeypatch):
        """Transient OSErrors during publish stay inside ONE trace: the
        publish.seconds span plus one publish.attempt child per try, failed
        attempts carrying the error attr."""
        from repro.serve import publisher as pub_mod
        real = pub_mod.to_checkpoint
        fail = {"left": 2}

        def flaky(*a, **kw):
            if fail["left"] > 0:
                fail["left"] -= 1
                raise OSError("transient write failure")
            return real(*a, **kw)

        monkeypatch.setattr(pub_mod, "to_checkpoint", flaky)
        X, y = _toy_parts()
        reg, path = _sinked_registry(tmp_path)
        root = str(tmp_path / "ckpts")
        pub = TrainPublisher(X, y, _toy_cfg(max_iters=10), root=root,
                             segment_iters=5, publish_retries=3,
                             publish_backoff=0.001, registry=reg,
                             trace=True).start()
        pub.join()
        assert pub.publish_retries_used == 2
        recs = _records(reg, path)
        pubs = [r for r in recs if r["name"] == "publish.seconds"]
        atts = [r for r in recs if r["name"] == "publish.attempt"]
        assert len(pubs) == 2  # versions 5 and 10
        v5 = next(r for r in pubs if r["fields"]["iteration"] == 5)
        v5_atts = [a for a in atts if a["trace_id"] == v5["trace_id"]]
        assert [a["fields"]["attempt"] for a in v5_atts] == [0, 1, 2]
        assert all("OSError" in a["fields"]["error"] for a in v5_atts[:2])
        assert "error" not in v5_atts[-1]["fields"]
        # each attempt is a child of the publish span; publish hangs off the
        # segment root
        assert all(a["parent_id"] == v5["span_id"] for a in v5_atts)
        seg = next(r for r in recs if r["name"] == "train.segment"
                   and r["trace_id"] == v5["trace_id"])
        assert v5["parent_id"] == seg["span_id"]
        # the visibility event lands after the publish span closes
        vis = next(r for r in recs if r["name"] == "publish.visible"
                   and r["trace_id"] == v5["trace_id"])
        assert vis["ts"] >= v5["ts"]

    def test_full_chain_complete_for_every_version(self, tmp_path):
        """The acceptance shape: live publish + deterministic replay via
        point_latest makes every published version's chain complete and
        monotone, recoverable from the JSONL alone."""
        X, y = _toy_parts()
        reg, path = _sinked_registry(tmp_path)
        root = str(tmp_path / "ckpts")
        pub = TrainPublisher(X, y, _toy_cfg(max_iters=10), root=root,
                             segment_iters=5, registry=reg,
                             trace=True).start()
        pub.join()
        srv = SvmServer.watch(root, use_kernels=False, registry=reg)
        Xq = RNG.normal(size=(2, 32)).astype(np.float32)
        for step in pub.published:
            ckpt.point_latest(root, step)
            srv.maybe_reload()
            srv.score(Xq)
        chains = tmtr.lineage_chains(_records(reg, path))
        assert sorted(chains) == pub.published == [5, 10]
        for version, chain in chains.items():
            assert chain["complete"], (version, chain["events"].keys())
            assert chain["monotone"]
        # the manifest carried the propagation context + a wall-clock anchor
        manifest = ckpt.read_manifest(root, 10)
        assert "ts" in manifest
        trace = manifest["extra"]["trace"]
        assert trace["trace_id"] == chains[10]["trace_id"]

    def test_untraced_publisher_emits_no_trace_records(self, tmp_path):
        """Tracing off (the default) adds nothing to the stream — the
        invariance half of the overhead bound."""
        X, y = _toy_parts()
        reg, path = _sinked_registry(tmp_path)
        root = str(tmp_path / "ckpts")
        pub = TrainPublisher(X, y, _toy_cfg(max_iters=10), root=root,
                             segment_iters=5, registry=reg).start()
        pub.join()
        srv = SvmServer.watch(root, use_kernels=False, registry=reg)
        srv.score(RNG.normal(size=(2, 32)).astype(np.float32))
        recs = _records(reg, path)
        assert [r for r in recs if "trace_id" in r] == []
        assert "trace" not in (ckpt.read_manifest(root, 10).get("extra") or {})

    def test_quarantined_reload_closes_swap_span(self, tmp_path):
        """A checkpoint that fails to load until quarantine closes its
        serve.swap span with error="quarantined", linked to the publish
        trace recovered from the (readable) manifest."""
        X, y = _toy_parts()
        reg, path = _sinked_registry(tmp_path)
        root = str(tmp_path / "ckpts")
        pub = TrainPublisher(X, y, _toy_cfg(max_iters=10), root=root,
                             segment_iters=5, registry=reg,
                             trace=True).start()
        pub.join()
        srv = SvmServer.watch(root, use_kernels=False, registry=reg,
                              reload_quarantine=1)
        # a poisoned step: manifest intact (trace recoverable), arrays not
        import os
        bad = os.path.join(root, "step_000000099")
        os.makedirs(bad)
        poison_ctx = tmtr.TraceContext.new()
        with open(os.path.join(bad, "manifest.json"), "w") as fh:
            json.dump({"version": 1, "step": 99, "ts": 0.0,
                       "extra": {"trace": poison_ctx.to_extra()}}, fh)
        with open(os.path.join(bad, "arrays.npz"), "w") as fh:
            fh.write("not an npz")
        ckpt_io._write_pointer(root, 99)
        assert srv.maybe_reload() is None
        assert srv.quarantined_steps == [99]
        # no first-score event is armed for a failed swap
        srv.score(RNG.normal(size=(2, 32)).astype(np.float32))
        recs = _records(reg, path)
        swap = next(r for r in recs if r["name"] == "serve.swap"
                    and r["fields"].get("error"))
        assert swap["fields"]["error"] == "quarantined"
        assert swap["fields"]["version"] == 99
        assert swap["trace_id"] == poison_ctx.trace_id
        assert swap["parent_id"] == poison_ctx.span_id
        assert not any(r["name"] == "serve.first_score"
                       and r["trace_id"] == poison_ctx.trace_id
                       for r in recs)

    def test_resume_links_fresh_trace_to_prior(self, tmp_path):
        """Kill-and-resume: the restarted run starts fresh traces but stamps
        the pre-crash trace_id (recovered from the manifest) onto its first
        segment span as resumed_from_trace."""
        X, y = _toy_parts()
        cfg = _toy_cfg(max_iters=10)
        root = str(tmp_path / "ckpts")
        # "crashed" run: one traced segment published by hand, then death
        for seg in gadget_train_stream(X, y, cfg, segment_iters=5,
                                       trace=True):
            prior = seg.trace
            to_checkpoint(Snapshot(seg.iteration, np.asarray(seg.w_consensus),
                                   seg.objective), root, lam=cfg.lam,
                          train_state=TrainState(seg.iteration, seg.W,
                                                 seg.W_sum),
                          trace=prior.to_extra())
            break
        reg, path = _sinked_registry(tmp_path)
        pub = TrainPublisher(X, y, cfg, root=root, segment_iters=5,
                             save_train_state=True, resume="latest",
                             registry=reg, trace=True).start()
        pub.join()
        assert pub.resumed_from == 5 and pub.published == [10]
        recs = _records(reg, path)
        seg10 = next(r for r in recs if r["name"] == "train.segment")
        assert seg10["trace_id"] != prior.trace_id  # fresh trace per segment
        assert seg10["fields"]["resumed_from_trace"] == prior.trace_id


# ---------------------------------------------------------------------------
# Lineage assembly + CLI
# ---------------------------------------------------------------------------


def _synthetic_chain(version, t0=100.0, *, drop=(), swap_ts=None):
    """Hand-built lineage records for one version."""
    root = tmtr.TraceContext.new()
    pub = root.child()
    swap = pub.child()
    out = [
        {"ts": t0, "kind": "span", "name": "train.segment", "labels": {},
         "seconds": 0.5, "fields": {"iteration": version},
         **tmtr._trace_fields(root)},
        {"ts": t0 + 1, "kind": "span", "name": "publish.seconds",
         "labels": {}, "seconds": 0.01, "fields": {"iteration": version},
         **tmtr._trace_fields(pub)},
        {"ts": t0 + 1.1, "kind": "event", "name": "publish.visible",
         "labels": {}, "fields": {"iteration": version},
         **tmtr._trace_fields(pub)},
        {"ts": swap_ts if swap_ts is not None else t0 + 2, "kind": "span",
         "name": "serve.swap", "labels": {}, "seconds": 0.02,
         "fields": {"version": version}, **tmtr._trace_fields(swap)},
        {"ts": t0 + 3, "kind": "event", "name": "serve.first_score",
         "labels": {}, "fields": {"version": version},
         **tmtr._trace_fields(swap.child())},
    ]
    return [r for r in out if r["name"] not in drop]


class TestLineageAssembly:
    def test_complete_and_incomplete_chains(self):
        recs = (_synthetic_chain(5)
                + _synthetic_chain(10, t0=200.0, drop=("serve.swap",
                                                       "serve.first_score")))
        chains = tmtr.lineage_chains(recs)
        assert chains[5]["complete"] and chains[5]["monotone"]
        assert not chains[10]["complete"]
        text = tmtr.format_chain(5, chains[5])
        assert "complete" in text and "hops:" in text

    def test_non_monotone_flagged(self):
        chains = tmtr.lineage_chains(_synthetic_chain(5, swap_ts=50.0))
        assert chains[5]["complete"] and not chains[5]["monotone"]
        assert "NON-MONOTONE" in tmtr.format_chain(5, chains[5])

    def test_records_without_version_skipped(self):
        root = tmtr.TraceContext.new()
        recs = [{"ts": 1.0, "kind": "span", "name": "train.segment",
                 "labels": {}, "seconds": 0.1, "fields": {},
                 **tmtr._trace_fields(root)}]
        assert tmtr.lineage_chains(recs) == {}

    def test_cli(self, tmp_path, capsys):
        path = tmp_path / "run.jsonl"
        with open(path, "w") as fh:
            for rec in _synthetic_chain(5):
                fh.write(json.dumps(rec) + "\n")
        assert tmtr.main([str(path)]) == 0
        out = capsys.readouterr().out
        assert "1 chain(s), 1 complete" in out
        assert tmtr.main([str(path), "--version", "5"]) == 0
        assert "segment-end" in capsys.readouterr().out
        assert tmtr.main([str(path), "--version", "7"]) == 1
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        assert tmtr.main([str(empty)]) == 1


# ---------------------------------------------------------------------------
# Observatory: per-node health
# ---------------------------------------------------------------------------


def _obs_parts(m=6, n_i=16, d=24, seed=0):
    """Fleet sized so a dead node separates cleanly from its peers."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(m, n_i, d)).astype(np.float32)
    w_true = rng.normal(size=d).astype(np.float32)
    y = np.sign(X @ w_true).astype(np.float32)
    y[y == 0] = 1.0
    return X, y


@pytest.fixture(scope="module")
def faulted_report():
    X, y = _obs_parts()
    cfg = GadgetConfig(max_iters=300, epsilon=0.0, seed=3, check_every=1,
                       use_kernels=False,
                       faults=FaultPlan(drop_prob=0.05, drop="message",
                                        dead_nodes=(2,), seed=5))
    res = gadget_train(X, y, cfg,
                       telemetry=tm.TrainTelemetry(every=10, slots=32,
                                                   per_node=True))
    return tm.analyze(res.telemetry)


class TestObservatory:
    def test_requires_per_node_rings(self):
        X, y = _toy_parts()
        res = gadget_train(X, y, _toy_cfg(),
                           telemetry=tm.TrainTelemetry())
        with pytest.raises(ValueError, match="per-node"):
            tm.analyze(res.telemetry)

    def test_faulted_fleet_flags_dead_node_and_leak(self, faulted_report):
        rep = faulted_report
        assert not rep.healthy
        assert 2 in rep.dead or 2 in rep.stragglers
        assert rep.mass_leak > 0  # message drops destroy Push-Sum mass
        flagged = next(h for h in rep.nodes if h.node == 2)
        assert flagged.dead or flagged.straggler
        assert flagged.drops == 0  # a dead node sends nothing to drop
        assert len(rep.nodes) == 6

    def test_healthy_fleet_clean_with_negative_mixing_rate(self):
        X, y = _obs_parts()
        cfg = GadgetConfig(max_iters=300, epsilon=0.0, seed=3, check_every=1,
                           use_kernels=False)
        res = gadget_train(X, y, cfg,
                           telemetry=tm.TrainTelemetry(every=10, slots=32,
                                                       per_node=True))
        rep = tm.analyze(res.telemetry)
        assert rep.healthy
        assert rep.stragglers == () and rep.dead == ()
        assert rep.mass_leak == 0.0
        assert rep.mixing_rate < 0  # fault-free gossip converges

    def test_publish_node_health_gauges(self, faulted_report):
        reg = Registry()
        tm.publish_node_health(faulted_report, reg)
        h = faulted_report.nodes[2]
        assert reg.value("node.disagreement", node="2") == h.disagreement
        assert reg.value("node.dead", node="2") == float(h.dead)
        assert reg.value("node.straggler", node="2") == float(h.straggler)
        assert reg.value("train.mass_leak") == faulted_report.mass_leak


# ---------------------------------------------------------------------------
# Top console
# ---------------------------------------------------------------------------


class TestTopConsole:
    def test_render_empty_placeholders(self):
        frame = tmtop.render({})
        assert "no node health published" in frame
        assert "=== serve fates ===" in frame
        assert "lineage needs span records" in frame

    def test_render_panes_from_run(self, tmp_path, faulted_report):
        reg, path = Registry(), tmp_path / "run.jsonl"
        tm.publish_node_health(faulted_report, reg)
        reg.counter("serve.submitted").inc(7)
        reg.counter("serve.delivered").inc(7)
        tm.dump_jsonl(reg, path, mode="a")
        with open(path, "a") as fh:
            for rec in _synthetic_chain(5):
                fh.write(json.dumps(rec) + "\n")
        records = tm.read_jsonl(path)
        frame = tmtop.render(tmtop.snapshot_values(records), records)
        assert "MASS LEAK" in frame
        assert "DEAD" in frame or "STRAGGLER" in frame
        assert "submitted 7" in frame and "delivered 7" in frame
        assert "v5: complete" in frame

    def test_cli_once(self, tmp_path, capsys, faulted_report):
        reg, path = Registry(), tmp_path / "run.jsonl"
        tm.publish_node_health(faulted_report, reg)
        tm.dump_jsonl(reg, path, mode="a")
        assert tmtop.main([str(path), "--once"]) == 0
        assert "gossip nodes" in capsys.readouterr().out
