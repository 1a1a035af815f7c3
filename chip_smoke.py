#!/usr/bin/env python3
"""Chip smoke: GADGET train → publish → serve once on a TPU, with checks.

Drives the system's main path through the entry points a user calls, at the
paper's own CCAT deployment (``configs/gadget_svm.py``: m = 10 nodes, B = 1,
R = 4 Push-Sum rounds, random topology, λ = 1e-4) over the full published
dataset (781,265 × 47,236 at 0.16 % nonzeros), generated from ``--seed``:

  device   the first device is a TPU and the Pallas kernels compile natively
  kernels  every Pallas entry point at full width against its jnp oracle
  train    gadget_train on full CCAT: the compiled loop holds the kernels,
           the weights are finite, and the consensus matches the same run
           with use_kernels=False
  serve    to_checkpoint (f32 and int8) → SvmServer.load → MicroBatcher over
           CCAT test rows, scores against numpy X @ w
  swap     TrainPublisher → SvmServer.watch / maybe_reload: a hot swap on
           the chip, trainer and server on threads of this process

Each phase raises on failure, and the script prints its verdict only after
every phase passed: the last line of standard output is then
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU it exits non-zero and prints no verdict.

  python chip_smoke.py                 # one chip, all phases
  python chip_smoke.py --four-chips    # 4 chips: mesh scorer + mesh step only

Times printed here are smoke numbers from one run, compile included where
said, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

try:
    import jax  # noqa: E402
    import jax.numpy as jnp  # noqa: E402

    from repro import compile_cache, serve  # noqa: E402
    from repro.configs.gadget_svm import PAPER_RUNS  # noqa: E402
    from repro.core import gadget  # noqa: E402
    from repro.data.svm_datasets import make_dataset, partition  # noqa: E402
    from repro.kernels.hinge_subgrad import ops, ref  # noqa: E402
except ImportError as e:  # run outside a checkout of the repository
    sys.exit(f"chip_smoke: cannot import the system ({e}); run it from the "
             f"root of a checkout of the repository")

# The paper's deployment and the smoke's cuts: full dataset, full widths;
# only the iteration count is capped (two ε-checks of 200 iterations each).
CCAT_SCALE = 1.0
TRAIN_ITERS = 400
CHECK_EVERY = 200
SERVE_QUERIES = 512
SWAP_SCALE = 0.01        # the hot-swap loop trains a 1 % CCAT slice

# Tolerances the repository's tests assert for the same comparisons.
DENSE_ATOL, DENSE_RTOL = 2e-5, 1e-5      # tests/test_fused_fleet.py
ELL_ATOL = 1e-5                          # tests/test_sparse.py (prefetch)
PREDICT_ATOL, PREDICT_RTOL = 2e-5, 1e-5  # tests/test_serve.py
TRAIN_PATH_TOL = 1e-4                    # kernel vs jnp training path


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


T_START = time.perf_counter()


def log(phase: str, msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:7.1f}s {phase}] {msg}", flush=True)


def max_err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float64)
                               - np.asarray(want, np.float64))))


def assert_close(phase, name, got, want, atol, rtol=0.0) -> None:
    err = max_err(got, want)
    log(phase, f"{name}: max |err| = {err:.3e} (atol {atol:g}, rtol {rtol:g})")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=atol, rtol=rtol, err_msg=name)


# ------------------------------------------------------------------ device


def phase_device(n_chips: int, cache_dir: str):
    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu",
          f"no TPU: JAX's first device is platform={d0.platform!r} "
          f"({d0.device_kind}); this smoke runs only on a TPU")
    check(not ops.default_interpret(),
          "the Pallas kernels would run in interpret mode on this backend")
    check(len(devs) >= n_chips,
          f"needs {n_chips} TPU chips, JAX found {len(devs)}")
    log("device", f"platform={d0.platform} kind={d0.device_kind} "
                  f"count={len(devs)} compile_cache={cache_dir}")
    return d0, len(devs)


# ----------------------------------------------------------------- kernels


def phase_kernels(rng, parts, y_parts, ds) -> None:
    P = "kernels"
    t = jnp.float32(3.0)

    # fused dense fleet half-step at webspam width (m = 10, B = 1, d = 254)
    m, B, d = 10, 1, 254
    X = np.abs(rng.normal(size=(m, B, d))).astype(np.float32)
    X /= np.linalg.norm(X, axis=-1, keepdims=True)
    y = np.sign(rng.normal(size=(m, B)) + 0.1).astype(np.float32)
    W = (rng.normal(size=(m, d)) * 0.1).astype(np.float32)
    args = jnp.asarray(W), jnp.asarray(X), jnp.asarray(y)
    got = ops.fleet_half_step(*args, lam=1e-3, t=t)
    want = ref.fleet_half_step_ref(*args, 1e-3, t)
    assert_close(P, "fleet_half_step webspam m=10 B=1 d=254", got, want,
                 DENSE_ATOL, DENSE_RTOL)

    # sparse fleet half-step, both schedules, at CCAT width (B = 1, k = 76)
    cols = jnp.asarray(parts.cols[:, :1])
    vals = jnp.asarray(parts.vals[:, :1])
    yb = jnp.asarray(y_parts[:, :1])
    Wc = jnp.asarray((rng.normal(size=(parts.cols.shape[0], parts.d)) * 0.1)
                     .astype(np.float32))
    t4 = jnp.float32(4.0)
    want = ref.ell_fleet_half_step_ref(Wc, cols, vals, yb, 1e-3, t4)
    for sched, cap in (("sweep", None), ("prefetch", parts.block_bound(1))):
        got = ops.ell_fleet_half_step(Wc, cols, vals, yb, lam=1e-3, t=t4,
                                      schedule=sched, n_blocks_max=cap)
        assert_close(P, f"ell_fleet_half_step {sched} m={cols.shape[0]} "
                        f"k={cols.shape[2]} d={parts.d}", got, want, ELL_ATOL)

    # serving kernels at CCAT width on test-split queries
    w = (rng.normal(size=(ds.d,)) * 0.1).astype(np.float32)
    Xq = ds.X_test.take_rows(np.arange(64)).to_dense()
    scores, labels = ops.dense_predict(jnp.asarray(w), jnp.asarray(Xq))
    assert_close(P, f"dense_predict B=64 d={ds.d}", scores,
                 Xq.astype(np.float64) @ w, PREDICT_ATOL, PREDICT_RTOL)
    check(np.array_equal(np.asarray(labels),
                         np.where(np.asarray(scores) >= 0, 1.0, -1.0)),
          "dense_predict labels disagree with its scores")
    qc = jnp.asarray(ds.X_test.cols[:8])
    qv = jnp.asarray(ds.X_test.vals[:8])
    scores, _ = ops.ell_predict(jnp.asarray(w), qc, qv)
    assert_close(P, f"ell_predict B=8 k={qc.shape[1]} d={ds.d}", scores,
                 ref.ell_predict_scores_ref(jnp.asarray(w)[None], qc, qv)[:, 0],
                 ELL_ATOL)


# ------------------------------------------------------------------- train


def assert_kernels_in(hlo: str) -> None:
    check("tpu_custom_call" in hlo,
          "the compiled training loop holds no Pallas kernel")


def phase_train(parts, y_parts, counts, cfg):
    P = "train"
    y_dev = jnp.asarray(y_parts)
    train_fn, targs = gadget._prepare_device_train(cfg, parts, y_dev, counts)
    assert_kernels_in(train_fn.lower(*targs).as_text())
    del train_fn, targs
    log(P, "compiled training loop contains tpu_custom_call: yes")

    t0 = time.perf_counter()
    res = gadget.gadget_train(parts, y_dev, cfg, n_counts=counts)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = gadget.gadget_train(parts, y_dev, cfg, n_counts=counts)
    steady_s = time.perf_counter() - t0
    check(res.iters == cfg.max_iters,
          f"ran {res.iters} of {cfg.max_iters} iterations")
    check(len(res.objective_trace) >= 2,
          f"{len(res.objective_trace)} ε-checks, expected at least 2")
    w = np.asarray(res.w_consensus)
    check(np.all(np.isfinite(np.asarray(res.W))) and np.all(np.isfinite(w)),
          "non-finite weights")
    check(np.array_equal(w, np.asarray(again.w_consensus)),
          "two identical runs disagree")
    log(P, f"iters={res.iters} eps_trace={np.round(res.eps_trace, 6).tolist()} "
           f"objective_trace={np.round(res.objective_trace, 6).tolist()}")
    log(P, f"smoke (not a benchmark metric): first call {first_s:.2f} s, "
           f"repeat call {steady_s:.2f} s, compile ≈ {first_s - steady_s:.2f} s, "
           f"{res.iters / steady_s:.1f} iterations/s over the repeat call "
           f"(data upload and objective passes included)")

    jnp_res = gadget.gadget_train(parts, y_dev, cfg._replace(use_kernels=False),
                                  n_counts=counts)
    w_j = np.asarray(jnp_res.w_consensus)
    diff = max_err(w, w_j)
    scale = float(np.max(np.abs(w_j)))
    log(P, f"kernel vs use_kernels=False consensus: max |diff| = {diff:.3e} "
           f"(max |w| = {scale:.3e}); objective traces "
           f"{np.round(res.objective_trace, 6).tolist()} vs "
           f"{np.round(jnp_res.objective_trace, 6).tolist()}")
    check(diff <= TRAIN_PATH_TOL * max(1.0, scale),
          f"kernel and jnp training paths diverged: {diff:.3e}")
    np.testing.assert_allclose(res.objective_trace, jnp_res.objective_trace,
                               rtol=TRAIN_PATH_TOL)
    return res


# ------------------------------------------------------------------- serve


def phase_serve(ds, parts, res, cfg, work: Path) -> None:
    P = "serve"
    snap = serve.Snapshot(iteration=res.iters,
                          w=np.asarray(res.w_consensus, np.float32),
                          objective=float(res.objective_trace[-1]))
    serve.to_checkpoint(snap, str(work / "f32"), lam=cfg.lam)
    serve.to_checkpoint(snap, str(work / "int8"), quantize="int8", lam=cfg.lam)
    srv = serve.SvmServer.load(str(work / "f32"))
    srv_q = serve.SvmServer.load(str(work / "int8"))
    check(np.array_equal(srv.W, snap.w), "f32 checkpoint round trip changed w")

    sample = slice(0, 20000)
    buckets = serve.calibrate_buckets(
        serve.bucket_ladder(ds.X_test.k_max, rows=8, min_k=16, d=ds.d),
        parts.cols.reshape(-1, parts.cols.shape[-1])[sample],
        parts.vals.reshape(-1, parts.vals.shape[-1])[sample], ds.d)
    mb = serve.MicroBatcher(buckets)
    n = min(SERVE_QUERIES, ds.X_test.shape[0])
    rids, results = [], {}
    for i in range(n):
        live = ds.X_test.vals[i] != 0
        rids.append(mb.submit(ds.X_test.cols[i][live], ds.X_test.vals[i][live]))
        if mb.pending >= 64:
            results.update(mb.drain(srv.scorer_for()))
    results.update(mb.drain(srv.scorer_for()))
    check(len(results) == n, f"{len(results)} of {n} queries answered")
    got = np.array([results[r][0] for r in rids], np.float64)
    want = ds.X_test.take_rows(np.arange(n)).matvec(snap.w.astype(np.float64))
    assert_close(P, f"MicroBatcher → scorer_for, {n} CCAT test rows vs "
                    f"numpy X @ w", got, want, PREDICT_ATOL, PREDICT_RTOL)
    st = srv.stats()
    check(st["reload_errors"] == 0, f"reload_errors = {st['reload_errors']}")
    check(st["distinct_shapes"] <= len(buckets),
          f"{st['distinct_shapes']} compiled shapes for {len(buckets)} buckets")

    cols, vals = ds.X_test.cols[:8], ds.X_test.vals[:8]
    scores_q, _ = srv_q.score_sparse(cols, vals)
    want_q = ds.X_test.take_rows(np.arange(8)).matvec(srv_q.W.astype(np.float64))
    assert_close(P, "int8 checkpoint, score_sparse vs dequantized numpy",
                 scores_q, want_q, PREDICT_ATOL, PREDICT_RTOL)
    acc = float(np.mean(np.sign(got) == ds.y_test[:n]))
    mbs = mb.stats()
    log(P, f"buckets={[(b.rows, b.k, b.n_blocks_max) for b in buckets]} "
           f"distinct_shapes={st['distinct_shapes']} reload_errors=0 "
           f"test accuracy on {n} rows={acc:.4f}")
    log(P, f"smoke (not a benchmark metric): {mbs['requests']} requests in "
           f"{mbs['batches']} batches, p50 {mbs['latency_p50_ms']:.2f} ms, "
           f"p99 {mbs['latency_p99_ms']:.2f} ms (first batches compile)")


# -------------------------------------------------------------------- swap


def phase_swap(seed: int, cfg, work: Path) -> None:
    P = "swap"
    small = make_dataset("ccat", scale=SWAP_SCALE, seed=seed + 1, sparse=True)
    parts, yp, nc = partition(small.X_train, small.y_train, 10, seed=seed)
    root = str(work / "live")
    first = cfg._replace(max_iters=40, check_every=20, epsilon=0.0)
    pub = serve.TrainPublisher(parts, jnp.asarray(yp), first, root=root,
                               segment_iters=20, n_counts=nc,
                               save_train_state=True).start()
    pub.join()
    srv = serve.SvmServer.watch(root)
    v0 = pub.published[-1]
    cols, vals = small.X_test.cols[:8], small.X_test.vals[:8]
    srv.score_sparse(cols, vals)
    shapes = srv.stats()["distinct_shapes"]

    more = first._replace(max_iters=100)
    pub2 = serve.TrainPublisher(parts, jnp.asarray(yp), more, root=root,
                                segment_iters=20, n_counts=nc,
                                save_train_state=True, resume="latest").start()
    swaps = []
    while pub2.running:
        step = srv.maybe_reload()
        if step is not None:
            swaps.append(step)
            scores, _ = srv.score_sparse(cols, vals)
            check(np.all(np.isfinite(scores)), "non-finite scores after a swap")
        time.sleep(0.01)
    pub2.join()
    step = srv.maybe_reload()
    if step is not None:
        swaps.append(step)
    scores, _ = srv.score_sparse(cols, vals)
    st = srv.stats()
    check(pub2.resumed_from == v0, f"resumed from {pub2.resumed_from}, not {v0}")
    check(swaps and swaps[-1] == pub2.published[-1],
          f"server swaps {swaps}, publisher published {pub2.published}")
    check(st["reload_errors"] == 0, f"reload_errors = {st['reload_errors']}")
    check(st["distinct_shapes"] == shapes, "a hot swap recompiled")
    want = small.X_test.take_rows(np.arange(8)).matvec(srv.W.astype(np.float64))
    assert_close(P, "scores after the last swap vs numpy", scores, want,
                 PREDICT_ATOL, PREDICT_RTOL)
    log(P, f"published {pub.published} then {pub2.published}; server swapped "
           f"to {swaps}; swaps={st['swaps']} reload_errors=0 "
           f"distinct_shapes={st['distinct_shapes']}")


# -------------------------------------------------------------- four chips


def phase_four_chips(seed: int) -> None:
    P = "four-chips"
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as Spec

    devs = jax.devices()[:4]
    rng = np.random.default_rng(seed)

    # batch-parallel scorer over a 4-device mesh vs the one-device server
    ds = make_dataset("ccat", scale=0.003, seed=seed, sparse=True)
    w = (rng.normal(size=(ds.d,)) * 0.1).astype(np.float32)
    X = ds.X_test.take_rows(np.arange(64)).to_dense()
    mesh = Mesh(np.array(devs), ("batch",))
    scorer = serve.make_mesh_scorer(w, mesh=mesh)
    scores, labels = scorer(jax.device_put(X, NamedSharding(mesh, Spec("batch"))))
    where = sorted((s.device.id, s.data.shape[0]) for s in scores.addressable_shards)
    log(P, f"make_mesh_scorer score shards (device id, rows): {where}")
    check(len({dev for dev, _ in where}) == 4, "scores did not spread over 4 chips")
    want, want_labels = serve.SvmServer(w).score(X)
    assert_close(P, "mesh scorer vs one-device SvmServer.score", scores, want,
                 PREDICT_ATOL, PREDICT_RTOL)
    check(np.array_equal(np.asarray(labels), want_labels), "labels differ")

    # one GADGET node per chip: the sparse-kernel mesh step vs the dense jnp
    # mesh step on the same data and keys (tests/test_sparse.py's comparison)
    m = 4
    rds = make_dataset("reuters", scale=0.05, seed=seed, sparse=True)
    parts, yp, _ = partition(rds.X_train, rds.y_train, m, seed=1)
    dense, _, _ = partition(rds.X_train.to_dense(), rds.y_train, m, seed=1)
    node_mesh = Mesh(np.array(devs), ("nodes",))
    cfg = gadget.GadgetConfig(lam=rds.lam, batch_size=2, gossip_rounds=2)
    step_s = gadget.make_gadget_mesh_step(
        cfg._replace(use_kernels=True, sparse_schedule="prefetch"), {"nodes": m},
        sparse_block_bound=parts.block_bound(cfg.batch_size))
    step_d = gadget.make_gadget_mesh_step(cfg._replace(use_kernels=False),
                                          {"nodes": m})

    def sharded(step, sparse):
        def per_node(w, c, v, x, y, keys, t):
            X_local = (c[0], v[0]) if sparse else x[0]
            return step(w[0], X_local, y[0], t, keys[0])[None]
        specs = (Spec("nodes"),) * 6 + (Spec(),)
        return jax.jit(jax.shard_map(per_node, mesh=node_mesh, in_specs=specs,
                                     out_specs=Spec("nodes"), check_vma=False))

    on_nodes = NamedSharding(node_mesh, Spec("nodes"))
    cols, vals, Xd, yj = (jax.device_put(a, on_nodes) for a in
                          (parts.cols, parts.vals, dense, yp))
    Ws = Wd = jax.device_put(np.zeros((m, parts.d), np.float32), on_nodes)
    run_s, run_d = sharded(step_s, True), sharded(step_d, False)
    for t in range(1, 6):
        keys = jax.device_put(
            jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), t), m),
            on_nodes)
        Ws = run_s(Ws, cols, vals, Xd, yj, keys, jnp.int32(t))
        Wd = run_d(Wd, cols, vals, Xd, yj, keys, jnp.int32(t))
    where = sorted((s.device.id, s.data.shape) for s in Ws.addressable_shards)
    log(P, f"make_gadget_mesh_step weight shards (device id, shape): {where}")
    check(len({dev for dev, _ in where}) == 4, "nodes did not spread over 4 chips")
    diff = max_err(Ws, Wd)
    log(P, f"sparse-kernel vs dense-jnp mesh step after 5 iterations: "
           f"max |diff| = {diff:.3e} (bound 1e-5)")
    check(diff <= 1e-5, f"mesh step paths diverged: {diff:.3e}")
    check(float(jnp.max(jnp.abs(Ws))) > 0, "mesh step produced all-zero weights")


# -------------------------------------------------------------------- main


def run_phases(args) -> dict:
    cache_dir = compile_cache.enable()  # before anything compiles
    d0, count = phase_device(4 if args.four_chips else 1, cache_dir)
    if args.four_chips:
        phase_four_chips(args.seed)
    else:
        rng = np.random.default_rng(args.seed)
        run = PAPER_RUNS["ccat"]
        cfg = run.gadget._replace(max_iters=TRAIN_ITERS, check_every=CHECK_EVERY,
                                  seed=args.seed)
        t0 = time.perf_counter()
        ds = make_dataset("ccat", scale=CCAT_SCALE, seed=args.seed, sparse=True)
        parts, y_parts, counts = partition(ds.X_train, ds.y_train, run.n_nodes,
                                           seed=args.seed)
        log("data", f"CCAT train {ds.X_train.shape} k_max={ds.X_train.k_max} "
                    f"test {ds.X_test.shape}; partitions {parts.shape}; "
                    f"generated in {time.perf_counter() - t0:.1f} s (set-up)")
        phase_kernels(rng, parts, y_parts, ds)
        res = phase_train(parts, y_parts, counts, cfg)
        with tempfile.TemporaryDirectory(prefix="chip_smoke-") as td:
            phase_serve(ds, parts, res, cfg, Path(td))
            phase_swap(args.seed, cfg, Path(td))
    cached = Path(cache_dir)
    log("done", f"{len(list(cached.iterdir())) if cached.is_dir() else 0} "
                f"entries in the compile cache {cache_dir}")
    return {"ok": True, "device": {"platform": d0.platform,
                                   "kind": d0.device_kind, "count": count}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh paths and their references")
    args = ap.parse_args()
    try:
        verdict = run_phases(args)
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    log("done", "all phases passed")
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
