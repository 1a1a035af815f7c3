"""The one general driver: set-up and measured window of any cell.

A traffic mix is data (``traffic/<name>.json``) with up to two parts:

  "train": {}                     GADGET training through the configuration's
                                  settings, segment after segment
                                  (``gadget_train_stream``, one segment per
                                  ε-check); with {"publish": true} through a
                                  ``TrainPublisher`` that publishes each
                                  segment as a checkpoint a watching server
                                  hot-swaps in
  "serve": {"rate_qps": r, "arrivals": ..., "rows_per_batch": b, ...}
                                  open-loop arrivals of single test rows,
                                  scheduled by ``loadgen`` from the mix's
                                  parameters (rate, bursts, query lengths),
                                  through ``MicroBatcher.submit`` and
                                  ``drain(SvmServer.scorer_for())``

Everything a run does is drawn from its seed. Set-up makes the data on the
device, builds the training stream and drives it through its first segments
(the ones the reference checks), warms the serving bucket the queries use, and
only then does the window start. Host spans (``jax.profiler.TraceAnnotation``)
mark each segment, drain, submit burst and publish, so the trace reduction can
name what the host did in each idle gap.
"""
from __future__ import annotations

import tempfile
import threading
import time
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.profiler import TraceAnnotation

import datagen
import loadgen

CHECKED_SEGMENTS = 3     # segments of the first run the reference follows
ANSWER_GRACE_S = 60.0    # how long past the window's close answers are awaited


def seeds(seed: int, n: int) -> list[int]:
    """n independent 31-bit seeds from one run seed of any size."""
    return [int(s) & 0x7FFFFFFF for s in
            np.random.SeedSequence(int(seed)).generate_state(n, np.uint64)]


class Partitions:
    """The program's ``EllPartitions`` over the device planes. Its own
    ``block_bound`` sets the sparse kernels' grid; that bound reads the planes
    back to the host, so it is taken once, in set-up, and reused by every
    training run the window starts (the runs share the planes)."""

    def __init__(self, cols, vals, d):
        from repro.sparse.formats import EllPartitions

        self.ell = EllPartitions(cols, vals, int(d))
        self.cols, self.vals, self.d = cols, vals, int(d)
        self._bounds = {}

    def block_bound(self, batch_size: int) -> int:
        if batch_size not in self._bounds:
            self._bounds[batch_size] = self.ell.block_bound(batch_size)
        return self._bounds[batch_size]


@dataclass
class Run:
    """Everything a run measured: read by the metrics, the check and the readers."""

    cell: str
    config: dict
    traffic: dict
    seconds: float
    window: tuple = (0.0, 0.0)
    # training: one (t_start, t_end, iterations) per segment ended in the window
    segments: list = field(default_factory=list)
    checked: list = field(default_factory=list)   # set-up's first segments
    train_seed: int = 0
    # serving, per request due in the window
    due: np.ndarray | None = None
    sent: np.ndarray | None = None
    drain_start: np.ndarray | None = None
    done: np.ndarray | None = None
    score: np.ndarray | None = None
    version: np.ndarray | None = None
    queries: np.ndarray | None = None
    query_nnz: np.ndarray | None = None          # nonzeros of the row each request sent
    planes: dict = field(default_factory=dict)    # version -> served weights
    live_rows: int = 0
    padded_rows: int = 0


# ------------------------------------------------------------------ set-up


def make_state(cell, seed: int, program) -> dict:
    """The data, the program's objects and the pieces of the seed."""
    cfg, traffic = cell.config, cell.traffic
    s_data, s_train, s_serve, s_plane = seeds(seed, 4)
    g = cfg["gadget"]
    data = datagen.make_data(s_data, cfg["dataset"], g["n_nodes"],
                             with_train="train" in traffic, with_test="serve" in traffic,
                             query_nnz=traffic.get("serve", {}).get("query_nnz"))
    parts = None
    if "train" in traffic:
        parts = (Partitions(data["cols"], data["vals"], cfg["dataset"]["d"])
                 if cfg["dataset"]["layout"] == "ell" else data["X"])
    jax.block_until_ready(data)
    return {"data": data, "parts": parts, "train_seed": s_train,
            "serve_seed": s_serve, "plane_seed": s_plane}


def gadget_config(cell, program, seed: int):
    g = dict(cell.config["gadget"])
    g.pop("n_nodes")
    return program.GadgetConfig(**g, seed=int(seed))


# ---------------------------------------------------------------- training


class Trainer:
    """Back-to-back GADGET runs: when one stops at ε or max_iters, the next
    starts from zero with the next seed drawn from the run's train seed."""

    def __init__(self, cell, state, program):
        self.cell, self.state, self.program = cell, state, program
        self.seg_iters = int(cell.config["gadget"]["check_every"])
        self.run_seeds = iter(seeds(state["train_seed"], 10_000))
        self.first_seed = next(self.run_seeds)
        self.stream = self._new_stream(self.first_seed)
        self.last_iteration = 0

    def _new_stream(self, seed):
        d = self.state["data"]
        return self.program.gadget_train_stream(
            self.state["parts"], d["y"], gadget_config(self.cell, self.program, seed),
            segment_iters=self.seg_iters, n_counts=d["counts"])

    def next_segment(self):
        """(segment result, iterations it ran)."""
        try:
            seg = next(self.stream)
        except StopIteration:
            self.stream = self._new_stream(next(self.run_seeds))
            self.last_iteration = 0
            seg = next(self.stream)
        ran = seg.iteration - self.last_iteration
        self.last_iteration = seg.iteration
        return seg, ran


def checked_segments(trainer) -> list:
    """Set-up: the first segments through the window's own call, kept for the
    reference (per-node W and W_sum on the host, and the objective)."""
    out = []
    for _ in range(CHECKED_SEGMENTS):
        seg, _ = trainer.next_segment()
        out.append((np.asarray(seg.W, np.float64), np.asarray(seg.W_sum, np.float64),
                    float(seg.objective)))
    return out


def train_window(run: Run, trainer, seconds: float, capture=None) -> None:
    """Segments back to back until ``seconds`` have passed; the window ends at
    the end of the last segment. ``capture`` traces its first seconds."""
    t0 = time.perf_counter()
    if capture:
        capture.start()
    while True:
        ts = time.perf_counter()
        with TraceAnnotation("bench.segment"):
            _, ran = trainer.next_segment()
        te = time.perf_counter()
        run.segments.append((ts, te, ran))
        if capture:
            capture.maybe_stop()
        if te - t0 >= seconds:
            break
    if capture:
        capture.stop()
    run.window = (t0, te)


# ----------------------------------------------------------------- serving


class Server:
    """The serving side: a plane made from the seed (or the publisher's
    checkpoints), an ``SvmServer``, a ``MicroBatcher`` over the buckets the
    queries need, and the host copy of the test rows the arrivals draw."""

    def __init__(self, cell, state, serve_mod, root=None):
        d = state["data"]
        spec = cell.traffic["serve"]
        self.cols = np.asarray(d["test_cols"])
        self.vals = np.asarray(d["test_vals"])
        self.nnz = (self.vals != 0).sum(axis=1)
        dim = cell.config["dataset"]["d"]
        if root is None:
            w = jax.random.normal(jax.random.PRNGKey(state["plane_seed"]), (dim,)) * 0.1
            self.srv = serve_mod.SvmServer(np.asarray(w, np.float32))
        else:
            self.srv = serve_mod.SvmServer.watch(root)
        ladder = serve_mod.bucket_ladder(int(self.nnz.max()), rows=spec["rows_per_batch"],
                                         min_k=spec["min_k"], d=dim)
        # calibrated on every query the traffic can send: no batch overflows
        # its bucket's block cap, so no shape compiles inside the window
        self.buckets = serve_mod.calibrate_buckets(ladder, self.cols, self.vals, dim)
        self.mb = serve_mod.MicroBatcher(self.buckets)
        self.scorer = self.srv.scorer_for()
        self.watching = root is not None
        self.version = 0
        self.planes = {0: self.srv.W.copy()}

    def warm_up(self) -> None:
        """One batch for each bucket the test rows route to, and no others."""
        used = {}
        for i, n in enumerate(self.nnz):
            used.setdefault(self.mb.bucket_for(int(n)), i)
        for b, i in used.items():
            for _ in range(b.rows):
                self.mb.submit(self.cols[i][: self.nnz[i]], self.vals[i][: self.nnz[i]])
            self.mb.drain(self.scorer)

    def reload(self) -> None:
        if not self.watching:
            return
        step = self.srv.maybe_reload()
        if step is not None:
            self.version += 1
            self.planes[self.version] = self.srv.W.copy()


def serve_window(run: Run, server: Server, seconds: float, seed: int,
                 capture=None) -> None:
    """Open-loop arrivals for ``seconds``; every answer is awaited up to
    ``ANSWER_GRACE_S`` past the close. ``capture`` traces its first seconds."""
    due_rel, queries = loadgen.schedule(run.traffic["serve"], seed, seconds, len(server.cols))
    n = len(due_rel)
    sent = np.full(n, np.nan)
    rid_of = np.full(n, -1, np.int64)
    results = {}                  # rid -> (score, drain start, drain end, version)
    wake = threading.Event()
    gen_done = threading.Event()
    mb = server.mb
    base = mb.stats()
    failures = []
    t0 = time.perf_counter() + 0.01
    due = t0 + due_rel

    def generate():
        i = 0
        try:
            while i < n:
                now = time.perf_counter()
                if due[i] > now:
                    time.sleep(due[i] - now)
                    continue
                with TraceAnnotation("bench.submit"):
                    while i < n and due[i] <= time.perf_counter():
                        q = queries[i]
                        k = server.nnz[q]
                        rid_of[i] = mb.submit(server.cols[q][:k], server.vals[q][:k])
                        sent[i] = time.perf_counter()
                        i += 1
                wake.set()
        except Exception as e:  # a refused request stays unanswered
            failures.append(repr(e))
        finally:
            gen_done.set()
            wake.set()

    def drain():
        if capture:
            capture.start()
        try:
            serve_loop()
        except Exception as e:
            failures.append(repr(e))
            raise
        finally:
            if capture:
                capture.stop()

    def serve_loop():
        deadline = t0 + seconds + ANSWER_GRACE_S
        while time.perf_counter() < deadline:
            wake.wait(0.005)
            wake.clear()
            server.reload()
            if mb.pending == 0:
                if gen_done.is_set():
                    break
                continue
            ts = time.perf_counter()
            with TraceAnnotation("bench.drain"):
                out = mb.drain(server.scorer)
            te = time.perf_counter()
            if capture:
                capture.maybe_stop()
            for rid, r in out.items():
                if isinstance(r, tuple):
                    results[rid] = (float(np.asarray(r[0]).reshape(-1)[0]), ts, te, server.version)

    threads = [threading.Thread(target=generate, name="bench-generator"),
               threading.Thread(target=drain, name="bench-drain")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    run.window = (t0, t0 + seconds)
    run.due, run.sent, run.queries = due, sent, queries
    run.query_nnz = server.nnz[queries]
    run.drain_start = np.full(n, np.nan)
    run.done = np.full(n, t0 + seconds + ANSWER_GRACE_S)   # unanswered: past every limit
    run.score = np.full(n, np.nan)
    run.version = np.full(n, -1)
    for i in range(n):
        r = results.get(int(rid_of[i]))
        if r is not None:
            run.score[i], run.drain_start[i], run.done[i], run.version[i] = r
    st = mb.stats()
    run.live_rows = st["requests"] - base["requests"]
    run.padded_rows = run.live_rows + st["padded_rows"] - base["padded_rows"]
    run.planes = server.planes
    if failures:
        raise RuntimeError(f"serving failed: {failures}")


# ------------------------------------------------------- train while serving


class Publishing:
    """Back-to-back ``TrainPublisher`` runs into one checkpoint root that the
    server watches. Each publish is stamped (time, iteration) and wrapped in a
    host span. ``stop()`` lets the running publisher finish its run and starts
    no other."""

    def __init__(self, cell, state, serve_mod, program, root):
        self.cell, self.state, self.root = cell, state, root
        self.seg_iters = int(cell.config["gadget"]["check_every"])
        self.run_seeds = iter(seeds(state["train_seed"], 10_000))
        self.first_seed = next(self.run_seeds)
        self.records = []            # (time, iteration) per publish
        self.stopping = threading.Event()
        self.error = None
        records = self.records

        class Stamped(serve_mod.TrainPublisher):
            def _publish(self, seg):
                with TraceAnnotation("bench.publish"):
                    super()._publish(seg)
                records.append((time.perf_counter(), seg.iteration))

        def supervise():
            seed = self.first_seed
            try:
                while True:
                    d = state["data"]
                    pub = Stamped(state["parts"], d["y"],
                                  gadget_config(cell, program, seed), root=root,
                                  segment_iters=self.seg_iters, n_counts=d["counts"],
                                  save_train_state=True)
                    pub.start().join()
                    if self.stopping.is_set():
                        return
                    seed = next(self.run_seeds)
            except BaseException as e:   # surfaced after the window
                self.error = e

        self.thread = threading.Thread(target=supervise, name="bench-publisher",
                                       daemon=True)
        self.thread.start()

    def wait_for(self, n: int, timeout: float = 600.0) -> None:
        t_end = time.perf_counter() + timeout
        while len(self.records) < n:
            if self.error is not None or time.perf_counter() > t_end:
                raise RuntimeError(f"publisher made {len(self.records)} of {n} "
                                   f"checkpoints: {self.error!r}")
            time.sleep(0.01)

    def stop(self) -> None:
        self.stopping.set()
        self.thread.join()


def checked_from_checkpoints(root: str, seg_iters: int) -> list:
    """Set-up: the first published segments' per-node state, for the reference."""
    from repro.serve import snapshot

    out = []
    for j in range(1, CHECKED_SEGMENTS + 1):
        st = snapshot.train_state_from_checkpoint(root, j * seg_iters)
        _, extra = snapshot.from_checkpoint(root, j * seg_iters)
        out.append((np.asarray(st.W, np.float64), np.asarray(st.W_sum, np.float64),
                    float(extra["objective"])))
    return out


def publish_segments(run: Run, pub: Publishing) -> None:
    """The window's training: whole segments published inside it."""
    t0, t1 = run.window
    inside = [(t, it) for t, it in pub.records if t0 <= t <= t1]
    prev = None
    for t, it in inside:
        if prev is not None:
            ran = it - prev[1] if it > prev[1] else it
            run.segments.append((prev[0], t, ran))
        prev = (t, it)


def temp_root() -> str:
    return tempfile.mkdtemp(prefix="bench-ckpt-")
