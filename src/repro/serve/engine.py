"""SvmServer: snapshot-and-serve engine over the fused predict kernels.

The serving half of the anytime loop: load a model (live
:class:`~repro.serve.snapshot.Snapshot` or versioned checkpoint, f32 or
int8+scale), then answer queries three ways —

  * :meth:`score` — dense (B, d) batches through the fused scores+argmax
    kernel (``ops.dense_predict``), one launch per batch;
  * :meth:`score_sparse` — padded-ELL (B, k) batches through the query-side
    touched-block kernel (``ops.ell_predict``): the batch's compact
    touched-block-id map is built on host (``formats.block_map``) and steers
    the W DMA, so a CCAT-shaped sparse query touches only the d-blocks its
    features live in;
  * :func:`make_mesh_scorer` — the batch-parallel ``shard_map`` path: w
    replicated (closed over), queries sharded over the mesh's batch axis, the
    multi-device shape of the ROADMAP's serve-heavy-traffic goal.

Every distinct static shape is jitted once and cached;
``stats()["distinct_shapes"]`` is the measured compile count the bucketed
batcher's ≤ len(buckets) guarantee is asserted against
(``benchmarks/serve_bench.py``). The same stats dict tracks blocks visited by
the sparse path vs the dense sweep equivalent — the serving twin of the
training bench's ``blocks_visited_ratio``.

Live updates: the compiled executables take the weight plane as a *runtime*
argument, so :meth:`SvmServer.swap_weights` replaces the model under load
without invalidating the jit cache — same shapes, same executables,
``distinct_shapes`` stays flat across swaps (the hot-swap tests pin this).
:meth:`SvmServer.watch` + :meth:`SvmServer.maybe_reload` turn that into the
consuming half of the live train-to-serve loop: between batcher drains the
server polls the checkpoint root's ``LATEST`` pointer
(``repro.checkpoint.read_latest``) and hot-swaps whenever the version moved —
forward when :class:`~repro.serve.publisher.TrainPublisher` publishes,
backward when an operator rolls back via ``checkpoint.point_latest``.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.kernels.hinge_subgrad import ops as hinge_ops
from repro.kernels.hinge_subgrad import ref as hinge_ref
from repro.serve import snapshot as snap_mod
from repro.serve.batcher import Bucket
from repro.sparse.formats import DEFAULT_BUCKET_BLK_D, block_map
from repro.telemetry import trace as tmtr
from repro.telemetry.registry import Registry

__all__ = ["SvmServer", "make_mesh_scorer"]

# Counters every server keeps on its registry (as ``serve.<key>`` series);
# stats() reads them back under these exact keys for back-compat.
_STAT_KEYS = ("queries", "batches", "sparse_batches", "blocks_visited",
              "dense_block_equivalent", "cap_overflows", "swaps",
              "reload_errors", "quarantined", "plane_swaps")


class SvmServer:
    """Load-once, score-many serving engine for GADGET SVM models.

    ``W``: (d,) binary weights or (C, d) one-vs-rest class matrix.
    ``use_kernels=None`` (default) follows the package convention — Pallas
    kernels wherever they compile natively, jnp oracles where they would only
    interpret — so a CPU replica and a TPU replica run the same engine.
    ``use_kernels=True`` forces the kernel path (interpret off-TPU; what CI
    exercises). ``meta`` carries the checkpoint's manifest ``extra`` when
    loaded from disk (iteration, objective, export dtype). ``registry``: the
    telemetry registry the ``serve.*`` counters and per-call kernel
    launch/bytes accounting land on — private per server by default, pass a
    shared one to fold several components into one dump.
    """

    def __init__(self, W, *, meta: dict | None = None,
                 blk_d: int = DEFAULT_BUCKET_BLK_D,
                 use_kernels: bool | None = None,
                 reload_quarantine: int = 3,
                 registry: Registry | None = None):
        W = np.asarray(W, np.float32)
        if W.ndim not in (1, 2):
            raise ValueError(f"W must be (d,) or (C, d), got {W.shape}")
        if reload_quarantine < 1:
            raise ValueError(
                f"reload_quarantine must be >= 1, got {reload_quarantine}")
        self.W = W
        self.binary = W.ndim == 1
        self.d = int(W.shape[-1])
        self.n_classes = 1 if self.binary else int(W.shape[0])
        self.meta = dict(meta or {})
        self.blk_d = int(blk_d)
        self.n_d_blocks = -(-self.d // self.blk_d)
        if use_kernels is None:
            use_kernels = not hinge_ops.default_interpret()
        self.use_kernels = bool(use_kernels)
        self.reload_quarantine = int(reload_quarantine)
        self._W_dev = jnp.asarray(W)
        # Weight planes the degradation ladder can step between: "f32" is the
        # full-precision model, "int8" (built lazily on first use) is the
        # int8-quantize→dequantize image of the same weights. Same shape and
        # dtype, so switching planes is a runtime-argument swap — the jit
        # cache (and therefore ``distinct_shapes``) never moves.
        self._planes: dict[str, jax.Array] = {"f32": self._W_dev}
        self._plane = "f32"
        self._compiled: dict[tuple, object] = {}
        self._watch_root: str | None = None
        self._watch_step: int | None = None
        self._reload_failures: dict[int, int] = {}
        # (step, swap ctx) awaiting its first scoring call — the lineage
        # chain's terminal "serve.first_score" event fires once per swap
        self._pending_first_score: tuple[int, tmtr.TraceContext] | None = None
        # All serving counters live on a telemetry registry (private per
        # server unless one is shared in) — stats() is a *view* over it, and
        # kernel launch/bytes accounting lands beside the serve counters.
        self.registry = registry if registry is not None else Registry()

    def _count(self, key: str, n: int = 1) -> None:
        self.registry.counter(f"serve.{key}").inc(n)

    # ------------------------------------------------------------- loading

    @classmethod
    def from_snapshot(cls, snap: snap_mod.Snapshot, **kw) -> "SvmServer":
        """Serve a live training snapshot (no disk round-trip)."""
        meta = {"iteration": snap.iteration, "objective": snap.objective}
        return cls(snap.w, meta=meta, **kw)

    @classmethod
    def load(cls, root: str, step: int | None = None, **kw) -> "SvmServer":
        """Restore a ``serve.snapshot.to_checkpoint`` export (f32 or int8 —
        quantized weights are dequantized once here; scoring runs f32)."""
        w, extra = snap_mod.from_checkpoint(root, step)
        return cls(w, meta=extra, **kw)

    @classmethod
    def watch(cls, root: str, **kw) -> "SvmServer":
        """Serve the checkpoint the root's ``LATEST`` pointer designates and
        keep watching it: the returned server's :meth:`maybe_reload` polls
        the pointer and hot-swaps when the published version moves (forward
        — a live :class:`~repro.serve.publisher.TrainPublisher` — or
        backward — an operator rollback via ``checkpoint.point_latest``).
        Call ``maybe_reload()`` between batcher drains; it is cheap (one
        small file read) when nothing changed."""
        step = ckpt.read_latest(root)
        if step is None:
            raise FileNotFoundError(f"no complete checkpoints under {root}")
        t0 = time.monotonic()
        w, extra = snap_mod.from_checkpoint(root, step)
        srv = cls(w, meta=extra, **kw)
        srv._watch_root = root
        srv._watch_step = step
        # the initial install is a swap too (version 0 of this server's
        # life) — without it the first published version's lineage chain
        # would have no serve-side stages
        srv._emit_swap_span(step, time.monotonic() - t0, extra=extra)
        return srv

    # ------------------------------------------------------------ hot swap

    def swap_weights(self, W, *, meta: dict | None = None) -> None:
        """Replace the served model in place, under load, without recompiling.

        ``W`` must match the current model's shape — (d,) vs (C, d) and both
        extents — because shapes key the compiled-executable cache; the cache
        itself is untouched (every executable takes the weight plane as a
        runtime argument), so ``stats()["distinct_shapes"]`` is invariant
        across swaps and in-flight batches simply score against whichever
        plane was installed when their launch read it. A shape change is a
        different model: build a new server. ``meta`` (e.g. the new
        checkpoint's manifest ``extra``) replaces :attr:`meta` when given."""
        W = np.asarray(W, np.float32)
        if W.shape != self.W.shape:
            raise ValueError(
                f"hot swap must preserve the weight shape {self.W.shape} "
                f"(compiled executables are shape-keyed), got {W.shape}")
        self.W = W
        had_int8 = "int8" in self._planes
        self._planes = {"f32": jnp.asarray(W)}
        if had_int8:
            # keep the degraded plane in lockstep with the live model, so a
            # hot swap while degraded serves the NEW weights' int8 image
            self._planes["int8"] = self._build_int8_plane()
        self._W_dev = self._planes[self._plane]
        if meta is not None:
            self.meta = dict(meta)
        self._count("swaps")

    def maybe_reload(self) -> int | None:
        """Poll the watched root once; hot-swap if ``LATEST`` moved.

        Returns the newly-installed step when a swap happened, None when the
        pointer is unchanged (the overwhelmingly common case — one small
        file read, no array I/O). Any failure mid-reload (pointer damage, a
        checkpoint deleted between pointer read and restore, a bad export)
        counts ``stats()["reload_errors"]`` and keeps serving the current
        model — a live replica must never wedge on a bad publish.

        A step that fails to load ``reload_quarantine`` times is
        *quarantined*: the server stops retrying it every poll (no repeated
        array I/O against a known-bad export, counted once in
        ``stats()["quarantined"]``) while continuing to watch the pointer —
        the next *different* published step gets a fresh chance, and an
        operator rollback to a good older step swaps normally."""
        if self._watch_root is None:
            raise RuntimeError(
                "server is not watching a checkpoint root — construct it "
                "with SvmServer.watch(root)")
        try:
            step = ckpt.read_latest(self._watch_root)
        except Exception:
            self._count("reload_errors")
            return None
        if step is None or step == self._watch_step:
            return None
        fails = self._reload_failures.get(step, 0)
        if fails >= self.reload_quarantine:
            return None
        t0 = time.monotonic()
        try:
            w, extra = snap_mod.from_checkpoint(self._watch_root, step)
            self.swap_weights(w, meta=extra)
        except Exception as e:
            self._count("reload_errors")
            self._reload_failures[step] = fails + 1
            quarantined = fails + 1 == self.reload_quarantine
            if quarantined:
                self._count("quarantined")
            self._emit_swap_span(step, time.monotonic() - t0, extra=None,
                                 error=("quarantined" if quarantined
                                        else f"{type(e).__name__}: {e}"))
            return None
        self._watch_step = step
        self._reload_failures.pop(step, None)
        self._emit_swap_span(step, time.monotonic() - t0, extra=extra)
        return step

    def _emit_swap_span(self, step: int, seconds: float, *,
                        extra: dict | None, error: str | None = None) -> None:
        """Emit the lineage ``serve.swap`` span for one reload attempt.

        Linked through the checkpoint manifest's ``extra["trace"]`` (the
        publish span's context); the failed-load path re-reads the manifest
        best-effort since ``from_checkpoint`` never returned. No-op for
        untraced checkpoints, so tracing off emits nothing. A successful
        swap arms the one-shot ``serve.first_score`` event the next scoring
        call completes the chain with."""
        trace = (extra or {}).get("trace")
        if trace is None:
            try:
                manifest = ckpt.read_manifest(self._watch_root, step)
                trace = (manifest.get("extra") or {}).get("trace")
            except Exception:
                return
        parent = tmtr.TraceContext.from_extra(trace)
        if parent is None:
            return
        ctx = parent.child()
        tmtr.emit_span(self.registry, "serve.swap", ctx, seconds,
                       version=step, error=error)
        if error is None:
            self._pending_first_score = (step, ctx)

    def _note_first_score(self) -> None:
        """Fire the pending ``serve.first_score`` lineage event, if armed —
        called by every scoring path; one event per successful swap."""
        if self._pending_first_score is None:
            return
        step, ctx = self._pending_first_score
        self._pending_first_score = None
        tmtr.emit_event(self.registry, "serve.first_score", ctx.child(),
                        version=step)

    @property
    def quarantined_steps(self) -> list[int]:
        """Checkpoint steps the watcher has given up retrying (sorted)."""
        return sorted(s for s, n in self._reload_failures.items()
                      if n >= self.reload_quarantine)

    # ------------------------------------------------- degradation ladder

    def _build_int8_plane(self) -> "jax.Array":
        """The int8-quantize→dequantize image of the current weights —
        what an int8 export of this model would serve (same shape/dtype as
        the f32 plane, so it swaps in without touching the jit cache)."""
        q, scale = snap_mod.quantize_int8(self.W)
        return jnp.asarray(snap_mod.dequantize_int8(q, scale))

    @property
    def plane(self) -> str:
        """The weight plane currently being served (``"f32"`` or ``"int8"``)."""
        return self._plane

    @property
    def degraded(self) -> bool:
        """True while the server is on a degraded (non-f32) weight plane."""
        return self._plane != "f32"

    def set_plane(self, name: str) -> None:
        """Serve from the named weight plane — the overload ladder's
        precision step (``repro.serve.overload.DegradeLadder`` drives this).

        ``"int8"`` installs the quantize→dequantize image of the current
        weights (built on device the first time — call once at startup to
        pre-warm so a mid-overload step-down never pays the build);
        ``"f32"`` restores full precision. Either way the swap is a runtime
        argument change: same shapes, same compiled executables,
        ``stats()["distinct_shapes"]`` stays flat across ladder transitions
        (asserted by ``benchmarks/overload_bench.py``). Composes with
        :meth:`swap_weights`: a hot swap while degraded re-quantizes the new
        weights and keeps serving the degraded plane."""
        if name not in ("f32", "int8"):
            raise ValueError(f"unknown weight plane {name!r} "
                             "(expected 'f32' or 'int8')")
        if name == "int8" and "int8" not in self._planes:
            self._planes["int8"] = self._build_int8_plane()
        if name != self._plane:
            self._count("plane_swaps")
        self._plane = name
        self._W_dev = self._planes[name]
        self.registry.gauge("serve.degraded").set(float(self.degraded))

    # ------------------------------------------------------------- scoring

    def _jit(self, key, build):
        fn = self._compiled.get(key)
        if fn is None:
            fn = self._compiled[key] = build()
        return fn

    def score(self, X) -> tuple[np.ndarray, np.ndarray]:
        """Dense batch: X (B, d) → (scores, labels) — binary ((B,), ±1 f32),
        multiclass ((B, C), int32 argmax). One fused kernel launch per call;
        one compile per distinct B."""
        X = np.asarray(X, np.float32)
        B, d = X.shape
        if d != self.d:
            raise ValueError(f"query d={d} != model d={self.d}")
        if self.use_kernels:
            fn = self._jit(("dense", B), lambda: jax.jit(functools.partial(
                hinge_ops.dense_predict, interpret=hinge_ops.default_interpret())))
        else:
            fn = self._jit(("dense", B), lambda: jax.jit(self._dense_oracle))
        scores, labels = fn(self._W_dev, jnp.asarray(X))
        self._count("queries", B)
        self._count("batches")
        self._note_first_score()
        if self.use_kernels:
            # The kernel runs inside jit, so the eager self-recording in ops
            # never fires — account the launch here, at the host boundary.
            hinge_ops.record_launch("dense_predict", registry=self.registry,
                                    B=B, d=d, C=self.n_classes)
        return np.asarray(scores), np.asarray(labels)

    def _dense_oracle(self, W, X):
        scores = hinge_ref.predict_scores_ref(W[None] if self.binary else W, X)
        return hinge_ops._finish_predict(scores, jnp.argmax(scores, axis=-1)
                                         .astype(jnp.int32), X.shape[0],
                                         self.n_classes, self.binary)

    def score_sparse(self, cols, vals, *, n_blocks_max: int | None = None
                     ) -> tuple[np.ndarray, np.ndarray]:
        """Sparse ELL batch: (B, k) padded planes → (scores, labels).

        ``n_blocks_max`` is the static map width (per-bucket constant when
        called through the batcher — one compile per bucket); defaults to the
        structural ``min(B·k, n_d_blocks)``. The touched-block map is built
        on host over the *actual batch* — blocks the batch doesn't live in
        are never DMA'd — and padded with sentinels to the static width.

        A batch touching more blocks than the cap (live traffic heavier than
        the calibration sample) is still served correctly: the map widens to
        the realized count, rounded up to an 8-multiple so over-cap traffic
        adds a bounded number of shapes, and ``stats()["cap_overflows"]``
        counts it — the signal to re-run ``calibrate_buckets``. It never
        raises mid-drain, so the batcher queue cannot wedge on one batch."""
        cols = np.asarray(cols, np.int32)
        vals = np.asarray(vals, np.float32)
        B, k = cols.shape
        if k == 0:
            cols = np.zeros((B, 1), np.int32)
            vals = np.zeros((B, 1), np.float32)
            k = 1
        cap = hinge_ops.resolve_block_cap(B, k, n_d_blocks=self.n_d_blocks,
                                          n_blocks_max=n_blocks_max)
        live = len(np.unique(cols[vals != 0] // self.blk_d))
        if live > cap:
            cap = min(-(-live // 8) * 8, self.n_d_blocks)
            self._count("cap_overflows")
        bm = block_map(cols[None], vals[None], self.blk_d, self.n_d_blocks, cap)[0]
        key = ("ell", B, k, cap)
        if self.use_kernels:
            fn = self._jit(key, lambda: jax.jit(functools.partial(
                hinge_ops.ell_predict, blk_d=self.blk_d,
                interpret=hinge_ops.default_interpret())))
            scores, labels = fn(self._W_dev, jnp.asarray(cols),
                                jnp.asarray(vals), block_ids=jnp.asarray(bm))
        else:
            fn = self._jit(key, lambda: jax.jit(self._ell_oracle))
            scores, labels = fn(self._W_dev, jnp.asarray(cols), jnp.asarray(vals))
        self._count("queries", B)
        self._count("batches")
        self._count("sparse_batches")
        self._note_first_score()
        self._count("blocks_visited", live)
        self._count("dense_block_equivalent", self.n_d_blocks)
        if self.use_kernels:
            hinge_ops.record_launch("ell_predict", registry=self.registry,
                                    blocks_visited=live, B=B, k=k,
                                    C=self.n_classes, blk_d=self.blk_d,
                                    n_blocks_max=cap)
        return np.asarray(scores), np.asarray(labels)

    def _ell_oracle(self, W, cols, vals):
        scores = hinge_ref.ell_predict_scores_ref(
            W[None] if self.binary else W, cols, vals)
        return hinge_ops._finish_predict(scores, jnp.argmax(scores, axis=-1)
                                         .astype(jnp.int32), cols.shape[0],
                                         self.n_classes, self.binary)

    def scorer_for(self, bucket: Bucket | None = None):
        """The ``score_fn`` the micro-batcher drains with. Each batch is
        scored with its own bucket's static ``n_blocks_max`` (the batcher
        passes the bucket per batch), so every batch of a bucket reuses one
        compiled executable; pass ``bucket`` to pin one cap for every batch
        instead."""
        def score_fn(b: Bucket, cols, vals):
            cap = (bucket or b).n_blocks_max
            return self.score_sparse(cols, vals, n_blocks_max=cap)
        return score_fn

    # --------------------------------------------------------------- stats

    def stats(self) -> dict:
        """Serving counters: queries/batches served, ``distinct_shapes``
        (jit-cache size — the compile count asserted flat across hot swaps
        *and* degradation-ladder transitions), ``swaps`` / ``reload_errors``
        / ``quarantined`` from the watch path, the sparse blocks-visited
        accounting vs a dense sweep, and the overload ladder's visible state
        (``degraded`` 0/1, the served ``plane`` name, ``plane_swaps``).

        A *view* over :attr:`registry` (the ``serve.*`` counter series) with
        the historical flat keys preserved — consumers that want the kernel
        launch/bytes series too should read the registry directly."""
        s = {k: int(self.registry.value(f"serve.{k}")) for k in _STAT_KEYS}
        s["distinct_shapes"] = len(self._compiled)
        s["blocks_visited_ratio"] = (
            s["blocks_visited"] / s["dense_block_equivalent"]
            if s["dense_block_equivalent"] else float("nan"))
        s["degraded"] = int(self.degraded)
        s["plane"] = self._plane
        return s


def make_mesh_scorer(W, *, mesh=None, axis: str = "batch",
                     use_kernels: bool | None = None):
    """Batch-parallel serving step: w replicated, queries sharded.

    Returns ``scorer(X) -> (scores, labels)`` where X's leading axis is
    sharded over ``mesh``'s ``axis`` (defaults to a 1-D mesh over every local
    device) and the class weights are closed over — replicated to each shard,
    never gathered. B must divide by the axis size (pad with zero rows; they
    score 0 and slice away). ``check_vma=False`` for the kernel path — jax
    has no ``pallas_call`` replication rule inside ``shard_map`` yet, same
    pin as the training mesh step."""
    from jax.sharding import Mesh, PartitionSpec as P

    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), (axis,))
    W_dev = jnp.asarray(np.asarray(W, np.float32))
    if use_kernels is None:
        use_kernels = not hinge_ops.default_interpret()
    binary = W_dev.ndim == 1

    def per_shard(Xl):
        if use_kernels:
            return hinge_ops.dense_predict(
                W_dev, Xl, interpret=hinge_ops.default_interpret())
        scores = hinge_ref.predict_scores_ref(
            W_dev[None] if binary else W_dev, Xl)
        labels = jnp.argmax(scores, axis=-1).astype(jnp.int32)
        return hinge_ops._finish_predict(scores, labels, Xl.shape[0],
                                         1 if binary else W_dev.shape[0], binary)

    sharded = jax.shard_map(per_shard, mesh=mesh, in_specs=P(axis),
                            out_specs=(P(axis), P(axis)), check_vma=False)
    return jax.jit(sharded)
