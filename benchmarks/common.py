"""Shared benchmark helpers: timing, CSV emit, runner fingerprinting, dataset
prep at bench scale."""
from __future__ import annotations

import os
import platform
import time

import jax

from repro import compile_cache
from repro.data.svm_datasets import SVMDataset, make_dataset
from repro.kernels.hinge_subgrad.ops import default_interpret

# every benchmark CLI imports this module: one persistent compile cache
compile_cache.enable()

# scale factors keep wall time sane on one CPU core while preserving each
# dataset's (d, sparsity, lambda) signature; row counts stay in the thousands.
BENCH_SCALE = {
    "adult": 0.15, "ccat": 0.006, "mnist": 0.08, "reuters": 0.6,
    "usps": 0.6, "webspam": 0.02,
}


def bench_dataset(name: str, seed: int = 0) -> SVMDataset:
    return make_dataset(name, scale=BENCH_SCALE[name], seed=seed)


def timed(fn, *args, **kw):
    t0 = time.time()
    out = fn(*args, **kw)
    out = jax.block_until_ready(out) if hasattr(out, "block_until_ready") else out
    return out, time.time() - t0


def emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}")


def runner_fingerprint() -> dict:
    """Identity of the machine/backend a benchmark JSON was recorded on.

    check_regression.py compares wall-clock leaves only between runs whose
    fingerprints match (like-vs-like) — the first step toward hard perf
    gates: a committed baseline from one runner class never produces timing
    warnings on a different one. Structural leaves are always compared.
    """
    return {
        "os": platform.system().lower(),
        "machine": platform.machine(),
        "python": ".".join(platform.python_version_tuple()[:2]),
        "backend": jax.default_backend(),
        "pallas_interpret": int(default_interpret()),
        "cpu_count": os.cpu_count() or 0,
    }


def fingerprint_slug() -> str:
    """This runner's fingerprint as the filesystem-safe slug that names
    per-runner-class baselines (``benchmarks/baselines/<stem>.<slug>.json``).
    Delegates to check_regression's formatter so recording and matching can
    never drift apart."""
    from benchmarks.check_regression import fingerprint_slug as _slug
    return _slug(runner_fingerprint())
