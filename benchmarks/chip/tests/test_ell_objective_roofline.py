"""The ELL objective kernel's roofline reader: its required work by hand at
CCAT's shape, nothing read where the kernel did not run, and the share from
the kernel's device seconds."""
import gzip
import importlib.util
import re
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

import bench
import run
import scopes
import tracing

DATA = Path(__file__).parent / "data"
CCAT = bench.load_cell("ccat_train").config
PEAKS = bench.peaks_for("TPU v5 lite")


def reader():
    path = bench.HERE / "layer_metrics" / "ell_objective_roofline.py"
    spec = importlib.util.spec_from_file_location("ell_objective_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_required_at_ccat():
    flops, nbytes = reader().required(CCAT)
    # 781,265 rows × (76 × 8 + 4) B, plus w once; 2 flops an entry, 4 a row
    assert nbytes == 781265 * 612 + 4 * 47236
    assert flops == 781265 * 156
    assert nbytes / PEAKS["hbm_bytes_per_s"] > flops / PEAKS["flops_per_s"]
    assert nbytes / PEAKS["hbm_bytes_per_s"] == pytest.approx(0.584e-3, rel=1e-3)


@pytest.fixture(scope="module")
def ccat_ctx(tmp_path_factory):
    """Two ccat_train segments traced on a TPU v5e with the ``jnp.take`` pass."""
    path = tmp_path_factory.mktemp("roofline") / "ccat.xplane.pb"
    with gzip.open(DATA / "train_window_scoped_ccat.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    tr = tracing.reduce(str(path))
    lo, hi = tr.window
    segs = [(s * 1e-9, e * 1e-9, 200) for n, s, e in tr.spans
            if n == "bench.segment" and s >= lo and e <= hi]
    ctx = run.Context(SimpleNamespace(config=CCAT, segments=segs), tr,
                      (lo * 1e-9, hi * 1e-9), PEAKS)
    ctx.trace_path = str(path)
    return ctx


def test_null_where_the_kernel_did_not_run(ccat_ctx):
    assert scopes.load(ccat_ctx) is not None and len(ccat_ctx.segments()) == 2
    assert bench.load_reader("ell_objective_roofline")(ccat_ctx) is None


def test_share_of_the_kernels_device_seconds(ccat_ctx, monkeypatch):
    seen = []

    def kernel_seconds(sc, pattern):
        seen.append(pattern)
        return 0.080  # two passes of 40 ms

    monkeypatch.setattr(scopes, "kernel_seconds", kernel_seconds)
    got = bench.load_reader("ell_objective_roofline")(ccat_ctx)
    nbytes = 781265 * 612 + 4 * 47236
    assert got == pytest.approx(100.0 * 2 * nbytes / PEAKS["hbm_bytes_per_s"] / 0.080)
    rx = re.compile(seen[0])
    assert rx.search("%ell_objective.1") and rx.search("jit(segment)/ell_objective/pallas_call:")
    assert not rx.search("%ell_fleet_half_step_gather.1")
    assert not rx.search("jit(segment)/gadget.objective/reduce_sum:")
