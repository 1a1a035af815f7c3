"""The scoped reading of a profiler trace (``scopes.py``) and the per-layer
metrics built on it, on traces recorded on a TPU v5e.

The two older recorded traces come from a program with no ``gadget.*`` scopes, no
kernel names and no ``train.*`` spans: on them the new readers find nothing,
and the metrics that were there read what they read before.
"""
import gzip
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

import bench
import run
import scopes
import tracing

DATA = Path(__file__).parent / "data"
NEW = ("objective_pass_ms.train", "push_sum_mix_us.train", "ell_fleet_half_step_roofline",
       "fleet_half_step_roofline", "segment_boundary_host_ms.train",
       "host_readbacks_per_segment.train")
# operations XLA adds on its own (memory-space copies, constants): no op-name path
COMPILER_MADE = ("%copy-start", "%copy-done", "%copy.", "%copy ", "%copy_bitcast_fusion",
                 "%iota")


def _unzip(tmp_path, name) -> str:
    path = tmp_path / f"{name}.xplane.pb"
    with gzip.open(DATA / f"{name}.xplane.pb.gz") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def _ctx(path, cell, iters_per_segment=200):
    """The reader's context of a recorded window: its segments are the
    ``bench.segment`` spans inside the window, on the trace's clock."""
    tr = tracing.reduce(path)
    lo, hi = tr.window
    segs = [(s * 1e-9, e * 1e-9, iters_per_segment) for n, s, e in tr.spans
            if n == "bench.segment" and s >= lo and e <= hi]
    r = SimpleNamespace(config=bench.load_cell(cell).config, segments=segs)
    ctx = run.Context(r, tr, (lo * 1e-9, hi * 1e-9), bench.peaks_for("TPU v5 lite"))
    ctx.trace_path = path
    return ctx


def _read(name, ctx):
    return bench.load_reader(name)(ctx)


@pytest.mark.parametrize("name, cell, idle", [
    ("train_window", "webspam_train", 68.72020579182848),
    ("serve_window", "ccat_train", 95.64815662863396),
])
def test_old_traces_read_as_before_and_new_readers_find_nothing(tmp_path, name, cell, idle):
    ctx = _ctx(_unzip(tmp_path, name), cell)
    assert _read("device_idle_share.train", ctx) == pytest.approx(idle, abs=1e-9)
    busy = {"train_window": 0.009821455, "serve_window": 0.042318093}[name]
    assert tracing.busy_s(ctx.trace) == pytest.approx(busy, abs=1e-12)
    assert sum(tracing.op_seconds(ctx.trace).values()) == pytest.approx(busy, abs=1e-9)
    for m in NEW:
        assert _read(m, ctx) is None, m
    sc = scopes.load(ctx)
    assert sc.spans == [] and scopes.scope_seconds(sc) == {None: pytest.approx(busy, abs=1e-9)}
    assert scopes.idle_gaps(sc) == tracing.idle_gaps(ctx.trace)


def test_train_step_mfu_reads_as_before(tmp_path):
    ctx = _ctx(_unzip(tmp_path, "train_window"), "webspam_train")
    assert len(ctx.segments()) == 4
    assert _read("train_step_mfu", ctx) == pytest.approx(0.15855953905326148, rel=1e-12)


def test_xspace_reader_names_every_operation_the_program_made(tmp_path):
    """Every leaf operation has its op-name path, save those XLA makes itself;
    the older program's half-step kernel sits under the ``cond`` branch, unnamed."""
    sc = scopes.read(_unzip(tmp_path, "train_window"))
    leaf = scopes.leaf_ops(sc)
    assert len(leaf) == 36583
    assert all(n.startswith(COMPILER_MADE) for n, tf_op, _ in leaf if tf_op is None)
    kernel = {tf_op for n, tf_op, _ in leaf if n.startswith("%branch_1_fun.1 ")}
    assert kernel == {"jit(segment)/while/body/closed_call/cond/branch_1_fun/pallas_call:"}


def test_scope_of_takes_the_innermost_gadget_scope():
    assert scopes.scope_of("jit(segment)/while/body/gadget.half_step/gadget.project/mul:") \
        == "gadget.project"
    assert scopes.scope_of("jit(segment)/gadget.objective/reduce_sum:") == "gadget.objective"
    assert scopes.scope_of("jit(segment)/broadcast_in_dim:") is None
    assert scopes.scope_of(None) is None


# Two windows traced on a TPU v5e with this program's scopes, kernel names and
# spans: two 200-iteration segments of ccat_train, and webspam_train segments.
SCOPED = {"ccat": ("train_window_scoped_ccat", "ccat_train"),
          "webspam": ("train_window_scoped", "webspam_train")}


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("scoped")
    return {k: _ctx(_unzip(tmp, name), cell) for k, (name, cell) in SCOPED.items()}


@pytest.mark.parametrize("which", sorted(SCOPED))
def test_busy_time_sits_under_the_programs_scopes(scoped, which):
    sc = scopes.load(scoped[which])
    leaf = scopes.leaf_ops(sc)
    busy = sum(sec for _, _, sec in leaf)
    # what has no op name is what XLA made or rewrote: copies, constants,
    # the block map's sort and scatter; never a kernel
    unnamed = [(n, sec) for n, tf_op, sec in leaf if tf_op is None]
    assert not any("custom-call" in n for n, _ in unnamed)
    assert sum(sec for _, sec in unnamed) / busy < 0.05
    by = scopes.scope_seconds(sc)
    assert 1 - by.get(None, 0.0) / busy >= 0.95
    assert set(by) - {None} <= {"gadget.half_step", "gadget.project", "gadget.push_sum_mix",
                                "gadget.average", "gadget.eps_check", "gadget.objective"}


def test_ccat_fusions_land_in_their_scopes(scoped):
    """``%fusion.3`` is the objective's gather over every ELL slot; ``%fusion.5``,
    whose scatter root XLA left without an op name, is the prefetch
    half-step's decay-and-scatter over the padded (10, 47,360) plane."""
    ops = {n.partition(" = ")[0]: tf_op for n, tf_op in scopes.load(scoped["ccat"]).scopes.items()}
    assert scopes.scope_of(ops["%fusion.3"]) == "gadget.objective"
    assert scopes.scope_of(ops["%fusion.5"]) == "gadget.half_step"
    assert "ell_fleet_half_step_gather" in ops["%ell_fleet_half_step_gather.1"]


def test_gaps_inside_a_readback_are_named_by_it(scoped):
    sc = scopes.load(scoped["ccat"])
    gaps = scopes.idle_gaps(sc)
    assert gaps[0][0] == "train.readback" and gaps[0][1] > 0.004
    assert {n for n, _ in gaps} <= {"train.readback", "train.segment.dispatch",
                                    "train.segment.wait", "train.segment.account",
                                    "train.segment", "bench.segment", "no bench span"}
    assert tracing.idle_gaps(sc.trace)[0][0] == "bench.segment"    # what the ledger names


@pytest.mark.parametrize("which, metric, value", [
    ("ccat", "objective_pass_ms.train", 514.4049845),
    ("ccat", "push_sum_mix_us.train", 11.441184999999887),
    ("ccat", "ell_fleet_half_step_roofline", 0.018543704561587666),
    ("ccat", "segment_boundary_host_ms.train", 3.7649589999999997),
    ("ccat", "host_readbacks_per_segment.train", 5.0),
    ("ccat", "fleet_half_step_roofline", None),
])
def test_new_readers_on_the_scoped_traces(scoped, which, metric, value):
    got = _read(metric, scoped[which])
    assert got == (None if value is None else pytest.approx(value, rel=1e-9))
