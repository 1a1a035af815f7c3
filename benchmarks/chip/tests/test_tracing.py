"""The trace reduction: busy time as the union of device intervals, time per
kernel, and idle gaps named by the host span over them."""
import tracing


def _trace():
    ms = 1_000_000
    tr = tracing.Trace()
    tr.window = (0, 100 * ms)
    tr.ops["/device:TPU:0"] = [
        ("fusion.1", -5 * ms, 10 * ms),                 # clipped to the window
        ("_ell_grad_prefetch_kernel", 10 * ms, 30 * ms),
        ("fusion.2", 20 * ms, 40 * ms),                 # overlaps the kernel
        ("_ell_grad_prefetch_kernel", 60 * ms, 70 * ms),
        ("fusion.3", 95 * ms, 120 * ms),
    ]
    tr.spans = [("bench.window", 0, 100 * ms), ("bench.segment", 0, 40 * ms),
                ("bench.drain", 40 * ms, 62 * ms), ("bench.segment", 62 * ms, 100 * ms)]
    return tr


def test_union_merges_overlaps():
    assert tracing.union_ns([(0, 10), (5, 20), (30, 40), (40, 45)]) == 35
    assert tracing.union_ns([]) == 0


def test_busy_is_the_union_inside_the_window():
    tr = _trace()
    # [0,40] ∪ [60,70] ∪ [95,100] = 55 ms of 100
    assert abs(tracing.busy_s(tr) - 0.055) < 1e-12
    assert abs(tr.window_s - 0.1) < 1e-12


WHILE = "%while.4 = (s32[], f32[10,47236]{1,0:T(8,128)}) while(%tuple.1), condition=%c"
COND = "%conditional.2 = (f32[10,47236]{1,0:T(8,128)}) conditional(%p), branch_computations={}"


def test_control_flow_is_not_busy_and_does_not_hide_gaps():
    """A while loop's event spans its body's operations and the idle time
    between them: busy time and the gaps come from the leaf operations."""
    ms = 1_000_000
    tr = _trace()
    tr.ops["/device:TPU:0"] += [(WHILE, 0, 100 * ms), (COND, 40 * ms, 95 * ms)]
    assert abs(tracing.busy_s(tr) - 0.055) < 1e-12
    assert [round(s, 6) for _, s in tracing.idle_gaps(tr)] == [0.025, 0.02]


def test_op_seconds_leave_out_control_flow_and_shorten_names():
    tr = _trace()
    tr.ops["/device:TPU:0"].append((WHILE, 0, 10**8))
    sec = tracing.op_seconds(tr)
    assert abs(sec["_ell_grad_prefetch_kernel"] - 0.030) < 1e-12
    assert not any("while" in n for n in sec)
    assert tracing.short_name("%fusion.3 = f32[59376520]{0:T(1024)} fusion(f32[47236] %a), "
                              "kind=kCustom") == "%fusion.3 f32[59376520] fusion"


def test_gaps_are_named_by_the_innermost_host_span():
    gaps = tracing.idle_gaps(_trace())
    assert [round(s, 6) for _, s in gaps] == [0.025, 0.02]
    assert [n for n, _ in gaps] == ["bench.segment", "bench.drain"]


def test_breakdown_lists_at_most_ten():
    br = tracing.breakdown(_trace())
    assert br["device_ops"][0][0] == "_ell_grad_prefetch_kernel"
    assert len(br["device_ops"]) <= 10 and len(br["idle_gaps"]) <= 10


def _recorded(tmp_path, name):
    import gzip
    import shutil
    from pathlib import Path

    path = tmp_path / f"{name}.xplane.pb"
    with gzip.open(Path(__file__).parent / "data" / f"{name}.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tracing.reduce(str(path))


def test_recorded_training_trace(tmp_path):
    """Four 200-iteration segments of ``webspam_train`` traced on a TPU v5e.
    Each segment is one ``while`` event that spans its body's operations and
    the idle time between them: busy time is the leaf operations' time, not
    the loop's."""
    tr = _recorded(tmp_path, "train_window")
    assert list(tr.ops) == ["/device:TPU:0"]
    assert abs(tr.window_s - 0.03139872) < 1e-9
    ops = tr.ops["/device:TPU:0"]
    loops = [(s, e) for n, s, e in ops if " while(" in n]
    assert len(loops) > 100 and max(e - s for s, e in loops) > 3e6     # a segment's loop: ~3.9 ms
    busy = tracing.busy_s(tr)
    leaf_sum = sum(tracing.op_seconds(tr).values())
    assert busy <= leaf_sum + 1e-12
    assert abs(busy - 0.009821455) < 1e-9
    with_loops = tracing.union_ns([(max(s, tr.window[0]), min(e, tr.window[1]))
                                   for _, s, e in ops if e > tr.window[0] and s < tr.window[1]])
    assert with_loops * 1e-9 > 1.5 * busy           # what counting the loops would claim
    gaps = tracing.idle_gaps(tr)
    assert abs(sum(s for _, s in gaps) + busy - tr.window_s) < 1e-6
    assert gaps[0][0] == "bench.segment" and gaps[0][1] > 0.003   # the host's segment boundary
    br = tracing.breakdown(tr)
    assert br["device_ops"][0][0] == "%branch_1_fun.1 f32[10,1,256] custom-call"  # the half-step


def test_recorded_chip_trace(tmp_path):
    """About one second of ``ccat_serve`` traced on a TPU v5e: 1,519 of the
    benchmark's own host spans and 4,515 device operations."""
    tr = _recorded(tmp_path, "serve_window")
    assert list(tr.ops) == ["/device:TPU:0"]
    assert abs(tr.window_s - 0.972417649) < 1e-9
    busy = tracing.busy_s(tr)
    # the device ops of one TensorCore do not overlap: busy is their sum
    assert abs(busy - sum(tracing.op_seconds(tr).values())) < 1e-9
    assert 0.04 < busy < 0.045
    ops = sorted(tracing.op_seconds(tr).items(), key=lambda kv: -kv[1])
    assert ops[0][0] == "%_unknown_.1 (f32[8,128], s32[8]) custom-call"   # the scoring kernel
    assert ops[0][1] / busy > 0.95
    gaps = tracing.idle_gaps(tr)
    assert abs(sum(s for _, s in gaps) + busy - tr.window_s) < 1e-6
    assert {n for n, _ in gaps} <= {"bench.drain", "bench.submit", "no bench span"}
    br = tracing.breakdown(tr)
    assert len(br["device_ops"]) == 10 and len(br["idle_gaps"]) == 10
