"""Profiler trace of the start of a run's window, and its reduction to device times.

``Capture`` traces the first ``TRACE_SECONDS`` of the window (training: to the
end of the segment running then) with ``jax.profiler``, host tracing at its
lowest level, and marks the traced stretch with a ``bench.window`` host span
so the device events can be cut to it. A whole window of a fast cell would be
a trace of hundreds of MB. ``reduce(path)`` reads the ``.xplane.pb`` with
``ProfileData`` and keeps:

  ops     (name, start_ns, end_ns) of every operation on each TPU's
          "XLA Ops" line; a name is the HLO instruction's text (Pallas kernels
          are ``custom-call`` instructions with no stable name yet)
  spans   (name, start_ns, end_ns) of the benchmark's own ``bench.*`` host spans
  window  (start_ns, end_ns) of the ``bench.window`` span

The functions below it are the whole arithmetic: busy time as the union of
leaf operation intervals, time per operation name, and idle gaps named by the
host span that covers them. Control flow (``while``, ``conditional``,
``call``) is on the same line and spans the operations it runs, so it counts
toward none of them.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import time
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
TRACE_SECONDS = 2.0
CONTAINERS = (" while(", " conditional(", " call(")


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)     # device name -> [(name, s, e)]
    spans: list = field(default_factory=list)   # [(name, s, e)] host bench.* spans
    window: tuple = (0, 0)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def device_ops(self) -> list:
        """All devices' operations inside the window, clipped to it."""
        lo, hi = self.window
        out = []
        for ops in self.ops.values():
            out += [(n, max(s, lo), min(e, hi)) for n, s, e in ops if e > lo and s < hi]
        return out


class Capture:
    """Trace from ``start()`` until the first ``maybe_stop()`` at least
    ``seconds`` later (or ``stop()``), into ``out_dir`` (emptied first).
    ``start`` and the stopping call run on the same thread."""

    def __init__(self, out_dir: str, seconds: float = TRACE_SECONDS):
        self.out_dir, self.seconds = out_dir, seconds
        self.stretch = None          # (start, end) of the traced stretch, host clock
        self.active = False

    def start(self) -> None:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self.span.__enter__()
        self.stretch = (time.perf_counter(), None)
        self.active = True

    def maybe_stop(self) -> None:
        if self.active and time.perf_counter() - self.stretch[0] >= self.seconds:
            self.stop()

    def stop(self) -> None:
        """Ends the stretch, then writes the trace, which takes seconds: the
        per-layer metrics read only what happened inside the stretch."""
        import jax

        if self.active:
            self.stretch = (self.stretch[0], time.perf_counter())
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active = False


def trace_file(out_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    return files[-1]


def reduce(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.ops[plane.name] = [(ev.name, ev.start_ns, ev.end_ns)
                                          for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        tr.spans.append((ev.name, ev.start_ns, ev.end_ns))
    windows = [(s, e) for n, s, e in tr.spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path} holds no {WINDOW_SPAN} span")
    tr.window = max(windows, key=lambda w: w[1] - w[0])
    return tr


def union_ns(intervals) -> int:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def is_leaf(op: str) -> bool:
    """False for control flow (while, conditional, call): such an event spans
    the operations it runs and the idle time between them."""
    return not any(c in op for c in CONTAINERS)


def leaf_intervals(tr: Trace, ops) -> list:
    """(start, end) of the leaf operations in ``ops``, clipped to the window."""
    lo, hi = tr.window
    return [(max(s, lo), min(e, hi)) for n, s, e in ops if e > lo and s < hi and is_leaf(n)]


def busy_s(tr: Trace) -> float:
    """Seconds in which a leaf operation ran, averaged over the traced devices."""
    per_dev = [union_ns(leaf_intervals(tr, ops)) for ops in tr.ops.values()]
    return sum(per_dev) / max(1, len(per_dev)) * 1e-9


def short_name(op: str) -> str:
    """``%fusion.3 = f32[59376520]{0:T(1024)} fusion(...)`` → ``%fusion.3 f32[59376520] fusion``."""
    head, _, rest = op.partition(" = ")
    if not rest:
        return op[:120]
    m = re.match(r"(\(.*?\)|\S+)\s+([\w\-]+)\(", rest)
    if not m:
        return head
    shape = re.sub(r"\{[^}]*\}", "", m.group(1))
    return f"{head} {shape[:60]} {m.group(2)}"


def op_seconds(tr: Trace) -> dict:
    """Seconds per operation inside the window, summed over devices; control
    flow (while, conditional, call), which spans the operations it runs, is
    left out."""
    out = {}
    for n, s, e in tr.device_ops():
        if not is_leaf(n):
            continue
        k = short_name(n)
        out[k] = out.get(k, 0) + (e - s)
    return {n: v * 1e-9 for n, v in out.items()}


def idle_gaps(tr: Trace):
    """(name, seconds) of every gap between leaf operations of the first
    device, longest first, each named by the innermost ``bench.*`` host span
    over its midpoint."""
    lo, hi = tr.window
    if not tr.ops:
        return []
    ops = sorted(leaf_intervals(tr, next(iter(tr.ops.values()))))
    gaps, cur = [], lo
    for s, e in ops:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    spans = [sp for sp in tr.spans if sp[0] != WINDOW_SPAN]
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        over = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        name = max(over, key=lambda sp: sp[1])[0] if over else "no bench span"
        out.append((name, (e - s) * 1e-9))
    return sorted(out, key=lambda g: -g[1])


def breakdown(tr: Trace, top: int = 10) -> dict:
    ops = sorted(op_seconds(tr).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, v] for n, v in ops],
            "idle_gaps": [[n, v] for n, v in idle_gaps(tr)[:top]]}
