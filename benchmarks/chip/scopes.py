"""Device scopes and program spans of a profiler trace, read beside ``tracing.reduce``.

``tracing.reduce`` keeps each device operation's name and the benchmark's own
``bench.*`` host spans. ``load`` reads two more things from the same
``.xplane.pb``:

  scopes  {operation name → tf_op} for every operation a device plane
          names. ``tf_op`` is JAX's op-name path, which ``jax.named_scope``
          extends (``jit(segment)/while/body/…/gadget.objective/…``); for a
          fusion it is the path of the fusion's root, or, where XLA rewrote
          the root without one, of the instructions inside the fusion. It is
          a stat of the event's *metadata*, which ``ProfileData`` does not
          expose, so ``xspace_scopes`` walks the protobuf wire format of the
          XSpace.
  spans   (name, start_ns, end_ns, stats) of the program's ``train.*`` host
          spans (``repro.telemetry`` spans are profiler annotations), on the
          device operations' clock.

A leaf operation belongs to the innermost ``gadget.*`` component of its
``tf_op`` (``gadget.project`` inside ``gadget.half_step`` is the projection).
A trace of a program without scopes or spans yields empty maps, and every
reader built on them returns None.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from pathlib import Path

import tracing

SPAN_PREFIX = "train."
SCOPE = re.compile(r"gadget\.\w+")

# XSpace field numbers (tsl/profiler/protobuf/xplane.proto)
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 2, 4, 5
_MAP_VALUE = 2
_EVENT_MD_ID, _EVENT_MD_NAME, _EVENT_MD_STATS = 1, 2, 5
_STAT_MD_ID, _STAT_MD_NAME = 1, 2
_STAT_ID, _STAT_U64, _STAT_I64, _STAT_STR, _STAT_BYTES, _STAT_REF = 1, 3, 4, 5, 6, 7
_HLO_PROTO_MODULE = 1           # xla/service/hlo.proto: HloProto.hlo_module
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?(%[\w.-]+) = ")
_CALLS = re.compile(r"calls=(%[\w.-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@dataclass
class Scoped:
    trace: tracing.Trace
    scopes: dict    # operation name -> tf_op
    spans: list     # [(name, start_ns, end_ns, stats)] of the train.* host spans


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, value) of one message in ``buf[lo:hi]``: an int for a
    varint, a (start, end) pair for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unknown protobuf wire type {wire} at byte {i}")
        yield number, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def xspace_scopes(path: str) -> dict:
    """{operation name → tf_op} over every device plane of the XSpace at
    ``path``. Only the planes' metadata maps are decoded; their event lines,
    the bulk of the file, are skipped by length. An operation with no
    ``tf_op`` of its own (XLA rewrote its root and dropped the op name, as it
    does for a batched scatter) takes the op name of the instructions inside
    its fused computation, read from the program's HLO in the trace."""
    buf = memoryview(Path(path).read_bytes())
    ops, hlo = {}, {}               # name -> (tf_op, program id); program id -> HloProto
    for number, plane in _fields(buf, 0, len(buf)):
        if number != _SPACE_PLANES:
            continue
        name, events, stat_names = "", [], {}
        for pf, pv in _fields(buf, *plane):
            if pf == _PLANE_NAME:
                name = _text(buf, pv)
            elif pf == _PLANE_EVENT_METADATA:
                events.append(pv)
            elif pf == _PLANE_STAT_METADATA:
                for mf, mv in _fields(buf, *pv):
                    if mf == _MAP_VALUE:
                        md = dict(_fields(buf, *mv))
                        if _STAT_MD_NAME in md:
                            stat_names[md.get(_STAT_MD_ID, 0)] = _text(buf, md[_STAT_MD_NAME])
        if not name.startswith(("/device:", "/host:metadata")):
            continue
        for entry in events:
            for mf, mv in _fields(buf, *entry):
                if mf != _MAP_VALUE:
                    continue
                md = {"stats": {}}
                for ef, ev in _fields(buf, *mv):
                    if ef == _EVENT_MD_ID:
                        md["id"] = ev
                    elif ef == _EVENT_MD_NAME:
                        md["name"] = _text(buf, ev)
                    elif ef == _EVENT_MD_STATS:
                        stat = dict(_fields(buf, *ev))
                        md["stats"][stat_names.get(stat.get(_STAT_ID))] = stat
                st = md["stats"]
                if name == "/host:metadata":
                    if "Hlo Proto" in st and _STAT_BYTES in st["Hlo Proto"]:
                        lo, hi = st["Hlo Proto"][_STAT_BYTES]
                        hlo[md.get("id")] = buf[lo:hi]
                elif md.get("name"):
                    tf_op = st.get("tf_op", {})
                    tf_op = (_text(buf, tf_op[_STAT_STR]) if _STAT_STR in tf_op
                             else stat_names.get(tf_op.get(_STAT_REF)))
                    pid = st.get("program_id", {})
                    ops[md["name"]] = (tf_op, pid.get(_STAT_U64, pid.get(_STAT_I64)))
    out, fused = {}, {}
    for op, (tf_op, pid) in ops.items():
        if not tf_op and pid in hlo:
            if pid not in fused:
                fused[pid] = _fused_op_names(hlo[pid])
            tf_op = fused[pid].get(op.partition(" = ")[0].strip())
        if tf_op:
            out[op] = tf_op
    return out


def _fused_op_names(hlo_proto) -> dict:
    """{instruction → op name} of the fusions in one program whose own op
    name is gone: the op name of the last instruction inside the fused
    computation that has one (the nearest to its root)."""
    from jax._src.lib import xla_client

    module = dict(_fields(hlo_proto, 0, len(hlo_proto)))[_HLO_PROTO_MODULE]
    text = xla_client._xla.HloModule.from_serialized_hlo_module_proto(
        bytes(hlo_proto[module[0]:module[1]])).to_string()
    inner, comp, calls = {}, None, []
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        op_name = _OP_NAME.search(line)
        if op_name:
            inner[comp] = op_name.group(1)
            continue
        inst, called = _INSTRUCTION.match(line), _CALLS.search(line)
        if inst and called:
            calls.append((inst.group(1), called.group(1)))
    return {inst: inner[c] for inst, c in calls if c in inner}


def program_spans(path: str, prefix: str = SPAN_PREFIX) -> list:
    """(name, start_ns, end_ns, stats) of every host span named ``prefix…``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(prefix):
                        out.append((ev.name, ev.start_ns, ev.end_ns, dict(ev.stats)))
    return sorted(out, key=lambda sp: sp[1])


_CACHE: dict = {}


def read(path: str, trace: tracing.Trace | None = None) -> Scoped:
    """The scopes and program spans of the trace at ``path`` (cached)."""
    p = Path(path)
    key = (str(p.resolve()), p.stat().st_mtime_ns)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = Scoped(trace if trace is not None else tracing.reduce(path),
                             xspace_scopes(path), program_spans(path))
    return _CACHE[key]


def load(ctx) -> Scoped | None:
    """The scoped view of a per-layer reader's trace: the file at
    ``ctx.trace_path`` when the context names one, else the newest trace in
    the runner's trace directory. None when the run was not traced."""
    if ctx.trace is None:
        return None
    path = getattr(ctx, "trace_path", None)
    if path is None:
        import run

        path = tracing.trace_file(str(run.TRACE_DIR))
    return read(path, ctx.trace)


def scope_of(tf_op: str | None) -> str | None:
    """The innermost ``gadget.*`` scope of an op-name path."""
    found = SCOPE.findall(tf_op or "")
    return found[-1] if found else None


def leaf_ops(sc: Scoped) -> list:
    """(name, tf_op, seconds) of every leaf operation in the window, clipped
    to it, over all devices."""
    return [(n, sc.scopes.get(n), (e - s) * 1e-9) for n, s, e in sc.trace.device_ops()
            if tracing.is_leaf(n)]


def scope_seconds(sc: Scoped) -> dict:
    """Device seconds per ``gadget.*`` scope in the window; operations under
    no scope count under None."""
    out = {}
    for _, tf_op, sec in leaf_ops(sc):
        k = scope_of(tf_op)
        out[k] = out.get(k, 0.0) + sec
    return out


def kernel_seconds(sc: Scoped, pattern: str) -> float:
    """Device seconds of the leaf operations whose name or ``tf_op`` holds a
    match of ``pattern`` (a kernel's ``pallas_call`` name)."""
    rx = re.compile(pattern)
    return sum(sec for n, tf_op, sec in leaf_ops(sc)
               if rx.search(n) or rx.search(tf_op or ""))


def spans_in_window(sc: Scoped, name: str) -> list:
    """The program spans named ``name`` that lie wholly inside the window."""
    lo, hi = sc.trace.window
    return [sp for sp in sc.spans if sp[0] == name and sp[1] >= lo and sp[2] <= hi]


def idle_gaps(sc: Scoped) -> list:
    """``tracing.idle_gaps``, each gap named by the innermost host span over
    its midpoint, of the benchmark's (``bench.*``) or the program's
    (``train.*``)."""
    spans = [sp for sp in sc.trace.spans if sp[0] != tracing.WINDOW_SPAN]
    spans += [sp[:3] for sp in sc.spans]
    return tracing.idle_gaps(tracing.Trace(ops=sc.trace.ops, spans=spans,
                                           window=sc.trace.window))
