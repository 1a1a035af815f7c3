#!/usr/bin/env python3
"""Print what a profiler trace holds, to read it by hand before trusting a reduction.

  python3 benchmarks/chip/trace_dump.py <dir or .xplane.pb> [--top N]

For every plane: its lines with their event counts, and the N names that took
most time on each line, with one event's stats.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tracing  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args()
    path = args.path if args.path.endswith(".pb") else tracing.trace_file(args.path)
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            tot = {}
            first = {}
            for ev in events:
                tot[ev.name] = tot.get(ev.name, 0) + ev.duration_ns
                first.setdefault(ev.name, ev)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[: args.top]:
                stats = {k: v for k, v in list(first[name].stats)[:6]}
                print(f"    {ns / 1e6:10.3f} ms  {name[:90]!r}  {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
