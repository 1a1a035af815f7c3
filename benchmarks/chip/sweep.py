#!/usr/bin/env python3
"""Knee sweep of a serving cell: offered rate against sustained rate and p99.

  python3 benchmarks/chip/sweep.py --workload ccat_serve --rates 1000,2000,4000
      [--seconds 10] [--seed n] [--out file.jsonl]

One process makes the cell's set-up once (data, server, warm-up and, for a
train-while-serve cell, the publisher), then runs the cell's open-loop window
at each offered rate in turn. Per rate it prints one JSON line: offered and
sustained queries/s (answered requests over the time from the window's start
to the last answer), p50, p95 and p99 latency from due time, the generator's p99
lag, and the growth of latency from the window's first tenth to its last (a
queue that grows all through the run is past the knee). The rate a cell
fixes is about four fifths of the highest rate that is sustained without a
growing queue.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (str(HERE), str(HERE.parents[1] / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

import bench  # noqa: E402


def summarize(run, rate):
    lat = (run.done - run.due) * 1e3
    answered = np.isfinite(run.score)
    n = len(lat)
    tenth = max(1, n // 10)
    span = (run.done[answered].max() - run.window[0]) if answered.any() else float("nan")
    return {"offered_qps": rate, "requests": n, "answered": int(answered.sum()),
            "sustained_qps": float(answered.sum() / span),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95, method="higher")),
            "p99_ms": float(np.percentile(lat, 99, method="higher")),
            "lag_p99_ms": float(np.percentile((run.sent - run.due) * 1e3, 99, method="higher")),
            "first_tenth_p50_ms": float(np.median(lat[:tenth])),
            "last_tenth_p50_ms": float(np.median(lat[-tenth:]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=2**31 + 99)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import run as runner

    cell = bench.load_cell(args.workload)
    if "serve" not in cell.traffic:
        raise LookupError(f"{args.workload} serves no requests")
    jax = runner.configure_jax()
    runner.chip_devices(jax, cell.chips)
    from repro import serve as serve_mod
    from repro.core import gadget as program

    import window

    state = window.make_state(cell, args.seed, program)
    pub = None
    if cell.traffic.get("train", {}).get("publish"):
        pub = window.Publishing(cell, state, serve_mod, program, window.temp_root())
        pub.wait_for(1)
    server = window.Server(cell, state, serve_mod, root=pub.root if pub else None)
    server.warm_up()
    runner.settle()
    sink = open(args.out, "w") if args.out else None
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            cell.traffic["serve"]["rate_qps"] = rate
            run = window.Run(cell=cell.name, config=cell.config, traffic=cell.traffic,
                             seconds=args.seconds)
            window.serve_window(run, server, args.seconds, args.seed + i)
            line = json.dumps(summarize(run, rate))
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
    finally:
        if pub is not None:
            pub.stop()
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
