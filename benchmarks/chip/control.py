#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

  python3 benchmarks/chip/control.py --workload <cell> --seeds <n> [--first <seed>]
      [--seconds <s>] [--out <file.jsonl>]

For each seed, in one process: the cell's set-up and, for training, its first
checked segments through the program; for serving, a short window at the
cell's own load. Then the numbers ``check.py`` compares, read for

  program      the system under test (sound runs: the lower reading)
  control      the reference computed in bfloat16 put in the program's place
  no_mix       the reference with the exchange between nodes left out
  half_batch   the reference with half the nodes' rows left out
  altered      (serving) the program's answers with one score changed
  dropped      (serving) the program's answers with half of them left out

A state left unchanged reads 1 on every training number by their definition
and needs no run. Each reading is one JSON line; the last line is the summary:
per number, the largest program reading and the smallest of the others.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (str(HERE), str(HERE.parents[1] / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

import bench  # noqa: E402
import check  # noqa: E402


def training_readings(cell, state, program):
    import jax.numpy as jnp

    import window

    trainer = window.Trainer(cell, state, program)
    run = window.Run(cell=cell.name, config=cell.config, traffic=cell.traffic, seconds=0)
    run.train_seed = trainer.first_seed
    run.checked = window.checked_segments(trainer)
    del trainer
    ref = check.reference_segments(run, state)
    out = {"program": check.training_numbers(run.checked, ref)}
    variants = {"control": dict(dtype=jnp.bfloat16), "no_mix": dict(fault="no_mix"),
                "half_batch": dict(fault="half_batch")}
    for name, kw in variants.items():
        out[name] = check.training_numbers(check.reference_segments(run, state, **kw), ref)
    return out


def serving_readings(cell, state, serve_mod, seconds, seed):
    import window

    server = window.Server(cell, state, serve_mod)
    server.warm_up()
    run = window.Run(cell=cell.name, config=cell.config, traffic=cell.traffic, seconds=seconds)
    window.serve_window(run, server, seconds, seed)
    cols, vals = server.cols, server.vals
    out = {"program": check.serving_numbers(run, cols, vals),
           "control": check.serving_numbers(run, cols, vals, control=True)}
    answered = np.nonzero(np.isfinite(run.score))[0]
    saved = run.score.copy()
    i = answered[len(answered) // 2]
    run.score[i] += 0.01 * max(abs(saved[i]), 1e-6)
    out["altered"] = check.serving_numbers(run, cols, vals)
    run.score = saved.copy()
    run.score[answered[::2]] = np.nan
    out["dropped"] = check.serving_numbers(run, cols, vals)
    return out


def summarize(rows):
    names = sorted({n for r in rows for n in r["numbers"]})
    summary = {}
    for n in names:
        prog = [r["numbers"][n] for r in rows if r["variant"] == "program" and n in r["numbers"]]
        other = {}
        for r in rows:
            if r["variant"] != "program" and n in r["numbers"]:
                other[r["variant"]] = min(other.get(r["variant"], np.inf), r["numbers"][n])
        summary[n] = {"program_max": max(prog) if prog else None,
                      "program_seeds": len(prog), "others_min": other}
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=2**31 + 1000)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import run as runner

    cell = bench.load_cell(args.workload)
    runner.chip_devices(runner.configure_jax(), cell.chips)
    from repro import serve as serve_mod
    from repro.core import gadget as program

    import window

    rows = []
    sink = open(args.out, "w") if args.out else None
    for i in range(args.seeds):
        seed = args.first + 7919 * i
        t0 = time.perf_counter()
        state = window.make_state(cell, seed, program)
        got = {}
        if "train" in cell.traffic:
            got.update({k: dict(v) for k, v in training_readings(cell, state, program).items()})
        if "serve" in cell.traffic and not cell.traffic.get("train"):
            for k, v in serving_readings(cell, state, serve_mod, args.seconds,
                                         state["serve_seed"]).items():
                got.setdefault(k, {}).update(v)
        for variant, numbers in got.items():
            row = {"seed": seed, "variant": variant, "numbers": numbers,
                   "seconds": time.perf_counter() - t0}
            rows.append(row)
            line = json.dumps(row)
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
        del state
    summary = {"workload": args.workload, "summary": summarize(rows)}
    print(json.dumps(summary), flush=True)
    if sink:
        sink.write(json.dumps(summary) + "\n")
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
