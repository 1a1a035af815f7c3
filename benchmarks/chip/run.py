#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, per-layer metrics and correctness
limits are found by name from ``BENCHMARK.json`` (see ``bench.py``). The run
makes its data on the device from the seed, warms every shape the window uses
(set-up, timed from process start as ``setup_s``), measures for ``--seconds``,
then checks what the timed path produced against the plain reference. The
last line of standard output is one JSON object:

  {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"], "checks"}

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces the
window with the JAX profiler and reports its per-layer metrics. The numbers
compared are the last lines of standard error and the last key of the line.
Without a TPU, or with fewer chips than the cell asks for, it exits with code
2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench  # noqa: E402

CACHE_DIR = HERE / ".cache" / "jax"      # fixed path inside the checkout
TRACE_DIR = HERE / ".cache" / "trace"


class NoChip(RuntimeError):
    pass


def configure_jax():
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def chip_devices(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (first device: {devs[0].platform} "
                     f"{devs[0].device_kind}); this benchmark runs only on the chip")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


class Context:
    """What a per-layer reader gets: the run, its trace, the traced stretch of
    the window on the host clock (start, end), and the chip's peaks."""

    def __init__(self, run, trace, stretch, peaks):
        self.run, self.trace, self.stretch, self.peaks = run, trace, stretch, peaks

    def segments(self) -> list:
        """The training segments that ran inside the traced stretch."""
        lo, hi = self.stretch
        return [s for s in self.run.segments if s[0] >= lo and s[1] <= hi]

    def requests(self):
        """Mask of the requests due inside the traced stretch, less its last
        0.1 s, whose answers could wait on the trace being written."""
        lo, hi = self.stretch
        return (self.run.due >= lo) & (self.run.due < hi - 0.1)


def settle() -> None:
    """End of set-up: move everything set-up made into the collector's
    permanent generation. A full collection over the ~140k objects that
    importing and compiling leave took 75 ms on a CPU here and stalled the
    serving window's tail; a server freezes its start-up heap the same way."""
    gc.collect()
    gc.freeze()


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in (0, 100]) of all values."""
    v = sorted(values)
    k = max(0, min(len(v) - 1, -(-int(round(q * len(v))) // 100) - 1))
    return float(v[k])


def end_to_end(run, setup_s: float) -> dict:
    out = {"setup_s": setup_s}
    g = run.config["gadget"]
    if run.segments:
        iters = sum(r for _, _, r in run.segments)
        span = run.segments[-1][1] - run.segments[0][0]
        out["train_samples_per_s"] = g["n_nodes"] * g["batch_size"] * iters / span
    if run.due is not None and len(run.due):
        lat = (run.done - run.due) * 1e3
        out["serve_p99_ms"] = percentile(lat, 99)
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, *, t_start: float = T_START,
             require_tpu: bool = True) -> tuple[dict, str]:
    """Set-up, window, metrics and check of one run. Returns (result, log)."""
    jax = configure_jax()
    devs = chip_devices(jax, cell.chips) if require_tpu else jax.devices()
    from repro import serve as serve_mod
    from repro.core import gadget as program

    import check
    import tracing
    import window

    traffic = cell.traffic
    run = window.Run(cell=cell.name, config=cell.config, traffic=traffic, seconds=seconds)
    state = window.make_state(cell, seed, program)
    trainer = server = pub = None
    if "train" in traffic and not traffic["train"].get("publish"):
        trainer = window.Trainer(cell, state, program)
        run.train_seed = trainer.first_seed
        run.checked = window.checked_segments(trainer)
    if "train" in traffic and traffic["train"].get("publish"):
        root = window.temp_root()
        pub = window.Publishing(cell, state, serve_mod, program, root)
        run.train_seed = pub.first_seed
        pub.wait_for(window.CHECKED_SEGMENTS)
        run.checked = window.checked_from_checkpoints(
            root, int(cell.config["gadget"]["check_every"]))
    if "serve" in traffic:
        server = window.Server(cell, state, serve_mod, root=pub.root if pub else None)
        server.warm_up()
    settle()
    setup_s = time.perf_counter() - t_start

    capture = tracing.Capture(str(TRACE_DIR)) if trace else None
    try:
        if server is not None:
            window.serve_window(run, server, seconds, state["serve_seed"], capture)
        else:
            window.train_window(run, trainer, seconds, capture)
    finally:
        if pub is not None:
            pub.stop()
    if pub is not None:
        if pub.error is not None:
            raise pub.error
        window.publish_segments(run, pub)
        shutil.rmtree(pub.root, ignore_errors=True)

    stats = devs[0].memory_stats() or {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    red = None
    if trace:
        red = tracing.reduce(tracing.trace_file(str(TRACE_DIR)))
        device["busy_s"] = tracing.busy_s(red)
        device["window_s"] = red.window_s

    if trace:
        ctx = Context(run, red, capture.stretch,
                      bench.peaks_for(device["kind"]) if require_tpu else None)
        metrics = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]](ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(run, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}

    # the program's state goes before the reference runs
    del trainer
    gc.collect()
    numbers = {}
    if run.checked:
        numbers.update(check.training_numbers(run.checked, check.reference_segments(run, state)))
    if server is not None:
        numbers.update(check.serving_numbers(run, server.cols, server.vals))
    correct, checks = check.verdict(numbers, cell.limits)

    attempted = len(run.segments) + (0 if run.due is None else len(run.due))
    failed = int(numbers.get("unanswered", 0))
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if red is not None:
        result["breakdown"] = tracing.breakdown(red)
    result["checks"] = checks
    log = "\n".join(f"check {n}: {c['value']!r} (limit {c['limit']!r})"
                    for n, c in checks.items())
    if run.due is not None and len(run.due):
        lat = (run.done - run.due) * 1e3
        log = (f"latency over {len(lat)} requests (ms, from due): "
               + ", ".join(f"p{q} {percentile(lat, q)!r}" for q in (50, 90, 95, 99, 100))
               + "\n" + log)
    return result, log


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = bench.load_cell(args.workload)
        result, log = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"run: {e}", file=sys.stderr)
        return 2
    except ImportError as e:
        print(f"run: cannot import the system under test ({e}); run from the root "
              f"of a checkout of the repository", file=sys.stderr)
        return 3
    print(log, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
