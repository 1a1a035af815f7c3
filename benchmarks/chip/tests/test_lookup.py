"""Cells, mixes and metrics are found by name; a later change adds them as new
files and new entries, and a name that is not there fails loudly."""
import hashlib
import json
import shutil
from pathlib import Path

import pytest

import bench

ROOT = bench.ROOT
HERE = bench.HERE


def test_every_cell_resolves():
    names = [w["name"] for w in bench.load_benchmark()["workloads"]]
    for name in names:
        cell = bench.load_cell(name)
        assert cell.per_layer and cell.end_to_end
        assert {m["name"] for m in cell.per_layer} == set(cell.readers)
        assert "setup_s" in {m["name"] for m in cell.end_to_end}


@pytest.mark.parametrize("what", ["cell", "traffic", "config", "metric", "limits", "peaks"])
def test_missing_name_fails_loudly(tmp_path, what):
    root, here = _copy(tmp_path)
    bench_json = json.loads((root / "BENCHMARK.json").read_text())
    cell = "ccat_train"
    if what == "traffic":
        bench_json["workloads"][0]["traffic"] = "no_such_mix"
    elif what == "config":
        bench_json["workloads"][0]["config"] = "no_such_config"
    elif what == "metric":
        bench_json["per_layer"].append({"name": "no_such_metric", "unit": "%", "better": "higher",
                                        "source": "device_trace", "layer": "device",
                                        "moves": "train_samples_per_s"})
    elif what == "limits":
        (here / "limits" / f"{cell}.json").unlink()
    elif what == "cell":
        cell = "no_such_cell"
    (root / "BENCHMARK.json").write_text(json.dumps(bench_json))
    with pytest.raises(LookupError):
        if what == "peaks":
            bench.peaks_for("TPU v0 imaginary", here)
        else:
            bench.load_cell(cell, root, here)


def _copy(tmp_path):
    root = tmp_path / "checkout"
    here = root / "benchmarks" / "chip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root, here


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


BURSTY = {"serve": {"arrivals": "on_off", "rate_qps": 500, "burst": {"period_s": 0.5, "on_share": 0.25},
                   "query_nnz": {"dist": "lognormal", "median": 76, "sigma": 0.7, "max": 256},
                   "rows_per_batch": 8, "min_k": 16}}


def _add_bursty_cell(root, here):
    """A new cell on a new mix (bursts, heavy-tailed query lengths), a new
    end-to-end metric entry and a new per-layer metric: files and entries only."""
    (here / "traffic" / "serve_bursty.json").write_text(json.dumps(BURSTY))
    (here / "limits" / "ccat_serve_bursty.json").write_text(json.dumps(
        {"score_err": 1e-5, "unanswered": 0}))
    (here / "layer_metrics" / "answered_share.serve.py").write_text(
        "import numpy as np\n\n\ndef read(ctx):\n"
        "    return 100.0 * float(np.isfinite(ctx.run.score).mean())\n")
    bench_json = json.loads((root / "BENCHMARK.json").read_text())
    bench_json["workloads"].append({"name": "ccat_serve_bursty", "config": "ccat",
                                    "traffic": "serve_bursty", "chips": 1, "why": "test"})
    bench_json["end_to_end"].append({"name": "serve_p99_ms", "unit": "ms", "better": "lower",
                                     "bound": 0.25, "source": "host_clock",
                                     "workloads": ["ccat_serve_bursty"]})
    bench_json["per_layer"].append({"name": "answered_share.serve", "unit": "%",
                                    "better": "higher", "source": "host_clock",
                                    "layer": "micro-batcher", "moves": "serve_p99_ms",
                                    "workloads": ["ccat_serve_bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench_json))


def test_new_cell_mix_and_metric_are_new_files_and_entries(tmp_path):
    root, here = _copy(tmp_path)
    before = _digest(here)
    _add_bursty_cell(root, here)
    cell = bench.load_cell("ccat_serve_bursty", root, here)
    assert cell.traffic == BURSTY
    assert list(cell.readers) == ["answered_share.serve"]
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "serve_p99_ms"}
    after = _digest(here)
    assert all(after[k] == v for k, v in before.items()), "an existing file changed"


def test_new_mix_runs_from_its_data_alone(tmp_path):
    """The added bursty, mixed-length cell runs end to end at a CPU test's
    size through the harness as it is: arrivals only in the bursts, queries
    of many lengths in several buckets, every answer correct."""
    import time

    import numpy as np

    import run as runner
    from conftest import SMALL_ROWS

    root, here = _copy(tmp_path)
    _add_bursty_cell(root, here)
    cell = bench.load_cell("ccat_serve_bursty", root, here)
    cell.config["dataset"].update(SMALL_ROWS)
    import window

    seen = {}
    real_e2e, real_warm = runner.end_to_end, window.Server.warm_up

    def keep_run(run, setup_s):
        seen["run"] = run
        return real_e2e(run, setup_s)

    def keep_server(server):
        seen["server"] = server
        return real_warm(server)

    runner.end_to_end, window.Server.warm_up = keep_run, keep_server
    try:
        result, _ = runner.run_cell(cell, 2**31 + 77, 2.0, False, t_start=time.perf_counter(),
                                    require_tpu=False)
    finally:
        runner.end_to_end, window.Server.warm_up = real_e2e, real_warm
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"setup_s", "serve_p99_ms"}
    run, server = seen["run"], seen["server"]
    assert len(run.due) == 1000
    assert np.all(((run.due - run.window[0]) % 0.5) < 0.125 + 1e-9)
    sent = server.nnz[run.queries]
    assert sent.min() < 40 and sent.max() > 150 and abs(np.median(sent) - 76) < 10
    assert len({server.mb.bucket_for(int(n)).k for n in sent}) >= 4
