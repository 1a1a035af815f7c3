"""Shared set-up of the harness tests: the harness and the system on the path,
and cells shrunk to sizes a CPU test run can hold.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q``.
"""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
for p in (str(HERE), str(HERE.parents[1] / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench  # noqa: E402

SMALL_ROWS = {"n_train": 4000, "n_test": 600}
SMALL_TRAIN = {"check_every": 20, "max_iters": 100}
SMALL_RATE = 200.0


# mixes the driver supports that no cell of BENCHMARK.json runs yet
# (PERF.md section 7)
UNLISTED = {name: {"name": name, "config": "ccat", "traffic": traffic, "chips": 1}
            for name, traffic in (("ccat_serve", "serve_open"),
                                  ("ccat_train_serve", "train_serve"))}


def small_cell(name: str):
    """The cell as ``BENCHMARK.json`` defines it, cut to a CPU test's size:
    fewer rows and shorter runs, every width and setting kept."""
    if name in UNLISTED:
        cell = bench.cell_from(UNLISTED[name], bench.load_benchmark())
    else:
        cell = bench.load_cell(name)
    cell.config["dataset"].update(SMALL_ROWS)
    cell.config["gadget"].update(SMALL_TRAIN)
    if "serve" in cell.traffic:
        cell.traffic["serve"]["rate_qps"] = SMALL_RATE
    return cell


@pytest.fixture
def fresh_program():
    """The program's compiled segment cache emptied around a test that
    patches what the segment traces."""
    from repro.core import gadget

    gadget._make_segment_train.cache_clear()
    yield gadget
    gadget._make_segment_train.cache_clear()


@pytest.fixture(autouse=True, scope="session")
def _caches_outside_the_checkout(tmp_path_factory):
    """Test runs keep their compile cache and traces out of the checkout."""
    import run

    base = tmp_path_factory.mktemp("bench-cache")
    run.CACHE_DIR, run.TRACE_DIR = base / "jax", base / "trace"
    yield
