"""Find a cell's pieces by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; every piece sits in a file of
its own, found by name, so a later change adds a cell, a mix or a metric as
new files and new entries without editing one that is there:

  configs/<config>.json        the configuration's sizes, settings and source
  traffic/<traffic>.json       the mix's parameters, read by ``window.py``
  layer_metrics/<metric>.py    a per-layer metric's reader (``read(run)``)
  limits/<cell>.json           the correctness limits of the cell
  peaks.json                   the chip's peaks, keyed by ``device_kind``

A name that is not there raises ``LookupError`` with what was looked for.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    limits: dict
    readers: dict = field(default_factory=dict)


def _load_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise LookupError(f"no {what} file {path}")
    return json.loads(path.read_text())


def load_benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json", "benchmark")


def load_reader(name: str, here: Path = HERE):
    """The ``read`` function of the per-layer metric ``name``."""
    path = here / "layer_metrics" / f"{name}.py"
    if not path.is_file():
        raise LookupError(f"per-layer metric {name!r} has no reader {path}")
    spec = importlib.util.spec_from_file_location(f"layer_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise LookupError(f"{path} defines no read(run)")
    return mod.read


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT, here: Path = HERE) -> Cell:
    """The workload ``name`` of ``BENCHMARK.json`` with all its pieces."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise LookupError(f"no workload {name!r} in BENCHMARK.json "
                          f"(there are {sorted(cells)})")
    return cell_from(cells[name], bench, root, here)


def cell_from(w: dict, bench: dict, root: Path = ROOT, here: Path = HERE) -> Cell:
    """A cell from a workload entry (name, config, traffic, chips) and the
    benchmark's configurations and metrics."""
    name = w["name"]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise LookupError(f"workload {name!r} names configuration {w['config']!r}, "
                          f"which BENCHMARK.json does not list")
    config = _load_json(root / configs[w["config"]]["file"], f"configuration {w['config']!r}")
    traffic = _load_json(here / "traffic" / f"{w['traffic']}.json", f"traffic {w['traffic']!r}")
    per_layer = [m for m in bench["per_layer"] if _reports(m, name)]
    cell = Cell(name=name, chips=int(w["chips"]), config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
                per_layer=per_layer,
                limits=_load_json(here / "limits" / f"{name}.json", f"limits of {name!r}"))
    cell.readers = {m["name"]: load_reader(m["name"], here) for m in per_layer}
    return cell


def peaks_for(device_kind: str, here: Path = HERE) -> dict:
    table = _load_json(here / "peaks.json", "peaks")
    if device_kind not in table["devices"]:
        raise LookupError(f"device kind {device_kind!r} is not in peaks.json "
                          f"(it has {sorted(table['devices'])})")
    return table["devices"][device_kind]
