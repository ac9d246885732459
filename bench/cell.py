"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  The configuration is ``bench/configs/<config>.json``, the traffic
``bench/traffic/<traffic>.json``, whose ``driver`` key names
``bench/drivers/<driver>.py``; the correctness limits of the cell are
``bench/limits/<cell>.json`` and each per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding a cell, a configuration or a
metric adds files and entries; it edits none.  A name that points at no
file is an error, raised before anything runs.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class CellError(Exception):
    """A cell, configuration, traffic, driver, limit or metric is missing
    or malformed."""


def _shown(path: Path) -> str:
    try:
        return str(path.relative_to(ROOT))
    except ValueError:
        return str(path)


def _json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise CellError(f"{what}: no file {_shown(path)}")
    return json.loads(path.read_text())


def load_module(path: Path, what: str):
    if not path.is_file():
        raise CellError(f"{what}: no file {_shown(path)}")
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    driver: object

    @property
    def model(self) -> dict:
        return self.config["model"]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, benchmark: dict | None = None, bench_dir: Path = BENCH,
         root: Path = ROOT) -> Cell:
    bm = benchmark if benchmark is not None else _json(
        root / "BENCHMARK.json", "benchmark")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise CellError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    if w["config"] not in configs:
        raise CellError(f"{name}: unknown configuration {w['config']!r}")
    config = _json(root / configs[w["config"]]["file"],
                   f"configuration {w['config']}")
    traffic = _json(bench_dir / "traffic" / f"{w['traffic']}.json",
                    f"traffic {w['traffic']}")
    if "driver" not in traffic:
        raise CellError(f"traffic {w['traffic']}: no 'driver' key")
    driver = load_module(bench_dir / "drivers" / f"{traffic['driver']}.py",
                         f"driver {traffic['driver']}")
    limits = _json(bench_dir / "limits" / f"{name}.json", f"limits of {name}")
    e2e = [m for m in bm["end_to_end"] if _applies(m, name)]
    per_layer = [m for m in bm["per_layer"] if _applies(m, name)]
    for m in per_layer:
        # every reader must exist before a run spends any chip time
        reader = bench_dir / "metrics" / f"{m['name']}.py"
        if not reader.is_file():
            raise CellError(f"metric {m['name']}: no reader "
                            f"{_shown(reader)}")
    return Cell(name, w["chips"], config, traffic, limits, e2e, per_layer,
                driver)


def read_per_layer(cell: Cell, ctx: dict, bench_dir: Path = BENCH) -> dict:
    """Each per-layer metric's reader applied to the trace context.  A
    reader that finds nothing returns None; a metric declared for this
    cell that reads nothing is an error, so that a kernel the reduction
    no longer finds stops the run instead of silently leaving its
    metric out (and moving the metrics that subtract it)."""
    out = {}
    for m in cell.per_layer:
        mod = load_module(bench_dir / "metrics" / f"{m['name']}.py",
                          f"metric {m['name']}")
        value = mod.read(ctx)
        if value is None:
            raise CellError(f"metric {m['name']} read nothing in the trace "
                            f"of {cell.name}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
