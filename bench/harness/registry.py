"""Resolve a cell's pieces by name: BENCHMARK.json is the only index.

Everything that belongs to one configuration, traffic mix, generator, window
driver or metric sits in a file of its own, found by its name:

    configs      -> the ``file`` of the configuration's entry
    traffic mix  -> bench/traffic/<traffic>.json
    generator    -> bench/generators/<config["generator"]["kind"]>.py
    driver       -> bench/drivers/<traffic["driver"]>.py
    end-to-end   -> bench/end_to_end/<metric>.py
    per-layer    -> bench/layer_metrics/<metric>.py

so a later change adds a cell, a mix or a metric by adding files and entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType



class SpecError(ValueError):
    """BENCHMARK.json names something that is not there."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    bench_dir: Path         # where the cell's files are found
    chips: int
    config: dict          # the configuration file, as run
    traffic: dict         # the traffic file
    end_to_end: list[dict]  # BENCHMARK.json entries this cell reports
    per_layer: list[dict]


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_plugin_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def plugin(kind: str, name: str, bench_dir: Path) -> ModuleType:
    return load_module(bench_dir / kind / f"{name}.py")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(root: Path, workload: str) -> Cell:
    """The cell named ``workload`` in ``root``/BENCHMARK.json; its files are
    under ``root``/bench."""
    bench_dir = root / "bench"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names no configuration")
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    tpath = bench_dir / "traffic" / f"{w['traffic']}.json"
    if not tpath.is_file():
        raise SpecError(f"no traffic file {tpath}")
    traffic = json.loads(tpath.read_text())
    e2e = [m for m in spec["end_to_end"] if _reports(m, workload)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if _reports(m, workload) and m["moves"] in moved]
    return Cell(workload, bench_dir, int(w["chips"]), config, traffic, e2e, layer)
