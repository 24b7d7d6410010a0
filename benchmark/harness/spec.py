"""BENCHMARK.json and the files it names.  Everything that belongs to one
configuration, one traffic mix or one per-layer metric is found BY NAME:
``configs/<config>.json``, ``traffic/<mix>.json``, ``metrics/<metric>.py``.
A later PR adds a cell by adding entries and files, never by editing these."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    """BENCHMARK.json, or a file it names, does not hold what was asked for."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: tuple     # metric entries this cell reports with --trace 0
    per_layer: tuple      # metric entries this cell reports with --trace 1


def _load_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def _in_cell(metric: dict, cell: str, reports: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in reports


def load_cell(name: str, *, spec_path: str | None = None,
              data_dir: str | None = None) -> Cell:
    """The cell ``name`` with its configuration, its traffic and the metric
    entries it reports.  ``spec_path`` / ``data_dir`` let the tests point at
    a tiny benchmark of their own; the chip runs use the defaults."""
    spec = _load_json(spec_path or os.path.join(ROOT, "BENCHMARK.json"),
                      "benchmark")
    data_dir = data_dir or BENCH_DIR
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; BENCHMARK.json has "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names configuration "
                        f"{w['config']!r}, which BENCHMARK.json lacks")
    config = _load_json(os.path.join(ROOT, configs[w["config"]]["file"])
                        if spec_path is None else
                        os.path.join(data_dir, "configs", w["config"] + ".json"),
                        f"configuration {w['config']!r}")
    traffic = _load_json(os.path.join(data_dir, "traffic", w["traffic"] + ".json"),
                         f"traffic mix {w['traffic']!r}")
    e2e = tuple(m for m in spec["end_to_end"] if _in_cell(m, name, set()))
    reports = {m["name"] for m in e2e}
    per_layer = tuple(m for m in spec["per_layer"] if _in_cell(m, name, reports))
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=config, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)


def load_metric_reader(name: str):
    """``metrics/<name>.py``: a module with ``read(ctx) -> float | None``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"unknown metric {name!r}: no reader "
                        f"{os.path.relpath(path, ROOT)}")
    mod_spec = importlib.util.spec_from_file_location(
        "benchmark.metrics." + name.replace(".", "__").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"metric reader {path} defines no read(ctx)")
    return mod.read
