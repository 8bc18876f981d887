"""Find a cell's configuration, traffic, reference and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one metric
lives in a file of its own, named after it:

  BENCHMARK.json                      cells, metrics, bounds (repo root)
  bench/configs/<config>.json         sizes as run, source, cuts, reference
  bench/configs/<reference>.py        the plain float32 reference it names
  bench/traffic/<traffic>.json        the job mix one generator reads
  bench/metrics/<metric>.py           one reader per metric

So a new cell, configuration or metric is new files plus entries in
``BENCHMARK.json``; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: tuple[str, ...] | None     # None: every cell reports it


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path}") from e
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: not JSON: {e}") from e


def load_module(path: Path, name: str):
    """Import one file of the benchmark by path (readers, references)."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    mod_spec = importlib.util.spec_from_file_location(f"bench_{name}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def _metric(entry: dict) -> Metric:
    wl = entry.get("workloads")
    return Metric(entry["name"], entry["unit"],
                  tuple(wl) if wl is not None else None)


def reports(metric: Metric, cell_name: str) -> bool:
    return metric.workloads is None or cell_name in metric.workloads


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def load_cell(name: str, bench: dict | None = None,
              root: Path = ROOT) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = tuple(m for m in map(_metric, bench["end_to_end"])
                if reports(m, name))
    per = tuple(m for m in map(_metric, bench["per_layer"])
                if reports(m, name))
    return Cell(name, int(w["chips"]), w["config"], config, traffic, e2e, per)


def metric_reader(metric_name: str, root: Path = ROOT):
    """The ``read(run)`` function of one metric's reader file."""
    module = load_module(root / "bench" / "metrics" / f"{metric_name}.py",
                         metric_name.replace(".", "_").replace("-", "_"))
    return module.read


def reference_module(config: dict, root: Path = ROOT):
    ref = config["reference"]
    return load_module(root / "bench" / "configs" / f"{ref}.py",
                       ref.replace("-", "_"))
