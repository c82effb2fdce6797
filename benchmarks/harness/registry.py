"""Find a cell's files by the names in BENCHMARK.json.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under the benchmark's directory; a
later PR adds files and a ``workloads`` entry and edits nothing here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
REPO_ROOT = BENCH_DIR.parent


def _load_json(path: pathlib.Path) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_module(path: pathlib.Path) -> ModuleType:
    """Import one generator or reader file by path (no package edit needed)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark module: {path}")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{path.parent.name}_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: tuple[str, ...] | None  # None = every cell

    def applies_to(self, workload: str) -> bool:
        return self.workloads is None or workload in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


class Registry:
    """``root`` holds BENCHMARK.json; ``bench_dir`` the benchmark's files.
    Both default to this checkout; the tests point them at a copy."""

    def __init__(self, root: pathlib.Path = REPO_ROOT,
                 bench_dir: pathlib.Path | None = None) -> None:
        self.root = pathlib.Path(root)
        self.bench_dir = pathlib.Path(bench_dir or self.root / BENCH_DIR.name)
        self.benchmark = _load_json(self.root / "BENCHMARK.json")

    def _metrics(self, key: str, workload: str) -> tuple[Metric, ...]:
        out = []
        for m in self.benchmark[key]:
            w = m.get("workloads")
            metric = Metric(m["name"], m["unit"], tuple(w) if w else None)
            if metric.applies_to(workload):
                out.append(metric)
        return tuple(out)

    def cell(self, workload: str) -> Cell:
        entries = {w["name"]: w for w in self.benchmark["workloads"]}
        if workload not in entries:
            raise KeyError(f"unknown workload {workload!r}; "
                           f"BENCHMARK.json has {sorted(entries)}")
        entry = entries[workload]
        configs = {c["name"]: c for c in self.benchmark["configs"]}
        config = _load_json(self.root / configs[entry["config"]]["file"])
        traffic = _load_json(
            self.bench_dir / "traffic" / f"{entry['traffic']}.json")
        return Cell(
            name=workload, chips=int(entry["chips"]),
            config_name=entry["config"], config=config,
            traffic_name=entry["traffic"], traffic=traffic,
            end_to_end=self._metrics("end_to_end", workload),
            per_layer=self._metrics("per_layer", workload))

    def generator(self, kind: str) -> ModuleType:
        return load_module(self.bench_dir / "generators" / f"{kind}.py")

    def metric_reader(self, metric: str):
        """(read function, its arguments) for one per-layer metric."""
        spec = _load_json(self.bench_dir / "metrics" / f"{metric}.json")
        module = load_module(
            self.bench_dir / "readers" / f"{spec['reader']}.py")
        return module.read, dict(spec.get("args", {}))

    def peaks(self, device_kind: str) -> dict:
        table = _load_json(self.bench_dir / "peaks.json")["devices"]
        if device_kind not in table:
            raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                           f"({sorted(table)}): add it with its source")
        return table[device_kind]
