"""Find a cell's pieces by name: its entry in ``BENCHMARK.json``, its
configuration file, its traffic mix and its per-layer metric readers.

Nothing here names a cell, a configuration or a metric: a later cell,
configuration, mix or metric is added as files and entries, and this
module finds them by the names in ``BENCHMARK.json``:

  * ``<config file>``            the entry's ``file`` (JSON), under paths;
  * ``bench/mixes/<traffic>.json``  the traffic mix (parameters only);
  * ``bench/metrics/<metric>.py``   one reader per per-layer metric,
                                    a ``read(ctx)`` returning a number
                                    or None when it finds nothing.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]
    readers: dict[str, Callable[[Any], float | None]]


def _applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def load_reader(bench_dir: Path, name: str) -> Callable:
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric {name!r}: "
                                f"{path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(root: Path, workload: str,
              bench_dir: Path | None = None) -> Cell:
    """The cell `workload` of ``root/BENCHMARK.json``. `bench_dir` is
    where mixes and metric readers live (``root/bench`` by default)."""
    root = Path(root)
    bench_dir = Path(bench_dir) if bench_dir else root / "bench"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = json.loads((bench_dir / "mixes" / f"{w['traffic']}.json")
                     .read_text())
    e2e = tuple(Metric(m["name"], m["unit"])
                for m in spec["end_to_end"] if _applies(m, workload))
    layer = tuple(Metric(m["name"], m["unit"])
                  for m in spec["per_layer"] if _applies(m, workload))
    readers = {m.name: load_reader(bench_dir, m.name) for m in layer}
    return Cell(workload, int(w["chips"]), config, mix, e2e, layer,
                readers)
