"""Find a cell's files by the names ``BENCHMARK.json`` gives them."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable

from . import compare

ROOT = Path(__file__).resolve().parent.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: Callable  # (RunRecord) -> float | None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(bench_dir: Path, name: str) -> Callable:
    """``metrics/<name>.py``'s ``read`` function."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics(bench_dir: Path, entries: list, workload: str) -> tuple[Metric, ...]:
    return tuple(
        Metric(m["name"], m["unit"], load_reader(bench_dir, m["name"]))
        for m in entries
        if workload in m.get("workloads", [workload])
    )


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    return make_cell(workload, w["chips"], w["config"], w["traffic"], root)


def load_traffic(traffic_name: str, root: Path = ROOT) -> dict:
    """``traffic/<traffic_name>.json``."""
    bench = _load_json(root / "BENCHMARK.json")
    return _load_json(root / bench["paths"][0] / "traffic" / f"{traffic_name}.json")


def load_limits(name: str, root: Path = ROOT) -> dict:
    """``limits/<name>.json``: the cell's limits on its gaps to the
    reference. A cell without them, or whose limits compare none of
    those gaps, would be judged without a reference, and raises."""
    bench = _load_json(root / "BENCHMARK.json")
    path = root / bench["paths"][0] / "limits" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"{name}: no limits at {path}")
    limits = _load_json(path)
    if not set(limits) & set(compare.GAPS):
        raise ValueError(f"{name}: {path} limits none of {compare.GAPS}")
    return limits


def make_cell(name: str, chips: int, config_name: str, traffic_name: str,
              root: Path = ROOT) -> Cell:
    """A cell from its configuration's and traffic mix's names, with its
    limits and the metrics ``BENCHMARK.json`` gives it."""
    bench = _load_json(root / "BENCHMARK.json")
    bench_dir = root / bench["paths"][0]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(root / configs[config_name]["file"])
    traffic = load_traffic(traffic_name, root)
    if traffic["nodes"] != chips:
        raise ValueError(
            f"{name}: traffic {traffic_name!r} has {traffic['nodes']} "
            f"nodes for {chips} chips (one node per chip)"
        )
    return Cell(
        name=name,
        chips=chips,
        config_name=config_name,
        traffic_name=traffic_name,
        config=config,
        traffic=traffic,
        limits=load_limits(name, root),
        end_to_end=_metrics(bench_dir, bench["end_to_end"], name),
        per_layer=_metrics(bench_dir, bench["per_layer"], name),
    )


def load_peaks(device_kind: str, bench_dir: Path = ROOT / "chipbench") -> dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    peaks = _load_json(bench_dir / "peaks.json")
    if device_kind not in peaks:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json "
            f"(known: {sorted(peaks)})"
        )
    return peaks[device_kind]
