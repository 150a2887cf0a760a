"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<traffic>.json``); a per-layer metric is read by
``bench/metrics/<metric>.py``; a kernel's operations and bytes come from
``bench/roofline/<kernel>.py``. Adding any of these is adding a file and an
entry: nothing here names a cell.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Dict, List

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec: dict = None) -> Cell:
    spec = spec or benchmark()
    for w in spec["workloads"]:
        if w["name"] == name:
            break
    else:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    return Cell(
        name=name,
        config_name=w["config"],
        config=load_json(BENCH / "configs" / f"{w['config']}.json"),
        traffic_name=w["traffic"],
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        chips=int(w["chips"]),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
    )


def _load_module(path: pathlib.Path, name: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    if mod_spec is None or mod_spec.loader is None:
        raise ImportError(f"cannot load {path}")
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def metric_reader(name: str):
    """The reader module of metric ``name``: ``bench/metrics/<name>.py``,
    else that of its base name before the first ``.``, so a quantity split
    by the cells it serves (``fit_ms.sparse`` beside ``fit_ms``) keeps one
    reader."""
    for stem in (name, name.split(".", 1)[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.exists():
            return _load_module(path, f"bench_metric_{stem.replace('.', '_')}")
    raise FileNotFoundError(f"no reader for metric {name!r} in {BENCH / 'metrics'}")


def roofline(kernel: str):
    """The operation and byte count module of ``kernel``."""
    return _load_module(BENCH / "roofline" / f"{kernel}.py",
                        f"bench_roofline_{kernel}")


def roofline_kernels() -> List[str]:
    return sorted(p.stem for p in (BENCH / "roofline").glob("*.py")
                  if not p.stem.startswith("_"))


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json")
    return table["devices"][device_kind]
