"""Resolve a cell of ``BENCHMARK.json`` to the files that define it."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from typing import Any, Dict, List

#: The benchmark's own directory (``benchmarks/chip``).
BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
#: The checkout's root, which holds ``BENCHMARK.json``.
ROOT = BENCH_DIR.parents[1]


class SpecError(ValueError):
    """A cell, configuration, traffic mix or metric that cannot be found."""


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_module(path: pathlib.Path):
    """Import a Python file by path (names may hold ``.`` and ``-``)."""
    if not path.is_file():
        raise SpecError(f"no such file: {path}")
    mod_name = "chipbench_file_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(BENCH_DIR)))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[mod_name]
        raise
    return mod


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    traffic mix and the metrics it reports."""
    bench_path = ROOT / "BENCHMARK.json"
    if not bench_path.is_file():
        raise SpecError(f"no {bench_path.name} at {bench_path.parent}")
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    cfg_path = bench_path.parent / configs[w["config"]]["file"]
    if not cfg_path.is_file():
        raise SpecError(f"config file missing: {cfg_path}")
    traffic_path = BENCH_DIR / "traffic" / f"{w['traffic']}.json"
    if not traffic_path.is_file():
        raise SpecError(f"traffic file missing: {traffic_path}")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in e2e_names]
    for m in per_layer:
        reader = metric_path(m["name"])
        if not reader.is_file():
            raise SpecError(f"metric {m['name']!r} has no reader {reader}")
    return Cell(name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=json.loads(cfg_path.read_text()),
                traffic=json.loads(traffic_path.read_text()),
                end_to_end=e2e, per_layer=per_layer)


def metric_path(name: str) -> pathlib.Path:
    return BENCH_DIR / "metrics" / f"{name}.py"


def reference_module(config: Dict[str, Any]):
    """The plain reference that sits beside a configuration's file."""
    return load_module(BENCH_DIR / "configs" / config["reference"])


def driver_module(config: Dict[str, Any]):
    """The driver that runs this kind of system (``drivers/<name>.py``)."""
    return load_module(BENCH_DIR / "drivers" / f"{config['driver']}.py")


def metric_reader(name: str):
    return load_module(metric_path(name))
