"""Where each named part of the benchmark lives, and how it is loaded.

``BENCHMARK.json`` at the root names the cells, configurations and
metrics. Everything else is found by name under the benchmark's
directory, so a later change adds a cell, a traffic mix, a configuration
or a metric with new files alone:

    configs/<config>.json      sizes, as run (the path BENCHMARK.json gives)
    references/<ref>.py        the plain reference a configuration names
    traffic/<traffic>.json     the parameters of one traffic mix
    limits/<cell>.json         the limits that decide ``correct`` in a cell
    metrics/<metric>.py        one reader per metric: ``read(run)``
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
CACHE_DIR = ".jax_cache"  # under the checkout; a fixed path, so it hits


def configure_cache(root: Path) -> str:
    """Keep JAX's persistent compilation cache at ``<root>/.jax_cache``,
    whatever the environment said, and cache every program, however
    quickly it compiled. Call before JAX is imported; the program's
    ``configure_compile_cache`` then finds and keeps this directory."""
    path = str(Path(root) / CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    return path


def load_module(path: Path) -> ModuleType:
    """Import a file by path; metric and reference files are named after
    what they measure (``queue_ms.ttft.py``), not as Python modules."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve_config(raw: Dict[str, Any]) -> Dict[str, Any]:
    """The program's configuration fields from a configuration file: each
    value of its ``program`` block is a literal or ``"@key"``, a key of
    the file's published (top-level) settings, so every number is written
    once."""
    out = {}
    for k, v in raw["program"].items():
        if isinstance(v, str) and v.startswith("@"):
            v = raw[v[1:]]
        out[k] = v
    return out


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    read: Callable[[Any], Optional[float]]


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]       # resolved program configuration
    config_file: Dict[str, Any]  # the configuration file as written
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    reference: ModuleType
    end_to_end: List[Metric]
    per_layer: List[Metric]
    others: List[Metric]         # every other metric BENCHMARK.json names


def _metric(m: Dict[str, Any], bench_dir: Path) -> Metric:
    mod = load_module(bench_dir / "metrics" / f"{m['name']}.py")
    return Metric(m["name"], m["unit"], m["better"], mod.read)


def _ours(m: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in m or cell in m["workloads"]


def load_cell(name: str, root: Path = ROOT,
              bench_dir: Optional[Path] = None) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with every file it
    names. ``bench_dir`` holds the traffic, limits, metrics and
    references (default: ``<root>/benchmarks/chip``)."""
    root = Path(root)
    bench_dir = Path(bench_dir) if bench_dir else root / "benchmarks" / "chip"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}"
                       f" (have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    raw = json.loads((root / conf["file"]).read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], config=resolve_config(raw),
        config_file=raw,
        traffic=json.loads(
            (bench_dir / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads(
            (bench_dir / "limits" / f"{name}.json").read_text()),
        reference=load_module(
            bench_dir / "references" / f"{raw['reference']}.py"),
        end_to_end=[_metric(m, bench_dir) for m in spec["end_to_end"]
                    if _ours(m, name)],
        per_layer=[_metric(m, bench_dir) for m in spec["per_layer"]
                   if _ours(m, name)],
        others=[_metric(m, bench_dir)
                for m in spec["end_to_end"] + spec["per_layer"]
                if not _ours(m, name)])


def load_peaks(device_kind: str, bench_dir: Path = BENCH_DIR
               ) -> Dict[str, Any]:
    """The chip's published peaks; a device not in the table is an error,
    never a default."""
    table = json.loads((bench_dir / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{bench_dir / 'peaks.json'} (have {sorted(table)})")
    return table[device_kind]
