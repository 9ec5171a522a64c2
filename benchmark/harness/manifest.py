"""BENCHMARK.json is the registry: a cell, a configuration, a traffic
mix, a driver, a data generator, a reference and a per-layer metric are
all found from the names it holds, as files under ``benchmark/``.
Nothing here (or anywhere in the harness) names a particular cell."""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List


class ManifestError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path  # the checkout (holds BENCHMARK.json)

    @property
    def bench_dir(self) -> Path:
        return self.root / "benchmark"


def repo_root() -> Path:
    return Path(__file__).resolve().parents[2]


def load_manifest(root: Path) -> Dict[str, Any]:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise ManifestError(f"{path} not found")
    return json.loads(path.read_text())


def _read_json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise ManifestError(f"{path} not found")
    out = json.loads(path.read_text())
    if not isinstance(out, dict):
        raise ManifestError(f"{path} must hold one JSON object")
    return out


def _applies(metric: Dict[str, Any], cell_name: str) -> bool:
    only = metric.get("workloads")
    return only is None or cell_name in only


def resolve_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``."""
    m = load_manifest(root)
    by_name = {w["name"]: w for w in m["workloads"]}
    if name not in by_name:
        raise ManifestError(
            f"no workload {name!r} in BENCHMARK.json "
            f"(have: {sorted(by_name)})"
        )
    w = by_name[name]
    configs = {c["name"]: c for c in m["configs"]}
    if w["config"] not in configs:
        raise ManifestError(f"workload {name!r} names unknown config "
                            f"{w['config']!r}")
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(root / "benchmark" / "traffic"
                         / f"{w['traffic']}.json")
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"], config=config,
        traffic_name=w["traffic"], traffic=traffic,
        end_to_end=[e for e in m["end_to_end"] if _applies(e, name)],
        per_layer=[p for p in m["per_layer"] if _applies(p, name)],
        root=root,
    )


def load_plugin(root: Path, kind: str, name: str) -> ModuleType:
    """Import ``benchmark/<kind>/<name>.py`` by path. ``kind`` is one of
    the plug-in directories (drivers, datasets, references,
    layer_metrics, rooflines); names may hold dots and dashes, so this
    never goes through the import system's module names."""
    path = root / "benchmark" / kind / f"{name}.py"
    if not path.is_file():
        raise ManifestError(f"{kind[:-1]} {name!r}: {path} not found")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace(".", "_").replace("-", "_"),
        path,
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up here
    spec.loader.exec_module(mod)
    return mod
