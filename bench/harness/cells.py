"""Resolve a cell of ``BENCHMARK.json`` into the files it names.

A cell names a configuration and a traffic mix; the configuration entry
names its file, the traffic mix is ``bench/traffic/<traffic>.json``, whose
``loop`` names ``bench/loops/<loop>.py``, and each per-layer metric
family is ``bench/layers/<family>.py``.  Adding a cell or a metric adds
files and entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Dict, List

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    end_to_end: List[Dict]      # this cell's end-to-end metric entries
    per_layer: List[Dict]       # this cell's per-layer metric entries


def _applies(metric: Dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def resolve(workload: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if m["moves"] in reported and _applies(m, workload)]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config_name=w["config"],
        config=json.loads((root / cfg_entry["file"]).read_text()),
        traffic_name=w["traffic"],
        traffic=json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=e2e, per_layer=layer)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (loops, layers)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_')}", path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no {kind} module {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
