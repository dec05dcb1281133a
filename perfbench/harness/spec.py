"""A cell of ``BENCHMARK.json``, resolved by name to its files.

Nothing here knows a particular cell: a configuration is the file the
``configs`` entry names, a traffic mix is ``traffic/<traffic>.json``, and
each metric is read by ``metrics/<name>.py`` (per layer) or
``end_to_end/<name>.py``, each a module with ``read(ctx)``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_benchmark(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def applies(metric: Dict, cell: str, reported: List[str]) -> bool:
    """A metric with ``workloads`` belongs to those cells; without, an
    end-to-end metric belongs to every cell and a per-layer one to every
    cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def resolve(bench: Dict, workload: str, root: Path = ROOT,
            bench_dir: Path = BENCH_DIR) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if applies(m, workload, [])]
    reported = [m["name"] for m in e2e]
    layer = [m for m in bench["per_layer"]
             if applies(m, workload, reported)]
    return Cell(name=workload, chips=w["chips"], config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def load_module(path: Path, name: str):
    """The Python file ``path``, loaded as a module named ``name``; a
    missing file raises an error that names it. Every file the harness
    finds by a name in ``BENCHMARK.json`` or a configuration is loaded
    here: metric readers, architecture modules and references."""
    if not path.is_file():
        raise FileNotFoundError(f"no module {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(kind: str, name: str, bench_dir: Path = BENCH_DIR):
    """The ``read`` function of ``<kind>/<name>.py``."""
    return load_module(bench_dir / kind / f"{name}.py",
                       f"perfbench_{kind}_{name.replace('.', '_')}").read
