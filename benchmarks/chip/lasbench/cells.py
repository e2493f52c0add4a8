"""Find a cell's files by the names in ``BENCHMARK.json``."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
HARNESS_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    traffic: dict           # traffic/<traffic>.json
    limits: dict            # limits/<workload>.json
    end_to_end: list        # BENCHMARK.json entries reported by this cell
    per_layer: list
    harness_dir: str


def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def resolve(root: str, workload: str) -> Cell:
    """The cell ``workload`` of the benchmark at ``root``; raises
    ``KeyError`` for an unknown name and ``FileNotFoundError`` for a file
    that is not there. Traffic, limits and metric readers are looked up
    in the harness directory, the first of ``paths``."""
    bench = load_benchmark(root)
    harness_dir = os.path.join(root, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=_read_json(os.path.join(harness_dir, "traffic",
                                        w["traffic"] + ".json")),
        limits=_read_json(os.path.join(harness_dir, "limits",
                                       workload + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        harness_dir=harness_dir)


def load_module(path: str, prefix: str):
    """The Python file at ``path`` as a module named ``prefix`` + its
    file name."""
    base = os.path.basename(path)[:-3].replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(prefix + base, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(harness_dir: str, name: str):
    """``read(ctx) -> float | None`` of ``metrics/<name>.py``."""
    return load_module(os.path.join(harness_dir, "metrics", name + ".py"),
                       "lasbench_metric_").read


def reference_module(harness_dir: str, config: dict):
    """The configuration's plain reference (a module under ``paths``).
    Raises ``ValueError`` where the configuration's graph has edges and
    the module does not declare ``READS_EDGES = True``: a comparison never
    leaves an edge out unseen."""
    mod = load_module(os.path.join(harness_dir, config["reference"]),
                      "lasbench_reference_")
    if config["network"].get("edges") and not getattr(mod, "READS_EDGES",
                                                      False):
        raise ValueError(f"{config['reference']} does not read the edges "
                         f"of {config['name']} (no READS_EDGES)")
    return mod
