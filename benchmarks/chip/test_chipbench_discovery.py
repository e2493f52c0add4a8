"""Every cell resolves its files by name, and a new cell needs only new
files and new ``BENCHMARK.json`` entries."""

import hashlib
import json
import os
import re
import shutil

import pytest

from lasbench import cells, check, tiny, traffic

HARNESS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HARNESS))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    return cells.load_benchmark(ROOT)


def test_benchmark_json_shape():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks/chip"]
    assert b["command"][1] == "benchmarks/chip/run.py"
    assert 1 <= b["run_seconds"] <= 51
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for entry in b["configs"] + b["workloads"] + b["end_to_end"] \
            + b["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
        assert len(entry.get("why", "x")) <= 200
        if "unit" in entry:
            assert UNIT.match(entry["unit"]), entry["unit"]
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            reported = e2e[m["moves"]].get("workloads")
            assert reported is None or w in reported


@pytest.mark.parametrize("workload",
                         [w["name"] for w in _bench()["workloads"]])
def test_every_workload_resolves(workload):
    cell = cells.resolve(ROOT, workload)
    assert callable(traffic.driver_class(cell.harness_dir,
                                         cell.traffic["driver"]))
    compared = set(cell.limits) - {"readings"}
    assert compared <= set(check.NAMES)
    assert compared >= {"mismatch_pct", "events_gap_pct", "latency_gap_pct"}
    assert compared & {"energy_gap_pct", "energy_tick_gap_pct"}
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cells.metric_reader(cell.harness_dir, m["name"]))
    assert hasattr(cells.reference_module(cell.harness_dir, cell.config),
                   "simulate")


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_cell_from_new_files_only(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    harness = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HARNESS, harness, ignore=shutil.ignore_patterns(
        ".cache", "__pycache__"))
    before = _digest(harness)

    # a new configuration, traffic mix, limits file and per-layer metric
    cfg = json.loads((harness / "configs" / "snn-mnist.json").read_text())
    cfg["name"] = "snn-wide"
    cfg["network"]["layers"] = [784, 512, 10]
    (harness / "configs" / "snn-wide.json").write_text(json.dumps(cfg))
    (harness / "traffic" / "closed-256x50.json").write_text(json.dumps(
        {"driver": "closed_loop", "batch": 256, "ticks": 50, "pool": 1,
         "check_calls": 1}))
    (harness / "limits" / "snn-wide.batch.json").write_text(
        (harness / "limits" / "snn-mnist.batch.json").read_text())
    (harness / "metrics" / "calls_per_s.py").write_text(
        "def read(ctx):\n    c = ctx['counters']\n"
        "    return c['calls'] / c['window_s']\n")
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "snn-wide", "source": "a paper",
                         "file": "benchmarks/chip/configs/snn-wide.json",
                         "reduced": [], "why": "a wider hidden layer"})
    b["workloads"].append({"name": "snn-wide.batch", "config": "snn-wide",
                           "traffic": "closed-256x50", "chips": 1,
                           "why": "wide"})
    b["end_to_end"][0]["workloads"].append("snn-wide.batch")
    b["per_layer"].append({"name": "calls_per_s", "unit": "1/s",
                           "better": "higher", "source": "host_clock",
                           "layer": "engine host path",
                           "moves": "sim_events_per_s",
                           "workloads": ["snn-wide.batch"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    cell = cells.resolve(str(tmp_path), "snn-wide.batch")
    assert cell.config["network"]["layers"] == [784, 512, 10]
    assert cell.traffic["batch"] == 256
    assert [m["name"] for m in cell.per_layer] == ["calls_per_s"]
    read = cells.metric_reader(cell.harness_dir, "calls_per_s")
    assert read({"counters": {"calls": 6, "window_s": 2.0}}) == 3.0
    after = _digest(harness)
    assert {k: after[k] for k in before} == before


def test_graph_cell_from_new_files_only(tmp_path):
    """A mixed graph with a recurrent edge, a surrogate per kind, its own
    reference and a driver file enters by new files and new entries."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    harness = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HARNESS, harness, ignore=shutil.ignore_patterns(
        ".cache", "__pycache__"))
    before = _digest(harness)
    workload = tiny.add_graph_cell(str(tmp_path))

    cell = cells.resolve(str(tmp_path), workload)
    assert cell.config["network"]["edges"]
    assert set(cell.config["surrogates"]) == {"lif", "crossbar"}
    drv = traffic.driver_class(cell.harness_dir, cell.traffic["driver"])
    assert drv is not traffic.ClosedLoop
    assert issubclass(drv, traffic.ClosedLoop)
    ref = cells.reference_module(cell.harness_dir, cell.config)
    assert ref.READS_EDGES
    assert [m["name"] for m in cell.per_layer] == \
        [m["name"] for m in _bench()["per_layer"]]
    after = _digest(harness)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        os.path.join("configs", tiny.GRAPH + ".json"),
        os.path.join("traffic", "closed-4x20-file.json"),
        os.path.join("limits", workload + ".json"),
        tiny.GRAPH_REFERENCE,
        os.path.join("drivers", tiny.GRAPH_DRIVER + ".py")}


def test_unknown_driver_is_refused(tmp_path):
    with pytest.raises(FileNotFoundError):
        traffic.driver_class(str(tmp_path), "no_such_driver")
    with pytest.raises(ValueError):
        traffic.driver_class(str(tmp_path), "../x")


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        cells.resolve(ROOT, "no-such.cell")
