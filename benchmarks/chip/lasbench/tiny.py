"""A copy of the benchmark at a size the CPU test suite can hold.

``make_root(dest, src_root)`` copies ``BENCHMARK.json`` and the harness
directory into ``dest`` and shrinks every cell in place there: smaller
images (so smaller input widths), narrower hidden layers, a few lanes and
ticks; with ``widths=True`` only the lanes shrink. The surrogate heads,
the traffic and the comparison are the cells' own.

``add_graph_cell(root)`` adds to such a copy, as new files and new
``BENCHMARK.json`` entries only, a small mixed graph: a crossbar layer
feeding a LIF layer with lateral inhibition, a surrogate per kind, a
reference of its own and a driver file.
"""

from __future__ import annotations

import json
import os
import shutil

from lasbench.cells import HARNESS_DIR


def _rewrite(path: str, fn):
    with open(path) as f:
        obj = json.load(f)
    fn(obj)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(dest: str, src_root: str, *, widths: bool = False) -> str:
    """A shrunk benchmark checkout at ``dest`` (returned). With
    ``widths`` the configurations keep their widths and only the traffic
    shrinks."""
    os.makedirs(dest, exist_ok=True)
    shutil.copy(os.path.join(src_root, "BENCHMARK.json"), dest)
    harness = os.path.join(dest, "benchmarks", "chip")
    shutil.copytree(HARNESS_DIR, harness,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))

    def config(c):
        enc = c["stimulus"]
        enc["image_size"] = 8
        net = c["network"]
        if net["spec"] == "graph_spec":
            net["fan_in"] = 64
            for layer in net["layers"]:
                layer["n_out"] = min(layer["n_out"], 24)
        else:
            net["layers"] = [64] + [min(w, 24) for w in net["layers"][1:]]

    if not widths:
        for name in os.listdir(os.path.join(harness, "configs")):
            _rewrite(os.path.join(harness, "configs", name), config)

    def mix(m):
        m.update(batch=16 if widths else 4, ticks=100 if widths else 20,
                 pool=1)

    for name in os.listdir(os.path.join(harness, "traffic")):
        _rewrite(os.path.join(harness, "traffic", name), mix)
    return dest


GRAPH = "mixed-tiny"
GRAPH_CELL = GRAPH + ".batch"
GRAPH_DRIVER = "closed_loop_file"
GRAPH_REFERENCE = "reference/lasana_graph.py"


def add_graph_cell(root: str) -> str:
    """Add the cell ``mixed-tiny.batch`` to the benchmark at ``root`` by
    new files only (returns the cell's name): a 64-24-10 crossbar -> LIF
    graph whose LIF layer inhibits itself (``lateral_inhibition``, one
    tick late), with the crossbar and LIF surrogate blocks of the two
    MNIST configurations, a copy of the plain reference of its own,
    the traffic driver ``drivers/closed_loop_file.py``, a limits file
    holding the looser of the two MNIST cells' limits, and the cell
    appended to every metric's ``workloads``."""
    harness = os.path.join(root, "benchmarks", "chip")

    def read(*parts):
        with open(os.path.join(harness, *parts)) as f:
            return json.load(f)

    def put(text, *parts):              # a new file, never one that is there
        path = os.path.join(harness, *parts)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "x") as f:
            f.write(text)

    def write(obj, *parts):
        put(json.dumps(obj, indent=1), *parts)

    snn, xbar = read("configs", "snn-mnist.json"), \
        read("configs", "xbar-mnist.json")
    write({"name": GRAPH, "source": "https://arxiv.org/abs/2410.08403",
           "network": {
               "spec": "graph_spec", "fan_in": 64, "spike_amp": 1.5,
               "layers": [
                   {"kind": "crossbar", "n_out": 24, "seg_width": 32,
                    "adc_bits": 8, "activation": "tanh",
                    "weights": {"recipe": "ternary",
                                "threshold_sigma": 0.5}},
                   {"kind": "lif", "n_out": 10,
                    "lif_knobs": [0.58, 0.5, 0.5, 0.5]}],
               "edges": [{"src": 1, "dst": 1, "weights": {
                   "recipe": "lateral_inhibition", "strength": 0.4}}]},
           "weights": {"recipe": "he_normal", "gain": 1.0, "seed": 3},
           "stimulus": dict(xbar["stimulus"], image_size=8),
           "surrogates": {"crossbar": xbar["surrogate"],
                          "lif": snn["surrogate"]},
           "reference": GRAPH_REFERENCE}, "configs", GRAPH + ".json")
    write({"driver": GRAPH_DRIVER, "batch": 4, "ticks": 20, "pool": 1,
           "check_calls": 2}, "traffic", "closed-4x20-file.json")
    a, b = read("limits", "snn-mnist.batch.json"), \
        read("limits", "xbar-mnist.batch.json")
    write({n: {"limit": max(a[n]["limit"], b[n]["limit"])}
           for n in a if n in b and n != "readings"},
          "limits", GRAPH_CELL + ".json")
    with open(os.path.join(harness, "reference", "lasana_net.py")) as f:
        put(f.read(), GRAPH_REFERENCE)
    put('"""The closed loop, found as a file of its own."""\n\n'
        "from lasbench.traffic import ClosedLoop\n\n\n"
        "class Driver(ClosedLoop):\n"
        '    """Back-to-back calls, as the built-in driver makes them."""\n',
        "drivers", GRAPH_DRIVER + ".py")

    bench_path = os.path.join(root, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": GRAPH, "source": "https://arxiv.org/abs/2410.08403",
        "file": f"benchmarks/chip/configs/{GRAPH}.json", "reduced": [],
        "why": "crossbar front end, LIF layer with lateral inhibition"})
    bench["workloads"].append({
        "name": GRAPH_CELL, "config": GRAPH, "traffic": "closed-4x20-file",
        "chips": 1, "why": "cross-kind adapter, delayed edge, two surrogates"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(GRAPH_CELL)
    with open(bench_path, "w") as f:
        json.dump(bench, f, indent=1)
    return GRAPH_CELL
