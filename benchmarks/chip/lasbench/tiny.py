"""A copy of the benchmark at a size the CPU test suite can hold.

``make_root(dest, src_root)`` copies ``BENCHMARK.json`` and the harness
directory into ``dest`` and shrinks every cell in place there: smaller
images (so smaller input widths), narrower hidden layers, a few lanes and
ticks; with ``widths=True`` only the lanes shrink. The surrogate heads,
the traffic and the comparison are the cells' own.
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
        layers = c["network"]["layers"]
        c["network"]["layers"] = [64] + [min(w, 24) for w in layers[1:]]

    if not widths:
        for name in os.listdir(os.path.join(harness, "configs")):
            _rewrite(os.path.join(harness, "configs", name), config)

    def mix(m):
        m.update(batch=16 if widths else 4, ticks=100 if widths else 20,
                 pool=1)

    for name in os.listdir(os.path.join(harness, "traffic")):
        _rewrite(os.path.join(harness, "traffic", name), mix)
    return dest

