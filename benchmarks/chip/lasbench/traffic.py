"""The traffic generators: a mix file names its ``driver``.

A driver is the built-in ``closed_loop`` or, for any other name, the class
``Driver`` of ``drivers/<name>.py`` in the harness directory. It is made
as ``Driver(cell, net, seed)`` and offers ``prepare()`` (set-up: the
stimulus and the warm-up of every shape), ``window(seconds)`` (the
counters: at least ``attempted``, ``failed`` and each end-to-end metric
of the cell but ``setup_s``), ``close()`` and ``check_pairs()`` (the
(program record, stimulus) pairs the comparison reads).

closed_loop  back-to-back ``lasana.simulate`` calls over a stimulus pool
             of ``pool`` batches of ``batch`` lanes x ``ticks`` ticks; the
             next call starts when the previous record is on the host.
             It keeps the records of ``check_calls`` calls of the window,
             drawn from the seed, for the comparison with the plain
             reference.
"""

from __future__ import annotations

import os
import time

import numpy as np

from lasbench import cells, model
from lasbench.data import sub_seed


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation("lasbench." + name)


class ClosedLoop:
    def __init__(self, cell, net, seed: int):
        self.cell, self.net, self.seed = cell, net, seed
        self.mix = cell.traffic

    def prepare(self):
        mix, cfg = self.mix, self.cell.config
        self.pool = [model.stimulus(cfg, mix["ticks"], mix["batch"],
                                    self.seed, "pool", i)
                     for i in range(mix["pool"])]
        self._simulate(self.pool[0])

    def _simulate(self, x):
        import repro.lasana as lasana
        return lasana.simulate(self.net.spec, x, surrogates=self.net.library)

    def window(self, seconds: float) -> dict:
        import repro.lasana as lasana
        eng = lasana.engine(self.net.spec)
        compiles = eng.compile_count
        rng = np.random.default_rng(sub_seed(self.seed, "sample"))
        k = self.mix["check_calls"]
        samples, calls, events = [], 0, 0
        with _span("window"):
            t0 = time.perf_counter()
            while True:
                x = self.pool[calls % len(self.pool)]
                with _span("call"):
                    run = self._simulate(x)
                with _span("record"):
                    events += int(run.events.sum())
                t1 = time.perf_counter()
                if len(samples) < k:
                    samples.append((calls, run))
                else:                      # reservoir: uniform over calls
                    j = int(rng.integers(0, calls + 1))
                    if j < k:
                        samples[j] = (calls, run)
                calls += 1
                if t1 - t0 >= seconds:
                    break
        self.samples = samples
        return {"attempted": calls, "failed": 0,
                "window_s": t1 - t0,
                "calls": calls, "events": events,
                "ticks": calls * self.mix["ticks"],
                "compiles_in_window": eng.compile_count - compiles,
                "sim_events_per_s": events / (t1 - t0)}

    def close(self):
        """Nothing to free: every record and the pool live on the host."""

    def check_pairs(self):
        """(program record, stimulus) of each sampled call."""
        return [(run, self.pool[i % len(self.pool)]) for i, run in self.samples]


BUILT_IN = {"closed_loop": ClosedLoop}


def driver_class(harness_dir: str, name: str):
    """The driver ``name``: built in, or ``drivers/<name>.py``'s
    ``Driver``."""
    if name in BUILT_IN:
        return BUILT_IN[name]
    if not cells.NAME.fullmatch(name):
        raise ValueError(f"driver name {name!r} is not a name")
    return cells.load_module(os.path.join(harness_dir, "drivers",
                                          name + ".py"),
                             "lasbench_driver_").Driver


def driver(cell, net, seed: int):
    return driver_class(cell.harness_dir, cell.traffic["driver"])(
        cell, net, seed)
