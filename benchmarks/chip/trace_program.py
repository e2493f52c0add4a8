#!/usr/bin/env python3
"""One traced window of a cell, read through the engine's own spans and
stages (``lasbench.program_trace``).

    python3 benchmarks/chip/trace_program.py --workload snn-mnist.batch \\
        --seed 7 --seconds 10 [--out events.json.gz]

Runs like ``run.py --trace 1`` on the chip (one process holds it), with
``program_trace.capture`` and ``program_trace.reduce`` in place of
``tracing``'s (the stages read from the engine's compiled HLO). Prints
one JSON line: ``correct``, the window's counters, every per-layer metric
of the cell and of ``program_trace.READERS``, ``idle_in_engine_pct`` (the
share of the idle time inside ``lasbench.call`` spans that lies inside
``lasana.run``), ``spans``, ``stages`` and the ``breakdown``. ``--out``
writes the events.
"""

import argparse
import gc
import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))

from lasbench import cells, check, harness, program_trace, traffic  # noqa


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args()
    cell = cells.resolve(ROOT, args.workload)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, JAX's first device is {dev.platform}")
    cache = os.path.join(cell.harness_dir, ".cache")
    harness.use_cache(cache)
    ref_mod = cells.reference_module(cell.harness_dir, cell.config)
    net = harness.build_net(cell, args.seed, cache, ref_mod)
    drv = traffic.driver(cell, net, args.seed)
    drv.prepare()
    gc.collect()
    gc.freeze()

    import repro.lasana as lasana
    hlo = lasana.engine(net.spec).compiled_hlo()
    events: list = []
    with program_trace.capture(os.path.join(cache, "trace"), events, hlo):
        counters = drv.window(args.seconds)
    drv.close()
    pairs = drv.check_pairs()
    checks = check.judge(check.compare(pairs, check.run_reference(
        ref_mod, net.artifacts, net.layers, pairs)), cell.limits)

    summary = program_trace.reduce(events, cell.chips)
    ctx = {"trace": summary, "counters": counters, "cell": cell, "net": net,
           "peaks": harness.peaks_for(cell.harness_dir, dev.device_kind),
           "chips": cell.chips}
    metrics = {m["name"]: cells.metric_reader(cell.harness_dir,
                                              m["name"])(ctx)
               for m in cell.per_layer}
    metrics.update({n: read(ctx)
                    for n, read in program_trace.READERS.items()})
    run = summary["spans"].get("lasana.run")
    call_idle = sum(s - b for s, b in summary["calls"])
    if run and call_idle:
        metrics["idle_in_engine_pct"] = \
            100.0 * (run["total_s"] - run["busy_s"]) / call_idle
    if args.out:
        with gzip.open(args.out, "wt") as f:
            json.dump(events, f)
    print(json.dumps({
        "correct": check.passed(checks) and counters["attempted"] > 0,
        "device": dev.device_kind, "counters": counters, "metrics": metrics,
        "spans": summary["spans"], "stages": summary["stages"],
        "breakdown": summary["breakdown"]}), flush=True)


if __name__ == "__main__":
    main()
