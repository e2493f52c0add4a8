#!/usr/bin/env python3
"""Measurements that set the benchmark's numbers; the runs do not use it.

    python3 benchmarks/chip/study.py control --workload snn-mnist.batch \\
        --seeds 1,2,3 --seconds 2 [--controls high,bf16] [--tpu-default]
    python3 benchmarks/chip/study.py trace --workload snn-mnist.batch \\
        --seed 1 --seconds 1 --out trace.json

control  per seed, one short window of the cell at its own size; the
         program's sampled calls against the plain reference (the lower
         readings of each limit), and the reference at each lower
         precision put in the program's place against the same reference
         (the control: the upper readings). One JSON line per seed.
         ``--tpu-default`` leaves JAX's default matmul precision as the
         platform sets it, to read what the program does without the
         configurations' float32.
trace    one traced window; writes the compact trace events as JSON.

Runs on the chip like ``run.py``: one process holds it.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))

from lasbench import cells, check, harness, tracing, traffic  # noqa: E402


def setup(workload, tpu_default=False):
    cell = cells.resolve(ROOT, workload)
    cache = os.path.join(cell.harness_dir, ".cache")
    harness.use_cache(cache)
    if tpu_default:
        import jax
        jax.config.update("jax_default_matmul_precision", None)
    return cell, cache, cells.reference_module(cell.harness_dir, cell.config)


def control(args):
    cell, cache, ref_mod = setup(args.workload, args.tpu_default)
    worst = {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        net = harness.build_net(cell, seed, cache, ref_mod)
        drv = traffic.driver(cell, net, seed)
        drv.prepare()
        drv.window(args.seconds)
        drv.close()
        pairs = drv.check_pairs()
        refs = check.run_reference(ref_mod, net.artifacts, net.layers, pairs)
        line = {"seed": seed, "program": check.compare(pairs, refs)}
        for prec in filter(None, args.controls.split(",")):
            crefs = check.run_reference(ref_mod, net.artifacts, net.layers,
                                        pairs, precision=prec)
            cpairs = [(check.as_record(cr, run), x)
                      for (run, x), cr in zip(pairs, crefs)]
            line["control_" + prec] = check.compare(cpairs, refs)
        if "lif" in net.artifacts:
            line["spike_share"] = [
                float((r > 0.75).mean())
                for r, layer in zip(refs[0]["published"], net.layers)
                if layer["kind"] == "lif"]
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        for side, nums in line.items():
            if isinstance(nums, dict):
                for k, v in nums.items():
                    worst.setdefault(side, {}).setdefault(k, []).append(v)
    print(json.dumps({"summary": {
        side: {k: {"max": max(v), "min": min(v)} for k, v in d.items()}
        for side, d in worst.items()}}), flush=True)


def trace(args):
    cell, cache, ref_mod = setup(args.workload)
    net = harness.build_net(cell, args.seed, cache, ref_mod)
    drv = traffic.driver(cell, net, args.seed)
    drv.prepare()
    events = []
    with tracing.capture(os.path.join(cache, "trace"), events):
        drv.window(args.seconds)
    drv.close()
    tracing.save_events(args.out, events)
    planes = sorted({(e[0], e[1]) for e in events})
    print(json.dumps({"events": len(events), "planes": planes,
                      "summary": tracing.reduce(events, cell.chips)}))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    c = sub.add_parser("control")
    c.add_argument("--workload", required=True)
    c.add_argument("--seeds", required=True)
    c.add_argument("--seconds", type=float, default=2.0)
    c.add_argument("--controls", default="high")
    c.add_argument("--tpu-default", action="store_true")
    t = sub.add_parser("trace")
    t.add_argument("--workload", required=True)
    t.add_argument("--seed", type=int, default=1)
    t.add_argument("--seconds", type=float, default=1.0)
    t.add_argument("--out", required=True)
    args = ap.parse_args()
    {"control": control, "trace": trace}[args.cmd](args)


if __name__ == "__main__":
    main()
