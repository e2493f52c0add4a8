"""One run of one cell: set-up, the measured window, the check, the line.

Order of a run: find the cell's files; refuse a device that is not a TPU
or has too few chips; fix JAX's compilation cache inside the checkout and
its default matmul precision at float32; refuse a graph with edges whose
plain reference does not read them; draw the surrogate heads from
``--seed`` and write one artifact per circuit kind; make the
configuration's weights on the device and the stimulus on the host from
``--seed``; warm up the cell's own shapes; freeze the set-up heap out of
the garbage collector. ``setup_s`` ends there. Then the window, traced
with ``--trace 1``; the peak device memory; the device state freed; the
plain reference over the sampled answers; the metrics; and the result
line, with each compared number beside its limit.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time

from lasbench import cells, check, model, tracing, traffic


@dataclasses.dataclass
class Net:
    spec: object            # repro NetworkSpec
    library: dict           # {kind: Surrogate}
    layers: list            # the graph as the reference reads it
    artifacts: dict         # {kind: reference.load_artifact(...)}


def _err(msg: str) -> int:
    print(f"lasbench: {msg}", file=sys.stderr, flush=True)
    return 2


def peaks_for(harness_dir: str, kind: str) -> dict:
    with open(os.path.join(harness_dir, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def use_cache(cache: str):
    """JAX's persistent compilation cache at a fixed path in the
    checkout; every program is kept, however fast it compiled. Every dot
    that names no precision computes in float32, as the configurations
    state (on a TPU the default rounds float32 operands to bfloat16)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", os.path.join(cache, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_matmul_precision", "float32")


def build_net(cell, seed: int, cache: str, ref_mod) -> Net:
    """Surrogate heads drawn from ``seed``, one artifact per circuit
    kind that the program and the reference both read; the
    configuration's weights and graph."""
    import jax
    import repro.lasana as lasana
    paths = {kind: model.write_surrogate(sur, seed, os.path.join(
        cache, "surrogates", f"{cell.config['name']}.{kind}.npz"))
        for kind, sur in model.surrogates(cell.config).items()}
    weights = model.make_weights(cell.config)
    return Net(spec=model.build_spec(cell.config, weights),
               library={k: lasana.load(p) for k, p in paths.items()},
               layers=model.reference_layers(cell.config,
                                             jax.device_get(weights)),
               artifacts={k: ref_mod.load_artifact(p)
                          for k, p in paths.items()})


def execute(root: str, workload: str, seed: int, seconds: float,
            trace: bool, *, t_start: float, require_tpu: bool = True) -> int:
    cell = cells.resolve(root, workload)
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        return _err(f"needs a TPU, JAX's first device is {dev.platform}")
    if require_tpu and len(devices) < cell.chips:
        return _err(f"{workload} needs {cell.chips} chips, JAX sees "
                    f"{len(devices)}")
    peaks = (peaks_for(cell.harness_dir, dev.device_kind) if require_tpu
             else None)
    used = devices[:cell.chips]

    cache = os.path.join(cell.harness_dir, ".cache")
    use_cache(cache)
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)

    try:
        ref_mod = cells.reference_module(cell.harness_dir, cell.config)
    except ValueError as e:
        return _err(str(e))
    t_net = time.perf_counter()
    net = build_net(cell, seed, cache, ref_mod)
    t_prep = time.perf_counter()
    drv = traffic.driver(cell, net, seed)
    drv.prepare()
    # what set-up built (JAX, the stimulus pool, the programs) leaves the
    # collector's view, so that no full collection walks it in the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    print(f"setup: jax_s={t_net - t_start} net_s={t_prep - t_net} "
          f"prepare_s={t_start + setup_s - t_prep}", file=sys.stderr,
          flush=True)

    events: list = []
    if trace:
        with tracing.capture(os.path.join(cache, "trace"), events):
            counters = drv.window(seconds)
    else:
        counters = drv.window(seconds)
    memory_peak = _memory_peak(used)
    drv.close()
    print(f"window: {json.dumps(counters)}", file=sys.stderr, flush=True)

    pairs = drv.check_pairs()
    numbers = check.compare(pairs, check.run_reference(
        ref_mod, net.artifacts, net.layers, pairs))
    checks = check.judge(numbers, cell.limits)
    correct = check.passed(checks) and counters["attempted"] > 0

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": counters["attempted"],
              "failed": counters["failed"]}
    if trace:
        summary = tracing.reduce(events, cell.chips)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        ctx = {"trace": summary, "counters": counters, "cell": cell,
               "net": net, "peaks": peaks, "chips": cell.chips}
        metrics = {}
        for m in cell.per_layer:
            value = cells.metric_reader(cell.harness_dir, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = summary["breakdown"]
    else:
        values = dict(counters, setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        verdict = "pass" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
