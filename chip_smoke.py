#!/usr/bin/env python3
"""Drive LASANA's main path once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # four chips: the batch-sharded path

Run it from the root of a checkout. One process holds the chip(s) and
starts no other. The phases, each printing its numbers and timings on
lines of their own:

  device    the first JAX device must be a TPU (else exit 1, no result)
  train     ``lasana.train("lif")`` with linear + MLP families (the MLP
            heads make the whole-tick megakernel's weight pack exist)
  simulate  the 784-256-128-10 spiking-MNIST net, batch 256, 100 ticks of
            0.25-rate Poisson spikes, through ``lasana.simulate`` on four
            paths: golden, behavioral, the default lasana path and the
            megakernel (``fused_kernel=True``). LASANA-vs-behavioral spike
            mismatch < 2%; default and megakernel records agree (discrete
            bitwise, energy to rtol 1e-5); the megakernel program holds a
            compiled Pallas kernel (``tpu_custom_call``)
  stream    ``lasana.simulate_stream`` at 25-tick chunks is bit-identical
            to the monolithic run; a one-LIF-layer graph takes the
            time-looped chunk kernel, compiled, and agrees with the
            default path
  serve     ``lasana.serve``: 8 requests from 2 tenants, each record equal
            to its solo ``simulate``; none failed, degraded or retried

``--four-chips`` runs only the sharded path: the same simulate (default
and megakernel) and stream with the batch sharded over a 4-device mesh,
each compared with the same run on one device.

Exit code 0 only if every check passed; the last line of standard output
is then ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

RTOL = 1e-5              # energy: reassociated f32 sums (docs/architecture.md)
ATOL_LATENCY = 1e-6      # latency maxes carry one-ULP noise on top
MAX_SPIKE_MISMATCH = 0.02


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Workload shape. ``FULL`` is what the script runs; smaller instances
    exist so the same phases can be rehearsed on a CPU."""

    layers: tuple = (784, 256, 128, 10)      # SNN_LAYERS_FULL
    batch: int = 256
    ticks: int = 100
    rate: float = 0.25
    chunk_ticks: int = 25
    n_runs: int = 600                        # training testbench runs
    n_steps: int = 80
    serve_shapes: tuple = ((32, 4), (48, 2))  # (ticks, batch) per request
    n_requests: int = 8


FULL = Sizes()


class Checks:
    """Collects pass/fail per named check; a phase never swallows an
    exception, it only records comparisons."""

    def __init__(self):
        self.failed = []

    def __call__(self, name, ok, detail=""):
        ok = bool(ok)
        print(f"  check {name}: {'pass' if ok else 'FAIL'}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)


def _line(phase, **kv):
    parts = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"{phase}: {parts}", flush=True)


def _max_rel(a, b):
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30),
                        initial=0.0))


def _discrete_equal(a, b):
    """Outputs, events, output spike train and (where recorded) every
    layer's spike train identical."""
    import numpy as np
    same = (np.array_equal(a.outputs, b.outputs)
            and np.array_equal(a.events, b.events)
            and (a.out_spikes is None
                 or np.array_equal(a.out_spikes, b.out_spikes)))
    if a.layer_spikes is not None:
        same = same and all(np.array_equal(x, y) for x, y in
                            zip(a.layer_spikes, b.layer_spikes))
    return bool(same)


def _analog_close(a, b):
    import numpy as np
    return bool(np.allclose(a.energy, b.energy, rtol=RTOL, atol=0)
                and np.allclose(a.flush_energy, b.flush_energy, rtol=RTOL,
                                atol=0)
                and np.allclose(a.latency, b.latency, rtol=RTOL,
                                atol=ATOL_LATENCY))


def _bit_identical(a, b):
    import numpy as np
    return bool(_discrete_equal(a, b)
                and np.array_equal(a.energy, b.energy)
                and np.array_equal(a.latency, b.latency)
                and np.array_equal(a.flush_energy, b.flush_energy))


def _has_kernel(eng):
    """Every tick program the engine compiled (monolithic and stream
    chunk; not the stream's flush helper) holds a compiled Pallas kernel."""
    texts = [compiled.as_text() for key, (compiled, _) in
             eng._sim_cache.items() if key[0] in ("mono", "stream")]
    return bool(texts) and all("tpu_custom_call" in t for t in texts)


def _energy_j(run):
    return run.report()["network"]["energy_j"]


def _net(sizes):
    from benchmarks.bench_network import make_net, poisson_spikes
    from repro.core.network import snn_spec
    ws, params = make_net(sizes.layers, seed=0)
    x = poisson_spikes(sizes.ticks, sizes.batch, sizes.layers[0],
                       rate=sizes.rate, seed=1)
    return ws, params, snn_spec(ws, params), x


def phase_train(sizes):
    import repro.lasana as lasana
    t0 = time.perf_counter()
    sur = lasana.train("lif", lasana.TrainConfig(
        n_runs=sizes.n_runs, n_steps=sizes.n_steps, seed=1,
        families=("linear", "mlp")))
    fams = {p: sur.manifest.family_of(p) for p in sorted(sur.params)}
    _line("train", seconds=f"{time.perf_counter() - t0:.3f}",
          n_runs=sizes.n_runs, families=fams)
    return sur


def phase_simulate(sizes, sur, check):
    import numpy as np
    import repro.lasana as lasana
    _, _, spec, x = _net(sizes)
    runs = {}
    for name, kw in (("golden", dict(backend="golden", record_hidden=False)),
                     ("behavioral", dict(backend="behavioral")),
                     ("lasana", dict(surrogates=sur)),
                     ("megakernel", dict(surrogates=sur, fused_kernel=True))):
        t0 = time.perf_counter()
        run = lasana.simulate(spec, x, **kw)
        runs[name] = run
        _line(f"simulate {name}", seconds=f"{time.perf_counter() - t0:.3f}",
              compile_seconds=f"{run.compile_seconds:.3f}",
              steady_seconds=f"{run.wall_seconds:.6f}",
              events=int(run.events.sum()), energy_j=_energy_j(run),
              out_spikes=int(np.sum(run.outputs)))
    behav, las, mega = runs["behavioral"], runs["lasana"], runs["megakernel"]
    mism = sum(int(np.sum((b > 0.75) != (l > 0.75)))
               for b, l in zip(behav.layer_spikes, las.layer_spikes))
    total = sum(b.size for b in behav.layer_spikes)
    check("spike mismatch vs behavioral < 2%",
          mism / total < MAX_SPIKE_MISMATCH, f"{mism / total:.6f}")
    e_g = _energy_j(runs["golden"])
    for name in ("lasana", "megakernel"):
        err = abs(_energy_j(runs[name]) - e_g) / max(e_g, 1e-30)
        _line(f"simulate {name}", energy_err_vs_golden=f"{err:.6f}")
    check("megakernel discrete records == default", _discrete_equal(mega, las))
    check("megakernel energy/latency within rtol 1e-5 of default",
          _analog_close(mega, las),
          f"energy max rel {_max_rel(mega.energy, las.energy):.3e}")
    check("megakernel program holds tpu_custom_call",
          _has_kernel(lasana.engine(spec, fused_kernel=True)))
    return spec, x, las


def phase_stream(sizes, sur, spec, x, mono, check):
    import repro.lasana as lasana
    t0 = time.perf_counter()
    st = lasana.simulate_stream(spec, x, chunk_ticks=sizes.chunk_ticks,
                                surrogates=sur, record_hidden=True)
    _line("stream lasana", seconds=f"{time.perf_counter() - t0:.3f}",
          chunk_ticks=sizes.chunk_ticks,
          compile_seconds=f"{st.compile_seconds:.3f}",
          steady_seconds=f"{st.wall_seconds:.6f}")
    check("stream bit-identical to monolithic", _bit_identical(st, mono))

    # one LIF layer, no edges: the time-looped chunk kernel replaces the scan
    from repro.core.network import snn_spec
    ws, params, _, _ = _net(sizes)
    spec1 = snn_spec(ws[:1], params[:1])
    eng1 = lasana.engine(spec1, fused_kernel=True)
    check("one-layer graph takes the chunk fast path", eng1._chunk_eligible())
    t0 = time.perf_counter()
    mono1 = lasana.simulate(spec1, x, surrogates=sur, fused_kernel=True)
    st1 = lasana.simulate_stream(spec1, x, chunk_ticks=sizes.chunk_ticks,
                                 surrogates=sur, fused_kernel=True,
                                 record_hidden=True)
    ref1 = lasana.simulate(spec1, x, surrogates=sur, fused_kernel=False)
    _line("stream chunk-kernel", seconds=f"{time.perf_counter() - t0:.3f}",
          circuits=x.shape[1] * sizes.layers[1],
          events=int(mono1.events.sum()))
    check("chunk-kernel stream bit-identical to its monolithic run",
          _bit_identical(st1, mono1))
    check("chunk-kernel discrete records == default path",
          _discrete_equal(mono1, ref1))
    check("chunk-kernel energy/latency within rtol 1e-5 of default",
          _analog_close(mono1, ref1),
          f"energy max rel {_max_rel(mono1.energy, ref1.energy):.3e}")
    check("chunk-kernel programs hold tpu_custom_call", _has_kernel(eng1))


def phase_serve(sizes, sur, spec, check):
    import numpy as np
    import repro.lasana as lasana
    from benchmarks.bench_network import poisson_spikes
    jobs = []
    for i in range(sizes.n_requests):
        ticks, batch = sizes.serve_shapes[i % len(sizes.serve_shapes)]
        jobs.append((f"tenant{i % 2}",
                     poisson_spikes(ticks, batch, sizes.layers[0],
                                    rate=sizes.rate, seed=100 + i)))
    t0 = time.perf_counter()
    with lasana.serve() as srv:
        srv.register_surrogate("lif", sur)
        handles = [srv.submit(spec, x, surrogates="lif", tenant=tenant)
                   for tenant, x in jobs]
        served = [h.result(timeout=900) for h in handles]
        stats = srv.stats()
    _line("serve", seconds=f"{time.perf_counter() - t0:.3f}",
          requests=len(jobs), completed=stats["requests_completed"],
          failed=stats["requests_failed"],
          degraded=stats["requests_degraded"],
          retried=stats["requests_retried"],
          compile_count=stats["compile_count"],
          batch_occupancy=f"{stats['batch_occupancy']:.3f}")
    solo = [lasana.simulate(spec, x, surrogates=sur, record_hidden=False)
            for _, x in jobs]
    same = [_discrete_equal(s, r) and _analog_close(s, r)
            for s, r in zip(solo, served)]
    check("every served record equals its solo simulate", all(same),
          f"{sum(same)}/{len(same)}")
    check("serve: 0 failed, 0 degraded, 0 retried",
          stats["requests_failed"] == 0 and stats["requests_degraded"] == 0
          and stats["requests_retried"] == 0
          and not any(h.degraded for h in handles))
    check("serve: every request completed",
          stats["requests_completed"] == len(jobs)
          and int(np.sum([r.events.sum() for r in served])) > 0)


def _stimulus_shards(eng, x_shape):
    """(device counts, per-device stimulus shapes) over the engine's
    monolithic and stream-chunk programs."""
    shard = [compiled.input_shardings[0][0] for key, (compiled, _) in
             eng._sim_cache.items() if key[0] in ("mono", "stream")]
    return ({len(s.device_set) for s in shard},
            {s.shard_shape((1,) + tuple(x_shape[1:]))[1:] for s in shard})


def phase_four_chips(sizes, sur, check):
    import repro.lasana as lasana
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    _, _, spec, x = _net(sizes)
    check("mesh spans 4 devices", mesh.devices.size == 4,
          f"{mesh.devices.size} devices")
    want = {(x.shape[1] // 4, x.shape[2])}     # (batch, fan_in) per device
    runs = (("simulate lasana", lasana.simulate, {}),
            ("simulate megakernel", lasana.simulate, {"fused_kernel": True}),
            ("stream lasana", lasana.simulate_stream,
             {"chunk_ticks": sizes.chunk_ticks}))
    for name, fn, kw in runs:
        t0 = time.perf_counter()
        one = fn(spec, x, surrogates=sur, **kw)
        four = fn(spec, x, surrogates=sur, mesh=mesh, **kw)
        eng = lasana.engine(spec, mesh=mesh,
                            record_hidden=fn is lasana.simulate,
                            fused_kernel=kw.get("fused_kernel"))
        devices, local = _stimulus_shards(eng, x.shape)
        _line(f"four-chips {name}",
              seconds=f"{time.perf_counter() - t0:.3f}",
              stimulus_devices=sorted(devices), stimulus_shard=sorted(local),
              energy_max_rel=f"{_max_rel(four.energy, one.energy):.3e}")
        check(f"{name}: batch sharded over 4 devices",
              devices == {4} and local == want)
        check(f"{name}: sharded discrete records == one device",
              _discrete_equal(four, one))
        check(f"{name}: sharded energy/latency within rtol 1e-5",
              _analog_close(four, one))


def run(sizes, *, four_chips, check):
    """Every phase of one invocation (``sizes`` fixes the workload)."""
    sur = phase_train(sizes)
    if four_chips:
        phase_four_chips(sizes, sur, check)
        return
    spec, x, mono = phase_simulate(sizes, sur, check)
    phase_stream(sizes, sur, spec, x, mono, check)
    phase_serve(sizes, sur, spec, check)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the batch-sharded path on a 4-chip mesh")
    args = ap.parse_args(argv)

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.kernels import ops       # fails outside a checkout
    cache = ops.enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} cache={cache}",
          flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{dev.platform}", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    check = Checks()
    t0 = time.perf_counter()
    run(FULL, four_chips=args.four_chips, check=check)
    n_cached = sum(len(f) for _, _, f in os.walk(cache)) \
        if os.path.isdir(cache) else 0
    _line("total", seconds=f"{time.perf_counter() - t0:.3f}",
          compile_cache_files=n_cached)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
