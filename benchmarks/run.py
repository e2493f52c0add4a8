"""Benchmark harness — one entry per paper table (+ the roofline report).

``PYTHONPATH=src python -m benchmarks.run [--full] [--only table1,...]
[--json BENCH.json]``

Prints ``name,us_per_call,derived`` CSV lines and writes JSON records under
results/benchmarks/. ``--json PATH`` additionally writes ONE
machine-readable trajectory record: a headline ``summary`` (events/s,
fused speedup, peak RSS, compile vs steady seconds) over the full
per-suite records — the perf baseline future PRs diff against (see
``BENCH_5.json`` at the repo root).

  table1    model training/testing times            (paper Table I)
  table2    predictor accuracy MSE/MAPE             (paper Table II)
  table3    error propagation LASANA-O vs -P + Fig8 (paper Table III)
  table4    runtime scaling vs layer size           (paper Table IV)
  network   network engine events/s vs naive loop   (§V-E system scale)
  mixed     heterogeneous crossbar->LIF graph       (§V-E mixed-signal)
  streaming chunked runs vs monolithic, T=10k       (ISSUE-4 tentpole)
  dse       vectorized 1024-candidate sweep vs loop (ISSUE-6 tentpole)
  serve     multi-tenant continuous batching        (ISSUE-8 tentpole)
  roofline  dry-run roofline terms                  (EXPERIMENTS §Roofline)
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _get(record, *path):
    """Nested dict lookup that tolerates missing suites/fields."""
    cur = record
    for key in path:
        if not isinstance(cur, dict) or key not in cur:
            return None
        cur = cur[key]
    return cur


def _summary(records: dict) -> dict:
    """The headline trajectory numbers future PRs diff against."""
    net = records.get("network") or {}
    stream = records.get("streaming") or {}
    dse = records.get("dse") or {}
    serve = records.get("serve") or {}
    return {
        # throughput
        "events_per_sec_engine": _get(net, "events_per_sec_engine"),
        "events_per_sec_fused": _get(net, "fused_ab",
                                     "events_per_sec_fused"),
        "events_per_sec_unfused": _get(net, "fused_ab",
                                       "events_per_sec_unfused"),
        "events_per_sec_mega": _get(net, "fused_ab",
                                    "events_per_sec_mega"),
        "events_per_sec_stream": _get(stream, "events_per_sec_stream"),
        "events_per_sec_stream_mega": _get(stream,
                                           "events_per_sec_stream_mega"),
        # the ISSUE-5 headline
        "fused_speedup": _get(net, "fused_ab", "fused_speedup"),
        # the ISSUE-7 headline
        "mega_speedup_vs_fused": _get(net, "fused_ab",
                                      "mega_speedup_vs_fused"),
        "mega_speedup_vs_unfused": _get(net, "fused_ab",
                                        "mega_speedup_vs_unfused"),
        "mega_over_fused_stream": _get(stream, "mega_over_fused_stream"),
        "fused_hlo_dots": _get(net, "fused_ab", "hlo_fused", "dots"),
        "unfused_hlo_dots": _get(net, "fused_ab", "hlo_unfused", "dots"),
        "fused_over_unfused_stream": _get(stream,
                                          "fused_over_unfused_stream"),
        # memory
        "peak_rss_kb_stream": _get(stream, "peak_rss_kb_stream"),
        "peak_rss_kb_mono": _get(stream, "peak_rss_kb_mono"),
        # compile vs steady split
        "compile_seconds_fused": _get(net, "fused_ab",
                                      "fused_compile_seconds"),
        "steady_seconds_fused": _get(net, "fused_ab",
                                     "fused_steady_seconds"),
        "steady_seconds_unfused": _get(net, "fused_ab",
                                       "unfused_steady_seconds"),
        # the ISSUE-6 design-space sweep
        "dse_candidates_per_sec": _get(dse, "candidates_per_sec_batched"),
        "dse_speedup_vs_loop": _get(dse, "speedup_vs_loop"),
        "dse_compile_count": _get(dse, "compile_count"),
        "dse_pareto_size": _get(dse, "pareto_size"),
        # the ISSUE-8 serving layer
        "serve_requests_per_sec": _get(serve, "requests_per_sec_served"),
        "serve_speedup_vs_serial": _get(serve, "speedup_vs_serial"),
        "serve_compile_count": _get(serve, "compile_count"),
        "serve_occupancy": _get(serve, "batch_occupancy"),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale datasets/models (slow)")
    ap.add_argument("--only", default="",
                    help="comma list: table1,table2,table3,table4,network,"
                         "mixed,streaming,dse,serve,roofline")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write one machine-readable trajectory record "
                         "(summary + per-suite outputs) to PATH")
    args = ap.parse_args()

    from repro.kernels import ops
    ops.enable_compile_cache()
    from benchmarks import (bench_accuracy, bench_dse, bench_mixed,
                            bench_models, bench_network, bench_propagation,
                            bench_roofline, bench_scaling, bench_serve,
                            bench_streaming)
    suites = {
        "table1": bench_models.run,
        "table2": bench_accuracy.run,
        "table3": bench_propagation.run,
        "table4": bench_scaling.run,
        "network": bench_network.run,
        "mixed": bench_mixed.run,
        "streaming": bench_streaming.run,
        "dse": bench_dse.run,
        "serve": bench_serve.run,
        "roofline": bench_roofline.run,
    }
    only = [s for s in args.only.split(",") if s] or list(suites)
    print("name,us_per_call,derived")
    records: dict = {}
    wall: dict = {}
    aborted = None        # a suite's acceptance SystemExit (smoke gates)
    for name in only:
        t0 = time.time()
        try:
            records[name] = suites[name](full=args.full)
        except SystemExit as e:
            # acceptance gates (fused floor, record parity) abort the run
            # — but the trajectory record must still be written below, or
            # the numbers needed to DIAGNOSE the failure are lost; gates
            # attach their measurements to the exception (bench_record)
            records[name] = {"aborted": str(e) or "SystemExit",
                             **(getattr(e, "bench_record", None) or {})}
            aborted = e
        wall[name] = time.time() - t0
        print(f"# {name} done in {wall[name]:.1f}s", file=sys.stderr)
        if aborted is not None:
            break

    if args.json:
        import jax

        payload = {
            "schema": 1,
            "generated_by": "benchmarks.run",
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
            "full": bool(args.full),
            "smoke": ops.bench_smoke(),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "suites_run": only,
            "aborted": str(aborted) if aborted is not None else None,
            "suite_wall_seconds": wall,
            "summary": _summary(records),
            "suites": records,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, default=float)
        print(f"# wrote {args.json}", file=sys.stderr)
    if aborted is not None:
        raise aborted


if __name__ == "__main__":
    main()
