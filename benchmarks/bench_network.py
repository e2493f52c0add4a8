"""Network-engine throughput — batched engine vs naive loop, fused vs
unfused inference (ISSUE-5 A/B).

A 3-layer spiking-MNIST-sized LIF network runs the same event stream two
ways:

  engine  core/network.py: one jit-compiled scan over ticks, all banks
          batched, idle neurons merged into E2 catch-up events
  naive   the pre-engine formulation: a Python loop over ticks and banks,
          one numpy predictor call per model per bank per tick

plus the ISSUE-5 fused-inference A/B on the standard 2-layer CPU
workload: the SAME spec/stimulus/surrogate through two compiled engine
programs —

  fused    lasana_step on ``Surrogate.predict_heads`` (one feature build
           per variant, same-family heads stacked into batched passes)
  unfused  lasana_step with one ``predict`` dispatch per head (the
           pre-ISSUE-5 formulation, ``NetworkEngine(fused=False)``)

Reported: events/s of engine vs naive (acceptance: >= 10x), fused vs
unfused events/s (acceptance: >= 1.3x steady state; the CI smoke leg
hard-fails below 1.0x), the per-program HLO instruction/dot counts of
both A/B programs (fusion must shrink the number of per-tick dot ops —
7 per-head chains collapse into stacked batched matmuls — not just win
a timer race), record parity between the two paths (discrete
outputs/events identical, energies within the documented rtol=1e-5),
compile vs steady-state seconds (explicit AOT warmup — first-call
compilation never pollutes events/s), and the per-layer energy report.

``REPRO_BENCH_SMOKE=1`` runs only the A/B (smaller tick count) and
enforces the >= 1.0x floor + record parity for CI.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import bank, emit, save_json, surrogate, warm_timed

SNN_LAYERS = (196, 64, 32, 10)          # CPU scale
SNN_LAYERS_FULL = (784, 256, 128, 10)   # spiking-MNIST scale
T_STEPS = 60
BATCH = 8

AB_LAYERS = (196, 64, 10)               # the standard 2-layer A/B workload
AB_T_STEPS = 60
AB_T_STEPS_SMOKE = 24


def make_net(layers, seed=0):
    rng = np.random.default_rng(seed)
    ws = []
    for i in range(len(layers) - 1):
        w = rng.normal(0, (2.0 / layers[i]) ** 0.5, (layers[i], layers[i + 1]))
        ws.append((w * 2.2).astype(np.float32))      # drive into spiking range
    params = [np.array([0.58, 0.5, 0.5, 0.5], np.float32) for _ in ws]
    return ws, params


def poisson_spikes(t, b, n, rate=0.25, seed=1):
    rng = np.random.default_rng(seed)
    return (rng.random((t, b, n)) < rate).astype(np.float32) * 1.5


def run_naive(b, weights, spike_seq, params_list, clock=5.0):
    """Per-bank Python loop: Algorithm 1 semantics, one numpy predictor
    call per model per bank per tick (no jit, no cross-tick fusion)."""
    t_steps, batch, _ = spike_seq.shape
    layers = []
    for w, p in zip(weights, params_list):
        n = batch * w.shape[1]
        layers.append({
            "w": w, "conn": (np.abs(w) > 0).astype(np.float32),
            "v": np.zeros(n, np.float32), "o": np.zeros(n, np.float32),
            "t_last": np.zeros(n, np.float32),
            "params": np.broadcast_to(p[None], (n, p.shape[0])),
        })
    energy = 0.0
    events = 0
    t0 = time.time()
    for ti in range(t_steps):
        t = (ti + 1) * clock
        s = spike_seq[ti]
        for L in layers:
            drive = (s @ L["w"]) / 1.5
            pre = (s > 0.75).astype(np.float32)
            changed = ((pre @ L["conn"]) > 0.5).reshape(-1)
            x = np.stack([np.clip(drive, -1, 1),
                          np.full_like(drive, 1.5),
                          np.full_like(drive, 5.0)], -1).reshape(-1, 3)
            n = L["v"].shape[0]
            stale = changed & (L["t_last"] < t - clock)
            tau_idle = np.maximum(t - L["t_last"] - clock, 0.0)
            fi = np.concatenate([np.zeros_like(x), L["v"][:, None],
                                 tau_idle[:, None], L["params"]], 1)
            v_cur = np.where(stale, b.predict_np("M_V", fi), L["v"])
            e = np.where(stale, b.predict_np("M_ES", fi), 0.0)
            tau = np.full((n, 1), clock, np.float32)
            f = np.concatenate([x, v_cur[:, None], tau, L["params"]], 1)
            o_hat = b.predict_np("M_O", f)
            v_new = b.predict_np("M_V", f)
            fired = o_hat > 0.75
            o_res = np.where(fired, 1.5, 0.0)
            ftr = np.concatenate([f, L["o"][:, None], o_res[:, None]], 1)
            e_evt = np.where(fired, b.predict_np("M_ED", ftr),
                             b.predict_np("M_ES", f))
            b.predict_np("M_L", ftr)
            energy += float(np.sum(e + np.where(changed, e_evt, 0.0)))
            L["v"] = np.where(changed, v_new, v_cur).astype(np.float32)
            L["o"] = np.where(changed, o_res, L["o"]).astype(np.float32)
            L["t_last"] = np.where(changed, t, L["t_last"]).astype(np.float32)
            events += int(changed.sum())
            s = np.where(changed, o_res, 0.0).reshape(batch, -1)
    return {"events": events, "energy_j": energy,
            "wall_seconds": time.time() - t0}


def _hlo_counts(engine) -> dict:
    """HLO instruction / dot-op counts of an engine's compiled programs.

    The per-tick inference body lives inside the scan's while-loop, which
    appears once in the optimized HLO — so instruction counts compare the
    per-tick op graphs of two same-shape programs directly."""
    out = {}
    for key, (compiled, _) in engine._sim_cache.items():
        try:
            txt = compiled.as_text()
        except Exception:          # backend without HLO text dumps
            continue
        lines = [l for l in txt.splitlines() if " = " in l]
        out[key[0]] = {
            "instructions": len(lines),
            "dots": sum(1 for l in lines
                        if " dot(" in l or " custom-call" in l and "gemm"
                        in l),
        }
    return out


def _record_parity(run_f, run_u) -> dict:
    """Fused-vs-unfused record agreement (ISSUE-5 documented tolerance:
    discrete records identical, analog records to rtol 1e-5)."""
    e_f, e_u = run_f.energy, run_u.energy
    rel = float(np.max(np.abs(e_f - e_u)
                       / np.maximum(np.abs(e_u), 1e-30)))
    return {
        "outputs_identical": bool(np.array_equal(run_f.outputs,
                                                 run_u.outputs)),
        "events_identical": bool(np.array_equal(run_f.events,
                                                run_u.events)),
        "energy_max_rel_err": rel,
        "energy_within_tolerance": bool(np.allclose(e_f, e_u, rtol=1e-5,
                                                    atol=1e-20)),
    }


def run_fused_ab(full: bool = False, smoke: bool = False) -> dict:
    """Fused / unfused / megakernel A/B/C on the standard 2-layer CPU
    workload: the SAME spec/stimulus/surrogate through three compiled
    engine programs (per-predict-call, stacked 3-dispatch predict_heads,
    and the ISSUE-7 whole-tick megakernel via ``fused_kernel=True``)."""
    from repro.core.network import NetworkEngine, snn_spec

    t_steps = AB_T_STEPS_SMOKE if smoke else AB_T_STEPS
    ws, params = make_net(AB_LAYERS, seed=5)
    spikes = poisson_spikes(t_steps, BATCH, AB_LAYERS[0], seed=6)
    fams = ("mean", "linear", "mlp")
    sur = surrogate("lif", full, families=fams)
    spec = snn_spec(ws, params)

    repeats = 5                        # min-of-N steadies the CI floor
    eng_f = NetworkEngine(spec, surrogates=sur, record_hidden=False,
                          fused_kernel=False)
    run_f, cold_f, steady_f = warm_timed(eng_f.run, spikes,
                                         repeats=repeats, stat="min")
    eng_u = NetworkEngine(spec, surrogates=sur, record_hidden=False,
                          fused=False)
    run_u, cold_u, steady_u = warm_timed(eng_u.run, spikes,
                                         repeats=repeats, stat="min")
    eng_m = NetworkEngine(spec, surrogates=sur, record_hidden=False,
                          fused_kernel=True)
    run_m, cold_m, steady_m = warm_timed(eng_m.run, spikes,
                                         repeats=repeats, stat="min")
    events = int(run_f.events.sum())
    ev_fused = events / max(steady_f, 1e-9)
    ev_unfused = events / max(steady_u, 1e-9)
    ev_mega = events / max(steady_m, 1e-9)
    speedup = ev_fused / max(ev_unfused, 1e-9)
    parity = _record_parity(run_f, run_u)
    parity_mega = _record_parity(run_m, run_f)
    hlo_f = _hlo_counts(eng_f).get("mono", {})
    hlo_u = _hlo_counts(eng_u).get("mono", {})
    hlo_m = _hlo_counts(eng_m).get("mono", {})
    return {
        "layers": list(AB_LAYERS), "t_steps": t_steps, "batch": BATCH,
        "events": events,
        "events_per_sec_fused": ev_fused,
        "events_per_sec_unfused": ev_unfused,
        "events_per_sec_mega": ev_mega,
        "fused_speedup": speedup,
        "mega_speedup_vs_fused": ev_mega / max(ev_fused, 1e-9),
        "mega_speedup_vs_unfused": ev_mega / max(ev_unfused, 1e-9),
        "fused_compile_seconds": run_f.compile_seconds,
        "unfused_compile_seconds": run_u.compile_seconds,
        "mega_compile_seconds": run_m.compile_seconds,
        "fused_steady_seconds": steady_f,
        "unfused_steady_seconds": steady_u,
        "mega_steady_seconds": steady_m,
        "fused_cold_call_seconds": cold_f,
        "unfused_cold_call_seconds": cold_u,
        "mega_cold_call_seconds": cold_m,
        "hlo_fused": hlo_f, "hlo_unfused": hlo_u, "hlo_mega": hlo_m,
        "parity": parity,
        "parity_mega": parity_mega,
    }


def _gate_fail(msg: str, record: dict):
    """Abort on a failed acceptance gate WITHOUT losing the measurements.

    The computed A/B record rides on the exception (``bench_record``) so
    ``benchmarks.run --json`` can still write it — the failing record is
    exactly the one worth keeping — and it is saved to
    results/benchmarks/ before raising."""
    save_json("network_engine", {"fused_ab": record, "gate_failure": msg})
    err = SystemExit(msg)
    err.bench_record = {"fused_ab": record, "gate_failure": msg}
    raise err


def run(full: bool = False):
    import repro.lasana as lasana
    from repro.core.network import snn_spec

    from repro.kernels import ops
    smoke = ops.bench_smoke()

    # --- ISSUE-5 fused-vs-unfused A/B (the CI smoke contract) ------------
    ab = run_fused_ab(full, smoke)
    emit("network/events_per_sec_fused", ab["events_per_sec_fused"])
    emit("network/events_per_sec_unfused", ab["events_per_sec_unfused"])
    emit("network/fused_speedup", ab["fused_speedup"],
         f"target >=1.3x; hlo dots {ab['hlo_fused'].get('dots')} vs "
         f"{ab['hlo_unfused'].get('dots')} "
         f"(instrs {ab['hlo_fused'].get('instructions')} vs "
         f"{ab['hlo_unfused'].get('instructions')})")
    emit("network/events_per_sec_mega", ab["events_per_sec_mega"])
    emit("network/mega_speedup_vs_fused", ab["mega_speedup_vs_fused"],
         f"target >=1.15x; hlo instrs {ab['hlo_mega'].get('instructions')} "
         f"vs {ab['hlo_fused'].get('instructions')}")
    emit("network/mega_speedup_vs_unfused", ab["mega_speedup_vs_unfused"],
         "target >=1.8x")
    parity = ab["parity"]
    if not (parity["outputs_identical"] and parity["events_identical"]
            and parity["energy_within_tolerance"]):
        # deterministic on the pinned stack (fixed seeds, pinned jax):
        # discrete records are exactly equal unless an o_hat lands within
        # ULPs of the spike threshold, which this seeded workload avoids.
        # A jax/XLA upgrade that reassociates dots differently could move
        # a borderline spike — if this gate ever trips after an upgrade,
        # compare parity["energy_max_rel_err"] against the documented
        # rtol=1e-5 before suspecting the fused path itself.
        _gate_fail(f"fused/unfused records diverged: {parity}", ab)
    pm = ab["parity_mega"]
    if not (pm["outputs_identical"] and pm["events_identical"]
            and pm["energy_within_tolerance"]):
        # the megakernel is a pure reformulation of the fused tick: its
        # discrete records must match the 3-dispatch path bit for bit
        _gate_fail(f"megakernel/fused records diverged: {pm}", ab)
    if ab["fused_speedup"] < 1.3:
        print(f"# WARNING: fused speedup {ab['fused_speedup']:.2f}x below "
              "1.3x target")
    if ab["mega_speedup_vs_fused"] < 1.15:
        print(f"# WARNING: megakernel speedup "
              f"{ab['mega_speedup_vs_fused']:.2f}x below 1.15x target")
    if smoke and ab["fused_speedup"] < 1.0:
        # the CI floor: fusion must never LOSE throughput
        _gate_fail(
            f"fused path slower than unfused ({ab['fused_speedup']:.2f}x "
            "< 1.0x smoke floor)", ab)
    if smoke and ab["mega_speedup_vs_fused"] < 1.0:
        # same floor for the megakernel: it must never LOSE to its own
        # fused 3-dispatch baseline
        _gate_fail(
            f"megakernel slower than fused baseline "
            f"({ab['mega_speedup_vs_fused']:.2f}x < 1.0x smoke floor)", ab)
    if smoke:
        out = {"fused_ab": ab, "smoke": True}
        save_json("network_engine", out)
        return out

    layers = SNN_LAYERS_FULL if full else SNN_LAYERS
    ws, params = make_net(layers)
    spikes = poisson_spikes(T_STEPS, BATCH, layers[0])
    fams = ("mean", "linear", "mlp")
    b = bank("lif", full, families=fams)
    sur = surrogate("lif", full, families=fams)
    spec = snn_spec(ws, params)

    # the engine AOT-compiles on first use: wall_seconds is steady-state
    # execution, compile_seconds the one-time trace+compile — reported
    # separately (never mixed into events/s)
    eng = lasana.engine(spec, record_hidden=False)
    run_e, cold_s, _ = warm_timed(eng.run, spikes, surrogates=sur)
    rep = run_e.report()
    ev_engine = rep["network"]["events_per_sec"]

    # naive: same event stream, Python loop over ticks x banks (numpy —
    # nothing compiles, so cold == steady and no warmup is needed)
    naive = run_naive(b, ws, spikes, params)
    ev_naive = naive["events"] / max(naive["wall_seconds"], 1e-9)
    speedup = ev_engine / max(ev_naive, 1e-9)

    # golden reference for context (the SPICE stand-in through the engine)
    run_g = lasana.engine(spec, backend="golden", record_hidden=False
                          ).run(spikes)
    rep_g = run_g.report()

    out = {
        "layers": list(layers), "t_steps": T_STEPS, "batch": BATCH,
        "engine": rep, "naive": naive,
        "golden": rep_g["network"],
        "fused_ab": ab,
        "events_per_sec_engine": ev_engine,
        "events_per_sec_naive": ev_naive,
        "speedup_engine_over_naive": speedup,
        "engine_compile_seconds": run_e.compile_seconds,
        "engine_steady_seconds": run_e.wall_seconds,
        "engine_cold_call_seconds": cold_s,
        "energy_err_vs_golden": abs(
            rep["network"]["energy_j"] - rep_g["network"]["energy_j"])
        / max(rep_g["network"]["energy_j"], 1e-30),
    }
    save_json("network_engine", out)
    emit("network/events_per_sec_engine", ev_engine)
    emit("network/events_per_sec_naive", ev_naive)
    emit("network/compile_seconds", run_e.compile_seconds,
         f"steady={run_e.wall_seconds:.4f}s cold_call={cold_s:.2f}s")
    for l in rep["layers"]:       # per-layer attribution (circuit + backend)
        emit(f"network/layer{l['layer']}_{l['circuit']}_energy_nj",
             l["energy_j"] * 1e9, f"{l['events']} events, {l['backend']}")
    emit("network/speedup", speedup,
         f"target >=10x; energy_err={out['energy_err_vs_golden']:.2%}")
    if speedup < 10:
        print(f"# WARNING: engine speedup {speedup:.1f}x below 10x target")
    return out


if __name__ == "__main__":
    import sys
    run(full="--full" in sys.argv)
