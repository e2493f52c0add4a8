"""The comparison that decides ``correct``.

For each sampled call of the window (its ``NetworkRun``) the plain
reference recomputes the same stimulus from the same surrogate artifact,
and these numbers compare the two over all sampled calls together:

  mismatch_pct    share of published outputs that differ: spikes
                  (> V_dd / 2) of every recorded layer, or crossbar codes
                  differing by half an ADC step or more
  events_gap_pct  sum |events - reference| / sum reference events, per
                  tick and layer
  energy_gap_pct  sum over answers of |total energy - reference| (ticks,
                  layers and the idle flush) / the reference's energy scale
  energy_tick_gap_pct
                  sum |energy - reference| per tick and layer, and of the
                  flush per layer / the reference's energy scale: the
                  gaps of single ticks cannot cancel one another
                  The energy scale is sum |reference energy| per tick and
                  layer and of the flush per layer: drawn heads may read
                  below zero on some rows, and a signed total that cancels
                  would make rounding look large
  latency_gap_pct sum |per-tick latency - reference| / sum reference
                  latency, per tick and layer

A gap over a scale of 0 (the reference reads no energy, or no latency on
any tick: a drawn M_L head may read below zero on every row that spikes)
is 0 where the program reads nothing either, and infinite where it reads
anything.

A cell compares the numbers its ``limits/<workload>.json`` gives a limit.
"""

from __future__ import annotations

import types

import numpy as np

XB_CODE_STEP = 4.0 / 255 / (40e3 * 12e-6)   # one ADC step, in code units
NAMES = ("mismatch_pct", "events_gap_pct", "energy_gap_pct",
         "energy_tick_gap_pct", "latency_gap_pct")


def _published(run):
    """(program, index into the reference's layers) per recorded output."""
    if run.layer_spikes is not None:
        return list(zip(run.layer_spikes, range(len(run.layer_spikes))))
    if run.out_spikes is None:
        raise ValueError("record holds neither layer outputs nor spikes")
    return [(run.out_spikes, len(run.circuits) - 1)]


def _share(gap: float, scale: float) -> float:
    """``gap`` as a percentage of ``scale``; over a scale of 0, exact."""
    if scale:
        return 100.0 * gap / scale
    return 0.0 if gap == 0 else float("inf")


def compare(pairs: list, refs: list) -> dict:
    """``pairs``: (program record, stimulus) per sampled call; ``refs``:
    the reference's records of each call's stimulus."""
    diff = total = 0
    ev_d = ev_t = e_d = e_t = et_d = l_d = l_t = 0.0
    for (run, _), ref in zip(pairs, refs):
        for prog, i in _published(run):
            r = ref["published"][i]
            prog = np.asarray(prog)
            if run.circuits[i] == "lif":
                d = (prog > 0.75) != (r > 0.75)
            else:
                d = np.abs(prog - r) >= 0.5 * XB_CODE_STEP
            diff += int(d.sum())
            total += d.size
        ev = ref["events"].sum(-1)
        ev_d += float(np.abs(run.events - ev).sum())
        ev_t += float(ev.sum())
        e_tick, e_flush = ref["energy"].sum(-1), ref["flush"].sum(-1)
        e_run = float(np.sum(run.energy, dtype=np.float64)
                      + np.sum(run.flush_energy, dtype=np.float64))
        e_d += abs(e_run - float(e_tick.sum() + e_flush.sum()))
        e_t += float(np.abs(e_tick).sum() + np.abs(e_flush).sum())
        et_d += float(np.abs(run.energy - e_tick).sum()
                      + np.abs(run.flush_energy - e_flush).sum())
        lat = ref["latency"].max(-1)
        l_d += float(np.abs(run.latency - lat).sum())
        l_t += float(lat.sum())
    return {"mismatch_pct": 100.0 * diff / max(total, 1),
            "events_gap_pct": 100.0 * ev_d / max(ev_t, 1.0),
            "energy_gap_pct": _share(e_d, e_t),
            "energy_tick_gap_pct": _share(et_d, e_t),
            "latency_gap_pct": _share(l_d, l_t)}


def run_reference(ref_mod, artifacts, layers, pairs, precision="highest"):
    """The reference's records of each sampled call's stimulus."""
    return [ref_mod.simulate(artifacts, layers, x, precision=precision)
            for _, x in pairs]


def judge(numbers: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` of each number ``limits`` holds,
    in the order of ``NAMES``."""
    return {n: {"value": numbers[n], "limit": limits[n]["limit"]}
            for n in NAMES if n in limits}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def as_record(ref: dict, run):
    """The reference's records of one call, shaped as the program's
    record of it (the control put in the program's place)."""
    pubs = list(ref["published"])
    return types.SimpleNamespace(
        circuits=run.circuits,
        layer_spikes=pubs if run.layer_spikes is not None else None,
        out_spikes=pubs[-1],
        energy=ref["energy"].sum(-1),
        latency=ref["latency"].max(-1),
        events=ref["events"].sum(-1),
        flush_energy=ref["flush"].sum(-1))
