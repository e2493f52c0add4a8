"""Plain reference of the ``diehl-cook-6400`` configuration: the Algorithm 1
of ``reference/lasana_net.py``, whose every other function this module
uses, with each tick's circuit energies summed over the whole batch.

A tick's energy is the float32 sum of its circuits' energies, taken here
in one reduction over all lanes, as a LIF layer's record states it (one
total a tick and layer). ``lasana_net.py`` sums each lane on its own and
the lanes afterwards; where this layer saturates (every neuron spiking,
its drive clipped to -1 by the inhibition), every circuit of a tick reads
the same energy, each partial sum rounds the same way tick after tick, and
the two orders of summation differ by more than the reference at ``high``
differs from it at ``highest``. ``READS_EDGES`` as in ``lasana_net.py``:
the 6400 x 6400 lateral inhibition is a delayed edge.
"""

from __future__ import annotations

import importlib.util
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "lasbench_reference_lasana_net_base",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "lasana_net.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

# the circuit interfaces, the artifact reader, the heads, Algorithm 1 and
# the adapters: lasana_net.py's own
(LIF_CLOCK_NS, LIF_VDD, LIF_SPIKES_PER_PERIOD, XB_CLOCK_NS, XB_INPUTS,
 XB_V_SAT, XB_GAIN, XB_IN_HI, XB_EVENT_EPS, PRECISIONS, READS_EDGES) = (
    _base.LIF_CLOCK_NS, _base.LIF_VDD, _base.LIF_SPIKES_PER_PERIOD,
    _base.XB_CLOCK_NS, _base.XB_INPUTS, _base.XB_V_SAT, _base.XB_GAIN,
    _base.XB_IN_HI, _base.XB_EVENT_EPS, _base.PRECISIONS, _base.READS_EDGES)
load_artifact, dot, head, _derived, _feats, alg1 = (
    _base.load_artifact, _base.dot, _base.head, _base._derived, _base._feats,
    _base.alg1)
_to_lif, _to_crossbar, _hits, _row_params, _Heads = (
    _base._to_lif, _base._to_crossbar, _base._hits, _base._row_params,
    _base._Heads)


@partial(jax.jit, static_argnames=("kinds", "n_out", "acts", "edges",
                                   "precision"))
def _run(heads, weights, knobs, edge_w, stimulus, t_end, *, kinds, n_out,
         acts, edges, precision):
    t_steps, b, _ = stimulus.shape
    into = [[j for j, (_, dst) in enumerate(edges) if dst == i]
            for i in range(len(kinds))]
    sources = {src for src, _ in edges}
    # each edge source's output of the tick before; None where no edge
    # leaves the layer
    prev = tuple(jnp.zeros((b, n_out[i]), jnp.float32) if i in sources
                 else None for i in range(len(kinds)))
    states = []
    for i, kind in enumerate(kinds):
        if kind == "lif":          # one knob set for the whole layer
            n = b * n_out[i]
            p = jnp.broadcast_to(knobs[i][None], (n, knobs[i].shape[0]))
        else:                      # every lane holds the same rows
            n = b * knobs[i].shape[0]
            p = jnp.broadcast_to(knobs[i][None], (b,) + knobs[i].shape
                                 ).reshape(n, -1)
        z = jnp.zeros((n,), jnp.float32)
        states.append((z, z, z, p))

    def tick(carry, xs):
        states, prev = carry
        u_in, k = xs
        cur, src, act = u_in, "input", "tanh"
        new_states, pubs, es, ls, evs = [], [], [], [], []
        for i, kind in enumerate(kinds):
            t = (k + 1.0) * (LIF_CLOCK_NS if kind == "lif" else XB_CLOCK_NS)
            if kind == "lif":
                u = _to_lif(src, act, cur)
                drive = dot(u, weights[i], precision) / LIF_VDD
                hit = _hits(u, src, weights[i])
                for j in into[i]:
                    s = edges[j][0]
                    ur = _to_lif(kinds[s], acts[s], prev[s])
                    drive = drive + dot(ur, edge_w[j], precision) / LIF_VDD
                    hit = hit | _hits(ur, kinds[s], edge_w[j])
                changed = hit.reshape(-1)
                d = jnp.clip(drive, -1.0, 1.0).reshape(-1)
                x = jnp.stack([d, jnp.full_like(d, LIF_VDD),
                               jnp.full_like(d, LIF_SPIKES_PER_PERIOD)], 1)
                st, e, lat = alg1(heads[kind], kind, states[i], changed, x,
                                  t, precision)
                pub = jnp.where(changed, st[1], 0.0).reshape(b, -1)
            else:
                xv = _to_crossbar(src, act, cur)
                for j in into[i]:
                    s = edges[j][0]
                    xv = xv + dot(_to_crossbar(kinds[s], acts[s], prev[s]),
                                  edge_w[j], precision)
                xv = jnp.clip(xv, -XB_IN_HI, XB_IN_HI)
                fan_in = weights[i].shape[0]
                n_seg = -(-fan_in // XB_INPUTS)
                xp = jnp.pad(xv, ((0, 0), (0, n_seg * XB_INPUTS - fan_in)))
                x = jnp.broadcast_to(
                    xp.reshape(b, 1, n_seg, XB_INPUTS),
                    (b, n_out[i], n_seg, XB_INPUTS)).reshape(-1, XB_INPUTS)
                changed = jnp.any(jnp.abs(x) > XB_EVENT_EPS, axis=1)
                st, e, lat = alg1(heads[kind], kind, states[i], changed, x,
                                  t, precision)
                levels = 255.0
                v = st[1]
                v_adc = (jnp.round((v + XB_V_SAT) / (2 * XB_V_SAT) * levels)
                         / levels * 2 * XB_V_SAT - XB_V_SAT)
                pub = v_adc.reshape(b, n_out[i], n_seg).sum(-1) / XB_GAIN
            new_states.append(st)
            pubs.append(pub)
            es.append(jnp.sum(e)[None])
            ls.append(lat.reshape(b, -1).max(1))
            evs.append(changed.reshape(b, -1).sum(1, dtype=jnp.int32))
            cur, src, act = pub, kind, acts[i]
        new_prev = tuple(None if p is None else pubs[i]
                         for i, p in enumerate(prev))
        return (new_states, new_prev), (tuple(pubs), jnp.stack(es),
                                        jnp.stack(ls), jnp.stack(evs))

    ks = jnp.arange(t_steps, dtype=jnp.float32)
    (states, _), (pubs, es, ls, evs) = jax.lax.scan(
        tick, (states, prev), (stimulus, ks))
    flush = []
    for i, kind in enumerate(kinds):
        if kind != "lif":
            flush.append(jnp.zeros((1,), jnp.float32))
            continue
        v, _, t_last, p = states[i]
        tau = jnp.repeat(t_end * LIF_CLOCK_NS, n_out[i]) - t_last
        f = _feats(kind, jnp.zeros((v.shape[0], 3), jnp.float32), v, tau, p)
        e = jnp.where(tau > 0, head(heads[kind]["M_ES"], f, precision), 0.0)
        flush.append(jnp.sum(e)[None])
    return pubs, es, ls, evs, jnp.stack(flush)


def simulate(artifacts: dict, layers: list, stimulus, *,
             precision: str = "highest") -> dict:
    """Reference records of one network run, kept per batch row.

    artifacts  {circuit kind: load_artifact(...)}
    layers     [{"kind": "lif", "weight": (fan_in, n_out), "knobs": (4,)}
                | {"kind": "crossbar", "weight": (fan_in, n_out) ternary,
                   "activation": "tanh" (default) | "none"}], each with
               optional "edges_in": [{"src": layer index, "weight":
               (n_out[src], n_out) into lif, (n_out[src], fan_in) into
               crossbar}]
    stimulus   (T, B, fan_in) drive of the first layer

    Returns per-row records: ``published`` [(T, B, n_out) per layer],
    ``latency``/``events`` (T, L, B); ``energy`` (T, L, 1) and ``flush``
    (L, 1) summed over every lane's circuits at once.
    """
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    x = jnp.asarray(stimulus, jnp.float32)
    t_steps, b, _ = x.shape
    t_end = jnp.full((b,), t_steps, jnp.float32)
    kinds = tuple(l["kind"] for l in layers)
    n_out = tuple(int(np.shape(l["weight"])[1]) for l in layers)
    acts = tuple(l.get("activation", "tanh") for l in layers)
    edges_in = [(e["src"], i, e["weight"]) for i, l in enumerate(layers)
                for e in l.get("edges_in", ())]
    edges = tuple((src, dst) for src, dst, _ in edges_in)
    edge_w = tuple(jnp.asarray(w, jnp.float32) for _, _, w in edges_in)
    heads = {k: {n: {"family": h["family"], "scale": h["scale"],
                     "arrays": {a: jnp.asarray(v) for a, v in
                                h["arrays"].items()}}
                 for n, h in artifacts[k]["heads"].items()}
             for k in set(kinds)}
    knobs = tuple(jnp.asarray(l["knobs"], jnp.float32) if l["kind"] == "lif"
                  else jnp.asarray(_row_params(np.asarray(l["weight"])))
                  for l in layers)
    weights = tuple(jnp.asarray(l["weight"], jnp.float32) for l in layers)
    pubs, es, ls, evs, flush = jax.device_get(_run(
        _Heads(heads), weights, knobs, edge_w, x, t_end, kinds=kinds,
        n_out=n_out, acts=acts, edges=edges, precision=precision))
    return {"published": [np.asarray(p) for p in pubs],
            "energy": np.asarray(es, np.float64),
            "latency": np.asarray(ls, np.float64),
            "events": np.asarray(evs, np.int64),
            "flush": np.asarray(flush, np.float64)}
