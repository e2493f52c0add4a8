"""Plain reference of a LASANA circuit network: Algorithm 1, tick by tick.

Straightforward ``jax.numpy`` in float32, written from the LASANA paper
(arXiv 2507.10748, Algorithm 1 and §V-E) and the circuit interfaces it
names. It imports nothing of the program under test. The surrogate's
predictor arrays are read here with numpy from the ``.npz`` artifact the
harness drew from the seed (the same file the program loads); every
feature and derived column is recomputed here.

One network tick, per layer, in graph order:

  lif       drive = (u @ W) / V_dd, clipped to [-1, 1]; u is the previous
            layer's spikes, or its codes through its activation x V_dd; a
            neuron has an input event when a live presynaptic line (a
            spike, |u| > V_dd / 2; a code, |u| > V_dd / 20) arrives
            through a nonzero weight; circuit inputs (drive, V_dd, 5)
  crossbar  the previous layer's codes through its activation (tanh or
            none), x 0.8 V, or its spikes x 0.8 V / V_dd (the stimulus as
            given for the first layer), clipped to +-0.8 V, cut into
            32-input row segments; a row has an input event when any of
            its lines is live (|x| > 1e-6)
  edges     an edge into a layer carries its source layer's output of
            the tick before (zeros on the first tick), adapted as above:
            into a lif layer, (u_src @ W_edge) / V_dd adds to the drive
            before the clip, and its live lines add input events; into a
            crossbar layer, u_src @ W_edge adds to the volts before the
            clip
  Alg. 1    stale event-receiving circuits catch up with one merged idle
            event (M_ES, M_V at zero input and the idle gap tau); then
            M_O, M_V, M_ES on the active rows and M_ED, M_L on the
            transition rows (active rows plus the previous and the
            resolved output); dynamic energy and latency where the output
            changed, static energy where it did not
  publish   lif: V_dd where the neuron spiked, else 0; crossbar: every
            row's output through an 8-bit ADC over +-2 V, summed over the
            row's segments and divided by the TIA gain
  flush     after the last tick each lif circuit pays M_ES over its
            trailing idle gap; crossbar rows pay nothing

Every matmul runs at the precision given: ``highest`` (float32),
``high`` (three bfloat16 passes, the TPU's bf16_3x, emulated by operand
splitting so it reads the same on any backend) or ``bf16`` (one pass).
The splitting rounds with integer operations: a float conversion pair
may be folded away by the TPU compiler.
"""

from __future__ import annotations

import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

# circuit interfaces (paper §V-E; the LIF neuron of Indiveri and the
# 32-input PCM crossbar row with its TIA)
LIF_CLOCK_NS = 5.0
LIF_VDD = 1.5
LIF_SPIKES_PER_PERIOD = 5.0
XB_CLOCK_NS = 4.0
XB_INPUTS = 32
XB_V_SAT = 2.0
XB_GAIN = -40e3 * 12e-6          # -R_f * G_unit
XB_V_BIAS = 0.8
XB_IN_HI = 0.8
XB_EVENT_EPS = 1e-6
XB_OUT_EPS = 0.02

PRECISIONS = ("highest", "high", "bf16")
READS_EDGES = True               # layers' ``edges_in`` are simulated


def load_artifact(path: str) -> dict:
    """Read a saved surrogate ``.npz``: ``{"circuit", "heads": {name:
    {"family", "scale", "arrays"}}}``."""
    with np.load(path) as z:
        meta = json.loads(bytes(z["__manifest__"].tobytes()).decode())
        heads = {}
        for name, family in meta["families"].items():
            arrays = {k.split("/", 1)[1]: np.asarray(z[k], np.float32)
                      for k in z.files if k.startswith(name + "/")}
            heads[name] = {"family": family,
                           "scale": float(meta["scales"][name]),
                           "arrays": arrays}
    return {"circuit": meta["circuit"], "heads": heads}


def _bf16(a):
    """float32 rounded to the nearest bfloat16 (ties to even), by integer
    operations on the bits, which no compiler folds away."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def dot(a, b, precision: str):
    """``a @ b`` at ``precision`` (see the module docstring)."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=HIGHEST)
    a1, b1 = _bf16(a), _bf16(b)
    if precision == "bf16":
        return jnp.matmul(a1, b1, precision=HIGHEST)
    a2, b2 = _bf16(a - a1), _bf16(b - b1)
    return (jnp.matmul(a1, b1, precision=HIGHEST)
            + jnp.matmul(a1, b2, precision=HIGHEST)
            + jnp.matmul(a2, b1, precision=HIGHEST))


def head(h: dict, x, precision: str):
    """One predictor's forward pass, in physical units."""
    a = h["arrays"]
    if h["family"] == "mean":
        y = jnp.full((x.shape[0],), a["mu"].reshape(()))
    elif h["family"] == "linear":
        xs = (x - a["mu"]) / a["sd"]
        y = dot(xs, a["w"][:-1, None], precision)[:, 0] + a["w"][-1]
    elif h["family"] == "mlp":
        z = (x - a["x_mu"]) / a["x_sd"]
        n = sum(1 for k in a if k.startswith("w"))
        for i in range(n):
            z = dot(z, a[f"w{i}"], precision) + a[f"b{i}"]
            if i < n - 1:
                z = jnp.maximum(z, 0.0)
        y = z[:, 0] * a["y_sd"][0] + a["y_mu"][0]
    else:
        raise ValueError(f"reference has no {h['family']!r} predictor")
    return y / h["scale"]


def _derived(kind: str, x, p):
    """The circuit's derived interface column, from inputs and knobs."""
    if kind == "lif":
        return (x[:, 0] * x[:, 1] * x[:, 2] / 5.0)[:, None]
    return ((p[:, :XB_INPUTS] * x).sum(axis=1)
            + p[:, XB_INPUTS] * XB_V_BIAS)[:, None]


def _feats(kind, x, v, tau, p, extra=()):
    cols = [x, v[:, None], tau[:, None], p] + [c[:, None] for c in extra]
    return jnp.concatenate(cols + [_derived(kind, x, p)], axis=1)


def alg1(heads, kind, state, changed, x, t, precision):
    """One tick of Algorithm 1 for N circuits of one kind."""
    v, o, t_last, p = state
    clock = LIF_CLOCK_NS if kind == "lif" else XB_CLOCK_NS
    stale = changed & (t_last < t - clock)
    tau_idle = jnp.maximum(t - t_last - clock, 0.0)
    f_idle = _feats(kind, jnp.zeros_like(x), v, tau_idle, p)
    e_idle = head(heads["M_ES"], f_idle, precision)
    v_cur = jnp.where(stale, head(heads["M_V"], f_idle, precision), v)
    tau = jnp.full_like(v, clock)
    f_act = _feats(kind, x, v_cur, tau, p)
    o_hat = head(heads["M_O"], f_act, precision)
    v_new = head(heads["M_V"], f_act, precision)
    e_s = head(heads["M_ES"], f_act, precision)
    if kind == "lif":
        out_changed = o_hat > 0.5 * LIF_VDD
        o_res = jnp.where(out_changed, LIF_VDD, 0.0)
    else:
        out_changed = jnp.abs(o_hat - o) > XB_OUT_EPS
        o_res = o_hat
    f_tr = _feats(kind, x, v_cur, tau, p, extra=(o, o_res))
    e_d = head(heads["M_ED"], f_tr, precision)
    lat = head(heads["M_L"], f_tr, precision)
    e = (jnp.where(stale, e_idle, 0.0)
         + jnp.where(changed, jnp.where(out_changed, e_d, e_s), 0.0))
    lat = jnp.where(changed & out_changed, lat, 0.0)
    o_out = jnp.where(changed, o_res, o)
    new = (jnp.where(changed, v_new, v_cur), o_out,
           jnp.where(changed, t, t_last), p)
    return new, e, lat


def _act(y, activation: str):
    """A crossbar layer's digital activation of its codes."""
    if activation == "tanh":
        return jnp.tanh(y)
    if activation == "none":
        return y
    raise ValueError(f"reference has no {activation!r} activation")


def _to_lif(src: str, activation: str, y):
    """A source's output as lif drive: spikes as they are, codes through
    the source's activation x V_dd."""
    if src in ("input", "lif"):
        return y
    return _act(y, activation) * LIF_VDD


def _to_crossbar(src: str, activation: str, y):
    """A source's output as crossbar input volts."""
    if src == "input":
        return y
    if src == "lif":
        return y * (XB_IN_HI / LIF_VDD)
    return _act(y, activation) * XB_IN_HI


def _hits(u, src: str, w):
    """(B, n_out) bool: a live line of ``u`` reaches the neuron through a
    nonzero weight of ``w``."""
    thr = 0.5 * LIF_VDD if src in ("input", "lif") else 0.05 * LIF_VDD
    pre = (jnp.abs(u) > thr).astype(jnp.float32)
    conn = (jnp.abs(w) > 0).astype(jnp.float32)
    return dot(pre, conn, "highest") > 0.5


def _row_params(w: np.ndarray) -> np.ndarray:
    """(fan_in, n_out) ternary matrix -> (n_out * n_seg, 33) row knobs:
    row (j, s) holds weights fan_in[32 s : 32 s + 32] of output j, then a
    zero bias."""
    n_in, n_out = w.shape
    n_seg = -(-n_in // XB_INPUTS)
    wp = np.pad(w, ((0, n_seg * XB_INPUTS - n_in), (0, 0)))
    segs = wp.reshape(n_seg, XB_INPUTS, n_out).transpose(2, 0, 1)
    segs = segs.reshape(-1, XB_INPUTS)
    return np.concatenate([segs, np.zeros((len(segs), 1))], axis=1
                          ).astype(np.float32)


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
            es.append(e.reshape(b, -1).sum(1))
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
            flush.append(jnp.zeros((b,), jnp.float32))
            continue
        v, _, t_last, p = states[i]
        tau = jnp.repeat(t_end * LIF_CLOCK_NS, n_out[i]) - t_last
        f = _feats(kind, jnp.zeros((v.shape[0], 3), jnp.float32), v, tau, p)
        e = jnp.where(tau > 0, head(heads[kind]["M_ES"], f, precision), 0.0)
        flush.append(e.reshape(b, -1).sum(1))
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
    ``energy``/``latency``/``events`` (T, L, B), ``flush`` (L, B).
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


@jax.tree_util.register_pytree_node_class
class _Heads(dict):
    """{kind: {head: ...}} whose family names and scales are static and
    whose arrays are traced."""

    def tree_flatten(self):
        keys, leaves, aux = [], [], []
        for kind in sorted(self):
            for name in sorted(self[kind]):
                h = self[kind][name]
                for a in sorted(h["arrays"]):
                    keys.append((kind, name, a))
                    leaves.append(h["arrays"][a])
                aux.append((kind, name, h["family"], h["scale"]))
        return leaves, (tuple(keys), tuple(aux))

    @classmethod
    def tree_unflatten(cls, meta, leaves):
        keys, aux = meta
        out = {}
        for kind, name, family, scale in aux:
            out.setdefault(kind, {})[name] = {"family": family,
                                              "scale": scale, "arrays": {}}
        for (kind, name, a), leaf in zip(keys, leaves):
            out[kind][name]["arrays"][a] = leaf
        return cls(out)
