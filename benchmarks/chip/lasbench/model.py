"""A configuration made runnable: surrogates, weights, graph and stimulus.

The surrogate heads are drawn from ``--seed`` here, at the families,
widths, feature scalings and output ranges the configuration states, and
written once per run in the program's published format, one artifact per
circuit kind (``.npz``: arrays keyed ``{head}/{array}`` and a JSON
``__manifest__``). The program loads each file with ``lasana.load`` and
the plain reference reads the same file with numpy: nothing the
reference reads is made by the program. A spiking output head is then
shifted so that every seed's neurons fire at the configuration's share
of the rows the network feeds them: a seed changes the heads, not the
amount of work or what the comparison can see. The network's graph
(``graph``) and weights are the configuration's own, the weights drawn
from its ``weights.seed``; the stimulus is drawn from ``--seed``.
"""

from __future__ import annotations

import json
import os

import numpy as np

from lasbench.data import make_digits, poisson_encode, sub_seed

ARTIFACT_FORMAT = 1          # the artifact format version written here
BIAS_SD = 0.1                # hidden biases of a drawn MLP head
CALIBRATION_ROWS = 4096      # feature rows that fix a head's output range


def _columns(groups: list) -> tuple:
    """``[[name, count, mu, sd(, spread)], ...]`` -> (names, mu, sd,
    spread) per column. ``spread`` is how far the column varies in
    calibration, in units of ``sd`` (default 1). A column with ``sd`` 0
    holds ``mu`` in every circuit of the network: it is stored with scale
    1 and does not vary in calibration."""
    names, mu, sd, spread = [], [], [], []
    for name, count, m, s, *rest in groups:
        names += [name] if count == 1 else [f"{name}{i}" for i in range(count)]
        mu += [m] * count
        sd += [s if s > 0 else 1.0] * count
        spread += [(rest[0] if rest else 1.0) if s > 0 else 0.0] * count
    return (names, np.asarray(mu, np.float32), np.asarray(sd, np.float32),
            np.asarray(spread, np.float64))


def _head_arrays(head: dict, x_mu, x_sd, spread, rng) -> dict:
    """One predictor's arrays: He-scaled normal layers, then the output
    set to the stated mean and spread over calibration rows (standardized
    features, normal with each column's spread), so that every seed's
    head covers the same range on the rows the network feeds it."""
    f = len(x_mu)
    calib = rng.standard_normal((CALIBRATION_ROWS, f)) * spread
    if head["family"] == "linear":
        w = rng.standard_normal(f)
        z = calib @ w
        w = w * (head["y_sd"] / z.std())
        bias = head["y_mu"] - z.mean() * head["y_sd"] / z.std()
        return {"w": np.append(w, bias).astype(np.float32),
                "mu": x_mu, "sd": x_sd}
    if head["family"] != "mlp":
        raise ValueError(f"no recipe for a {head['family']!r} head")
    dims = [f] + list(head["hidden"]) + [1]
    out, h = {}, calib
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        w = rng.standard_normal((a, b)) * np.sqrt(2.0 / a)
        bias = rng.standard_normal(b) * BIAS_SD
        z = h @ w + bias
        if i == len(dims) - 2:             # the output: unit spread, mean 0
            w, bias = w / z.std(), (bias - z.mean()) / z.std()
        h = np.maximum(z, 0.0)
        out[f"w{i}"] = w.astype(np.float32)
        out[f"b{i}"] = bias.astype(np.float32)
    out.update(x_mu=x_mu, x_sd=x_sd,
               y_mu=np.float32([head["y_mu"]]), y_sd=np.float32([head["y_sd"]]))
    return out


def _forward(arrays: dict, family: str, x) -> np.ndarray:
    """A drawn head's output on raw feature rows, before its scale."""
    if family == "linear":
        return ((x - arrays["mu"]) / arrays["sd"]) @ arrays["w"][:-1] \
            + arrays["w"][-1]
    z = (x - arrays["x_mu"]) / arrays["x_sd"]
    n = sum(1 for k in arrays if k[0] == "w")
    for i in range(n):
        z = z @ arrays[f"w{i}"] + arrays[f"b{i}"]
        if i < n - 1:
            z = np.maximum(z, 0.0)
    return z[:, 0] * arrays["y_sd"][0] + arrays["y_mu"][0]


def _operating_rows(sur: dict, names: list, mu, rng) -> np.ndarray:
    """Feature rows as the network presents them to a head: each column
    that ``operating.rows`` names ``[name, mean, sd(, lo, hi)]`` drawn
    normal and clipped, every other base column at its mean, the derived
    column the product of ``operating.derived``'s columns times its
    scale."""
    op = sur["operating"]
    x = np.tile(mu.astype(np.float64), (CALIBRATION_ROWS, 1))
    for name, m, s, *bounds in op["rows"]:
        col = m + s * rng.standard_normal(CALIBRATION_ROWS)
        x[:, names.index(name)] = np.clip(col, *bounds) if bounds else col
    prod = np.prod([x[:, names.index(n)] for n in op["derived"]["product"]],
                   axis=0)
    x[:, names.index(sur["derived"][0][0])] = prod * op["derived"]["scale"]
    return x


def _fire(arrays: dict, head: dict, rows) -> None:
    """Shift the head's output so that ``fire.share`` of the operating
    rows read above ``fire.threshold``: every seed's neurons spike at one
    rate, whatever way its drawn head leans on those rows."""
    fire = head["fire"]
    y = _forward(arrays, head["family"], rows) / head["scale"]
    shift = (fire["threshold"] - np.quantile(y, 1.0 - fire["share"])) \
        * head["scale"]
    if head["family"] == "linear":
        arrays["w"][-1] += np.float32(shift)
    else:
        arrays["y_mu"] = (arrays["y_mu"] + shift).astype(np.float32)


def surrogates(config: dict) -> dict:
    """``{kind: surrogate block}`` of the configuration: its
    ``surrogates`` object, one block per circuit kind, or its single
    ``surrogate`` block, which names its ``circuit``."""
    if "surrogates" not in config:
        sur = config["surrogate"]
        return {sur["circuit"]: sur}
    out = {}
    for kind, sur in config["surrogates"].items():
        if sur.get("circuit", kind) != kind:
            raise ValueError(f"surrogate under {kind!r} names circuit "
                             f"{sur['circuit']!r}")
        out[kind] = dict(sur, circuit=kind)
    return out


def write_surrogate(sur: dict, seed: int, path: str) -> str:
    """Draw one circuit kind's surrogate heads (a block of
    ``surrogates(config)``) from ``seed`` and write them to ``path``
    (returned). Each head's draw is seeded by the seed, the circuit and
    the head's name. Heads named in ``transition`` see the transition
    columns too; the derived column comes last. A head with ``fire`` is
    then shifted to its spike share on the operating rows (``_fire``)."""
    base, derived = sur["features"], sur["derived"]
    arrays, families, scales = {}, {}, {}
    for name in sorted(sur["heads"]):
        head = sur["heads"][name]
        groups = base + (sur["transition"] if name in sur["transition_heads"]
                         else []) + derived
        names, x_mu, x_sd, spread = _columns(groups)
        rng = np.random.default_rng(sub_seed(seed, "surrogate",
                                             sur["circuit"], name))
        drawn = _head_arrays(head, x_mu, x_sd, spread, rng)
        if "fire" in head:
            rows = _operating_rows(sur, names, x_mu, np.random.default_rng(
                sub_seed(seed, "operating", sur["circuit"], name)))
            _fire(drawn, head, rows)
        for k, v in drawn.items():
            arrays[f"{name}/{k}"] = v
        families[name] = head["family"]
        scales[name] = float(head["scale"])
    manifest = {"format_version": ARTIFACT_FORMAT, "circuit": sur["circuit"],
                "families": families, "scales": scales,
                "features": _columns(base)[0], "fit_info": None}
    arrays["__manifest__"] = np.frombuffer(json.dumps(manifest).encode(),
                                           dtype=np.uint8)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path[:-4] + ".partial.npz"
    np.savez(tmp, **arrays)
    os.replace(tmp, path)
    return path


def graph(config: dict) -> dict:
    """The configuration's circuit graph in one form: ``{"fan_in",
    "layers": [{"kind", "n_out", ...}], "edges": [{"src", "dst",
    "weights"}]}``, and ``spike_amp`` where the configuration states it.

    ``network.spec`` is ``graph_spec`` (the layers as objects: a LIF
    layer gives ``lif_knobs``; a crossbar layer ``seg_width``,
    ``adc_bits`` and ``activation``; either may give its own ``weights``
    recipe), or one of the chain forms ``snn_spec`` and
    ``crossbar_mlp_spec`` (the layers as widths, the fan-in first, every
    layer of one kind with the network's settings)."""
    net = config["network"]
    spec = net["spec"]
    if spec == "graph_spec":
        out = {"fan_in": net["fan_in"], "layers": net["layers"],
               "edges": net.get("edges", [])}
    elif spec == "snn_spec":
        out = {"fan_in": net["layers"][0], "edges": [],
               "layers": [{"kind": "lif", "n_out": w,
                           "lif_knobs": net["lif_knobs"]}
                          for w in net["layers"][1:]]}
    elif spec == "crossbar_mlp_spec":
        out = {"fan_in": net["layers"][0], "edges": [],
               "layers": [{"kind": "crossbar", "n_out": w,
                           "seg_width": net["seg_width"],
                           "adc_bits": net["adc_bits"],
                           "activation": net["activation"]}
                          for w in net["layers"][1:]]}
    else:
        raise ValueError(f"unknown network spec {spec}")
    if "spike_amp" in net:
        out["spike_amp"] = net["spike_amp"]
    return out


def _edge_shape(g: dict, edge: dict) -> tuple:
    """(n_out of the source, the destination's drive width): its n_out
    for a LIF destination, its fan-in for a crossbar one."""
    layers = g["layers"]
    dst = edge["dst"]
    if layers[dst]["kind"] == "lif":
        width = layers[dst]["n_out"]
    else:
        width = g["fan_in"] if dst == 0 else layers[dst - 1]["n_out"]
    return layers[edge["src"]]["n_out"], width


def make_weights(config: dict) -> tuple:
    """``(layer weights, edge weights)``, made on the device in one jitted
    call from the configuration's weight seed. Layer ``i`` draws at
    ``fold_in(key, i)`` with its own ``weights`` recipe or the
    configuration's; a drawn edge ``j`` at ``fold_in(key, n_layers +
    j)``. ``lateral_inhibition`` (``strength`` c) is ``-c * (1 - I)``."""
    import jax
    import jax.numpy as jnp
    g = graph(config)
    common = config["weights"]
    widths = [g["fan_in"]] + [l["n_out"] for l in g["layers"]]
    layer_recipes = [dict(common, **l.get("weights", {}))
                     for l in g["layers"]]
    edge_recipes = [dict(common, **e["weights"]) for e in g["edges"]]
    edge_shapes = [_edge_shape(g, e) for e in g["edges"]]

    def draw(key, i, shape, recipe):
        a = shape[0]
        w = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32) * (2.0 / a) ** 0.5
        if recipe["recipe"] == "he_normal":
            return w * recipe["gain"]
        if recipe["recipe"] == "ternary":
            thr = recipe["threshold_sigma"] * jnp.std(w)
            return jnp.sign(w) * (jnp.abs(w) > thr)
        raise ValueError(f"unknown weight recipe {recipe['recipe']}")

    @jax.jit
    def make(key):
        layers = [draw(key, i, (a, b), r) for i, (a, b, r) in
                  enumerate(zip(widths[:-1], widths[1:], layer_recipes))]
        edges = []
        for j, (shape, r) in enumerate(zip(edge_shapes, edge_recipes)):
            if r["recipe"] == "lateral_inhibition":
                if shape[0] != shape[1]:
                    raise ValueError(f"lateral inhibition needs a square "
                                     f"edge, not {shape}")
                edges.append(-r["strength"] * (
                    1.0 - jnp.eye(shape[0], dtype=jnp.float32)))
            else:
                edges.append(draw(key, len(layers) + j, shape, r))
        return layers, edges

    return make(jax.random.key(int(common["seed"])))


def build_spec(config: dict, weights: tuple):
    """The program's ``NetworkSpec`` of the graph, through its public
    builders."""
    from repro.core.network import (crossbar_layer, graph_spec, lif_layer,
                                    recurrent_edge)
    g = graph(config)
    layer_w, edge_w = weights
    layers = []
    for layer, w in zip(g["layers"], layer_w):
        if layer["kind"] == "lif":
            layers.append(lif_layer(w, np.asarray(layer["lif_knobs"],
                                                  np.float32)))
        elif layer["kind"] == "crossbar":
            layers.append(crossbar_layer(
                w, seg_width=layer["seg_width"], adc_bits=layer["adc_bits"],
                activation=layer["activation"]))
        else:
            raise ValueError(f"unknown layer kind {layer['kind']}")
    edges = [recurrent_edge(e["src"], e["dst"], w)
             for e, w in zip(g["edges"], edge_w)]
    amp = {"spike_amp": g["spike_amp"]} if "spike_amp" in g else {}
    return graph_spec(layers, edges=edges, **amp)


def reference_layers(config: dict, weights: tuple) -> list:
    """The graph as the plain reference reads it (host copies): each
    layer's kind, weight, LIF knobs and crossbar activation; where the
    configuration has edges, each layer's incoming ones as ``edges_in``
    (``[{"src", "weight"}]``)."""
    g = graph(config)
    layer_w, edge_w = weights
    out = []
    for i, (layer, w) in enumerate(zip(g["layers"], layer_w)):
        ref = {"kind": layer["kind"], "weight": np.asarray(w),
               "knobs": np.asarray(layer.get("lif_knobs", ()), np.float32)}
        if layer["kind"] == "crossbar":
            ref["activation"] = layer["activation"]
        if g["edges"]:
            ref["edges_in"] = [{"src": e["src"], "weight": np.asarray(we)}
                               for e, we in zip(g["edges"], edge_w)
                               if e["dst"] == i]
        out.append(ref)
    return out


def stimulus(config: dict, ticks: int, batch: int, seed: int, *tags
             ) -> np.ndarray:
    """(ticks, batch, fan_in) host stimulus of the configuration's
    encoder, drawn from ``seed`` and ``tags``."""
    enc = config["stimulus"]
    if enc["encoder"] == "poisson_image":
        # one image per lane, rate-coded over every tick
        imgs, _ = make_digits(batch, size=enc["image_size"],
                              seed=sub_seed(seed, "digits", *tags))
        spikes = poisson_encode(imgs, ticks, max_rate=enc["max_rate"],
                                seed=sub_seed(seed, "spikes", *tags))
        return spikes * np.float32(enc["amplitude"])
    if enc["encoder"] == "image_per_tick":
        # a new image per lane on every tick: one combinational wave each
        imgs, _ = make_digits(ticks * batch, size=enc["image_size"],
                              seed=sub_seed(seed, "digits", *tags))
        x = imgs.reshape(ticks, batch, -1) * np.float32(enc["scale"])
        return x + np.float32(enc["offset"])
    raise ValueError(f"unknown stimulus encoder {enc['encoder']}")
