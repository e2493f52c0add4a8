"""A configuration that states its circuit graph: mixed circuit kinds, a
recurrent edge, a surrogate per kind and a traffic driver of its own, all
from new files. Built through the program's public builders, it runs
through the harness on the CPU and is correct against a plain reference
that reads its edge; with the edge left out of the program, it is not."""

import dataclasses
import gc
import json
import os
import time

import numpy as np
import pytest

from lasbench import cells, flops, harness, model, tiny

HARNESS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HARNESS))
SEED = 2 ** 32 + 1601


@pytest.fixture(autouse=True)
def _thaw():
    """A run freezes its set-up heap out of the collector; let it go."""
    yield
    gc.unfreeze()
    gc.collect()


@pytest.fixture
def root(tmp_path):
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    root = tiny.make_root(str(tmp_path / "bench"), ROOT)
    tiny.add_graph_cell(root)
    yield root
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def _run(root, capsys):
    rc = harness.execute(root, tiny.GRAPH_CELL, SEED, 1.0, False,
                         t_start=time.perf_counter(), require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, out


def test_graph_cell_is_correct_against_a_reference_that_reads_edges(
        root, capsys):
    rc, out = _run(root, capsys)
    assert rc == 0
    res = json.loads(out[-1])
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0


def test_graph_cell_without_its_edge_is_not_correct(root, capsys,
                                                    monkeypatch):
    build = model.build_spec

    def no_edges(config, weights):
        return dataclasses.replace(build(config, weights), edges=())

    monkeypatch.setattr(model, "build_spec", no_edges)
    rc, out = _run(root, capsys)
    assert rc == 0
    res = json.loads(out[-1])
    assert not res["correct"], res["checks"]


def test_reference_that_does_not_read_edges_is_refused(root, capsys):
    path = os.path.join(root, "benchmarks", "chip", tiny.GRAPH_REFERENCE)
    with open(path) as f:
        source = f.read()
    assert "READS_EDGES = True" in source
    with open(path, "w") as f:
        f.write(source.replace("READS_EDGES = True", "READS_EDGES = False"))
    rc, out = _run(root, capsys)
    assert rc != 0
    assert out == []


def _chain_as_graph(cfg):
    """A chain-form configuration written as ``graph_spec``."""
    g = model.graph(cfg)
    net = {"spec": "graph_spec", "fan_in": g["fan_in"],
           "layers": g["layers"]}
    if "spike_amp" in g:
        net["spike_amp"] = g["spike_amp"]
    return dict(cfg, network=net)


@pytest.mark.parametrize("name", ["snn-mnist", "xbar-mnist"])
def test_chain_forms_are_graphs(name):
    import jax
    from repro.serve.buckets import spec_content_key
    cfg = cells._read_json(os.path.join(HARNESS, "configs", name + ".json"))
    as_graph = _chain_as_graph(cfg)
    w, wg = model.make_weights(cfg), model.make_weights(as_graph)
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.device_get(w[0]), jax.device_get(wg[0])))
    assert spec_content_key(model.build_spec(cfg, w)) == \
        spec_content_key(model.build_spec(as_graph, wg))


def _graph_config(edges):
    return {"name": "g", "weights": {"recipe": "he_normal", "gain": 1.0,
                                     "seed": 11},
            "network": {"spec": "graph_spec", "fan_in": 64, "layers": [
                {"kind": "crossbar", "n_out": 24, "seg_width": 32,
                 "adc_bits": 8, "activation": "tanh",
                 "weights": {"recipe": "ternary", "threshold_sigma": 0.5}},
                {"kind": "lif", "n_out": 10,
                 "lif_knobs": [0.58, 0.5, 0.5, 0.5]}], "edges": edges}}


def test_edge_weights_and_their_reference_form():
    import jax
    cfg = _graph_config([
        {"src": 1, "dst": 1, "weights": {"recipe": "lateral_inhibition",
                                         "strength": 0.4}},
        {"src": 1, "dst": 0, "weights": {"recipe": "he_normal"}}])
    layer_w, edge_w = jax.device_get(model.make_weights(cfg))
    assert [w.shape for w in layer_w] == [(64, 24), (24, 10)]
    assert set(np.unique(layer_w[0])) <= {-1.0, 0.0, 1.0}
    np.testing.assert_array_equal(
        edge_w[0], -0.4 * (1.0 - np.eye(10, dtype=np.float32)))
    assert edge_w[1].shape == (10, 64)           # into the crossbar's volts
    again = jax.device_get(model.make_weights(cfg))
    assert np.array_equal(edge_w[1], again[1][1])
    # the layers draw as they do without edges
    plain = jax.device_get(model.make_weights(_graph_config([])))
    assert all(np.array_equal(a, b) for a, b in zip(layer_w, plain[0]))

    spec = model.build_spec(cfg, model.make_weights(cfg))
    assert spec.circuits == ("crossbar", "lif")
    assert [(e.src, e.dst) for e in spec.edges] == [(1, 1), (1, 0)]
    layers = model.reference_layers(cfg, (layer_w, edge_w))
    assert [l["kind"] for l in layers] == ["crossbar", "lif"]
    assert [e["src"] for e in layers[0]["edges_in"]] == [1]
    assert [e["src"] for e in layers[1]["edges_in"]] == [1]
    assert "edges_in" not in model.reference_layers(
        _graph_config([]), plain)[0]
    # each edge's drive is counted: (10 x 64) and (10 x 10) a lane
    arts = {"crossbar": {"heads": {h: {"family": "mean", "arrays": {}}
                                   for h in flops.TICK_HEADS}},
            "lif": {"heads": {h: {"family": "mean", "arrays": {}}
                              for h in flops.TICK_HEADS}}}
    b = 3
    assert flops.tick_flops(layers, b, arts) == \
        2 * b * (64 * 24 + 10 * 64 + 24 * 10 + 10 * 10)


def test_one_surrogate_per_kind(tmp_path):
    snn = cells._read_json(os.path.join(HARNESS, "configs", "snn-mnist.json"))
    xb = cells._read_json(os.path.join(HARNESS, "configs", "xbar-mnist.json"))
    cfg = {"surrogates": {"lif": snn["surrogate"],
                          "crossbar": dict(xb["surrogate"])}}
    del cfg["surrogates"]["crossbar"]["circuit"]
    surs = model.surrogates(cfg)
    assert {k: s["circuit"] for k, s in surs.items()} == \
        {"lif": "lif", "crossbar": "crossbar"}
    # a kind's heads are those its single-surrogate configuration draws
    a = model.write_surrogate(surs["lif"], SEED, str(tmp_path / "a.npz"))
    b = model.write_surrogate(model.surrogates(snn)["lif"], SEED,
                              str(tmp_path / "b.npz"))
    with np.load(a) as za, np.load(b) as zb:
        assert za.files == zb.files
        assert all(np.array_equal(za[k], zb[k]) for k in za.files)
    with pytest.raises(ValueError):
        model.surrogates({"surrogates": {"lif": xb["surrogate"]}})
