"""Diehl & Cook's spiking layer with lateral inhibition: the configuration
states the published network, its cell runs through the harness on the
CPU (shrunk) and is correct against its own plain reference, which reads
the recurrent edge; with the edge left out of the program it is not. That
reference is ``lasana_net.py``'s Algorithm 1 but for its energy sums,
which it takes over every lane of a tick at once."""

import dataclasses
import gc
import json
import os
import time

import numpy as np
import pytest

from lasbench import cells, harness, model, tiny

HARNESS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HARNESS))
SEED = 2 ** 33 + 1707
CELL = "diehl-cook-6400.batch"


@pytest.fixture(autouse=True)
def _thaw():
    """A run freezes its set-up heap out of the collector; let it go."""
    yield
    gc.unfreeze()
    gc.collect()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench")), ROOT)
    yield root
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def _run(root, capsys):
    rc = harness.execute(root, CELL, SEED, 1.0, False,
                         t_start=time.perf_counter(), require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def test_configuration_states_the_published_network():
    cell = cells.resolve(ROOT, CELL)
    cfg = cell.config
    g = model.graph(cfg)
    assert g["fan_in"] == 28 * 28 == cfg["stimulus"]["image_size"] ** 2
    assert [(l["kind"], l["n_out"]) for l in g["layers"]] == [("lif", 6400)]
    assert g["edges"] == [{"src": 0, "dst": 0, "weights": {
        "recipe": "lateral_inhibition", "strength": 17.5}}]
    # 63.75 Hz at most (intensity / 4), 0.5 ms a tick, 350 ms a image
    assert cfg["stimulus"]["max_rate"] == 63.75 * 0.5e-3
    assert cell.traffic["ticks"] * 0.5 == 350
    assert cfg["reduced"] == []
    snn = cells._read_json(os.path.join(HARNESS, "configs", "snn-mnist.json"))
    assert cfg["surrogate"] == snn["surrogate"]
    assert cfg["weights"] == snn["weights"]
    ref = cells.reference_module(cell.harness_dir, cfg)
    assert ref.READS_EDGES
    assert os.path.basename(ref.__file__) == "diehl_cook.py"


def test_shrunk_cell_is_correct_against_its_reference(root, capsys):
    res = _run(root, capsys)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0


def test_shrunk_cell_without_its_edge_is_not_correct(root, capsys,
                                                     monkeypatch):
    build = model.build_spec

    def no_edges(config, weights):
        return dataclasses.replace(build(config, weights), edges=())

    monkeypatch.setattr(model, "build_spec", no_edges)
    res = _run(root, capsys)
    assert not res["correct"], res["checks"]


def test_reference_is_lasana_net_but_for_its_float64_energy_sums(root):
    cell = cells.resolve(root, CELL)
    own = cells.reference_module(cell.harness_dir, cell.config)
    shared = cells.load_module(
        os.path.join(cell.harness_dir, "reference", "lasana_net.py"),
        "lasbench_reference_")
    net = harness.build_net(cell, SEED, os.path.join(cell.harness_dir,
                                                     ".cache"), own)
    fan_in = net.layers[0]["weight"].shape[0]
    x = np.random.default_rng(0).random((40, 2, fan_in)) < 0.05
    x = x.astype(np.float32) * 1.5
    a = own.simulate(net.artifacts, net.layers, x)
    b = shared.simulate(net.artifacts, net.layers, x)
    for k in ("latency", "events"):
        np.testing.assert_array_equal(a[k], b[k])
    np.testing.assert_array_equal(a["published"][0], b["published"][0])
    assert a["events"].sum() > 0
    # the same energies, summed over all lanes of a tick at once instead
    # of lane by lane; float64 records either way
    for k in ("energy", "flush"):
        assert a[k].dtype == b[k].dtype == np.float64
        assert a[k].shape == b[k].shape[:-1] + (1,)
        np.testing.assert_allclose(a[k][..., 0], b[k].sum(-1), rtol=1e-5)
