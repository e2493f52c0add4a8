"""A whole run of each cell, small and on the CPU, with the look for a chip
skipped: sound, it is correct; with the timed path broken underneath, the
comparison with the plain reference turns ``correct`` false."""

import json
import gc
import os
import time

import numpy as np
import pytest

from lasbench import harness, tiny

HARNESS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HARNESS))
SEED = 98765432109
BATCH = ("snn-mnist.batch", "xbar-mnist.batch")


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


def _run(root, workload, capsys):
    rc = harness.execute(root, workload, SEED, 1.0, False,
                         t_start=time.perf_counter(), require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


@pytest.mark.parametrize("workload", BATCH)
def test_sound_run_is_correct(root, workload, capsys):
    res = _run(root, workload, capsys)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


def _state_unchanged(monkeypatch):
    import repro.core.network as network
    step = network.lasana_step

    def broken(surrogate, state, *a, **kw):
        _, e, l, o = step(surrogate, state, *a, **kw)
        return state, e, l, o

    monkeypatch.setattr(network, "lasana_step", broken)


def _half_batch(monkeypatch):
    """Simulate half of the lanes and stand them in for the whole."""
    from repro.core.network import NetworkEngine
    run = NetworkEngine._run

    def broken(self, x, *, surrogates=None):
        half = x.shape[1] // 2
        r = run(self, x[:, :half], surrogates=surrogates)
        twice = lambda a: np.concatenate([a, a], axis=1)   # noqa: E731
        r.outputs = np.concatenate([r.outputs, r.outputs])
        r.out_spikes = None if r.out_spikes is None else twice(r.out_spikes)
        r.layer_spikes = [twice(s) for s in r.layer_spikes]
        r.energy, r.events = 2 * r.energy, 2 * r.events
        r.flush_energy = 2 * r.flush_energy
        return r

    monkeypatch.setattr(NetworkEngine, "_run", broken)


def _answer_altered(monkeypatch):
    """One lane's first-layer outputs altered where they are produced."""
    from repro.core.network import NetworkEngine
    run = NetworkEngine._run

    def broken(self, x, *, surrogates=None):
        r = run(self, x, surrogates=surrogates)
        s = np.array(r.layer_spikes[0])
        s[:, 0] = 1.5 - s[:, 0] if r.circuits[0] == "lif" else s[:, 0] + 1.0
        r.layer_spikes[0] = s
        return r

    monkeypatch.setattr(NetworkEngine, "_run", broken)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
@pytest.mark.parametrize("workload", BATCH)
def test_broken_batch_path_is_not_correct(root, workload, fault, capsys,
                                          monkeypatch):
    fault(monkeypatch)
    res = _run(root, workload, capsys)
    assert not res["correct"], res["checks"]
