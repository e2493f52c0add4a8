"""The accepted configurations' inputs, pinned: the program's graph, the
surrogate artifacts, the weights, the stimulus, the graph as the reference
reads it and the tick's operation count come out as the digests below,
taken when those configurations were accepted. A change to the harness
that moves any of them moves what their cells measure."""

import hashlib
import os

import numpy as np
import pytest

from lasbench import cells, flops, model

HARNESS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HARNESS))
SEED = 2 ** 33 + 12345

PINNED = {
    "snn-mnist.batch": {
        "spec_key": "71d9cb24a0d5b27cc2e868d0c51514886a3e043c",
        "artifacts": {"lif": "9c8d5a349c35867757460cafb0e7c31f"
                             "f4491b671b635efbf17230f81c1dbe3a"},
        "weights": "207767da3faed6631f591a1a4bc1de6e"
                   "ad243cc3137a5b873dc609fae1b03886",
        "reference_layers": "81d7442576e208e0bb98b1df2b33deff"
                            "31444d0cb606810b77040230b5d448d1",
        "stimulus": "fca55ed91fc3ce26bfc0c170527195ef"
                    "d417ef864bdcfb6b10261b815fc12088",
        "tick_flops": 12290318336.0,
    },
    "xbar-mnist.batch": {
        "spec_key": "7defebd1e2375c3b2948480155b63e4d08a6bb39",
        "artifacts": {"crossbar": "2d1832bba3b00c6c685a439c1ba96d98"
                                  "0b039399115496689aba2c60ba569266"},
        "weights": "bf5db7aeeebcf6fe36c89d1717451cc6"
                   "9792fa9879b80e35b56bf93e12666e42",
        "reference_layers": "ee154ad33f13aec505259ff1799789a3"
                            "9eaf1db6657131acbe425edc7abe9e57",
        "stimulus": "39d7748d78fff7b120abc322fb47b764"
                    "5134152f59df2cffd27d484948b94db8",
        "tick_flops": 18426655200.0,
    },
}


def _sha(chunks) -> str:
    h = hashlib.sha256()
    for c in chunks:
        h.update(c)
    return h.hexdigest()


def _bytes(a) -> list:
    a = np.ascontiguousarray(np.asarray(a))
    return [a.dtype.str.encode(), repr(a.shape).encode(), a.tobytes()]


def _npz(path) -> str:
    """The artifact's members, names and arrays (a zip file's own bytes
    hold the time it was written)."""
    with np.load(path) as z:
        return _sha([p for k in sorted(z.files)
                     for p in [k.encode()] + _bytes(z[k])])


def _digests(workload, tmp_path) -> dict:
    import jax
    from repro.serve.buckets import spec_content_key
    cell = cells.resolve(ROOT, workload)
    cfg = cell.config
    paths = {kind: model.write_surrogate(sur, SEED,
                                         str(tmp_path / f"{kind}.npz"))
             for kind, sur in model.surrogates(cfg).items()}
    weights = model.make_weights(cfg)
    host = jax.device_get(weights)
    layers = model.reference_layers(cfg, host)
    ref = cells.reference_module(cell.harness_dir, cfg)
    arts = {k: ref.load_artifact(p) for k, p in paths.items()}
    return {
        "spec_key": spec_content_key(model.build_spec(cfg, weights)),
        "artifacts": {k: _npz(p) for k, p in paths.items()},
        "weights": _sha([p for w in host[0] + host[1] for p in _bytes(w)]),
        "reference_layers": _sha([
            p for l in layers for p in [l["kind"].encode()]
            + _bytes(l["weight"]) + _bytes(l["knobs"])]),
        "stimulus": _sha(_bytes(model.stimulus(cfg, 10, 8, SEED, "pool",
                                               0))),
        "tick_flops": flops.tick_flops(layers, cell.traffic["batch"], arts),
    }


@pytest.mark.parametrize("workload", sorted(PINNED))
def test_accepted_configuration_inputs_are_pinned(workload, tmp_path):
    got = _digests(workload, tmp_path)
    assert got == PINNED[workload]
