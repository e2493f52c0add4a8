"""The surrogate heads the harness draws from the seed: one artifact per
circuit kind, the same arrays for the same seed, read alike by the program and by the plain
reference, whose head arithmetic matches the program's head by head."""

import os

import numpy as np
import pytest

from lasbench import cells, model

HARNESS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HARNESS))
SEED = 3 * 2 ** 31 + 17
CONFIGS = {c["name"]: c for c in cells.load_benchmark(ROOT)["configs"]}


def _config(name):
    return cells._read_json(os.path.join(ROOT, CONFIGS[name]["file"]))


def _arrays(path):
    with np.load(path) as z:
        return {k: np.array(z[k]) for k in z.files}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_artifact_is_drawn_from_the_seed(tmp_path, name):
    for kind, sur in model.surrogates(_config(name)).items():
        a, b, c = (_arrays(model.write_surrogate(
            sur, seed, str(tmp_path / f"{kind}.{tag}.npz")))
            for tag, seed in (("a", SEED), ("b", SEED), ("c", SEED + 1)))
        assert a.keys() == b.keys() == c.keys()
        assert all(np.array_equal(a[k], b[k]) for k in a)
        assert any(not np.array_equal(a[k], c[k]) for k in a
                   if k != "__manifest__")
        assert all(v.dtype == np.float32 for k, v in a.items()
                   if k != "__manifest__")


def _kinds(name):
    """(kind, surrogate block, id prefix) of each circuit kind: the
    configuration's name alone where it has one kind."""
    surs = model.surrogates(_config(name))
    return [(kind, sur, name if len(surs) == 1 else f"{name}-{kind}")
            for kind, sur in sorted(surs.items())]


def _head_cases():
    return [pytest.param(name, kind, head, id=f"{prefix}-{head}")
            for name in sorted(CONFIGS)
            for kind, sur, prefix in _kinds(name)
            for head in sorted(sur["heads"])]


@pytest.mark.parametrize("name,kind,head", _head_cases())
def test_program_and_reference_read_one_head_alike(tmp_path, name, kind,
                                                   head):
    import repro.lasana as lasana
    cfg = _config(name)
    sur = model.surrogates(cfg)[kind]
    path = model.write_surrogate(sur, SEED, str(tmp_path / "s.npz"))
    ref_mod = cells.reference_module(HARNESS, cfg)
    ref = ref_mod.load_artifact(path)["heads"][head]

    groups = sur["features"] + (sur["transition"]
                                if head in sur["transition_heads"] else [])
    _, mu, sd, spread = model._columns(groups)
    rng = np.random.default_rng(5)
    raw = (mu + sd * np.maximum(spread, 0.1)
           * rng.standard_normal((257, len(mu)))).astype(np.float32)
    n_in = 3 if sur["circuit"] == "lif" else 32
    n_par = 4 if sur["circuit"] == "lif" else 33
    x, p = raw[:, :n_in], raw[:, n_in + 2:n_in + 2 + n_par]
    feats = np.concatenate(
        [raw, np.asarray(ref_mod._derived(sur["circuit"], x, p))], axis=1)

    want = np.asarray(ref_mod.head(ref, feats, "highest"))
    got = np.asarray(lasana.load(path).predict(head, raw))
    scale = sur["heads"][head]["y_sd"] / sur["heads"][head]["scale"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * scale)
    assert np.std(want) > 0.05 * scale


def _fire_cases():
    return [pytest.param(name, kind, head, seed, id=f"{prefix}-{head}-{seed}")
            for name in sorted(CONFIGS)
            for kind, sur, prefix in _kinds(name)
            for head, h in sorted(sur["heads"].items())
            if "fire" in h for seed in (1328774840, 3500000014, SEED)]


@pytest.mark.parametrize("name,kind,head,seed", _fire_cases())
def test_spiking_head_fires_at_its_share_on_every_seed(tmp_path, name, kind,
                                                       head, seed):
    """Fresh operating rows, not those the shift was fitted on, read above
    the threshold at the stated share, whichever way the seed's head
    leans (the first two seeds drew heads that never fired before)."""
    cfg = _config(name)
    sur = model.surrogates(cfg)[kind]
    h = sur["heads"][head]
    ref = cells.reference_module(HARNESS, cfg).load_artifact(
        model.write_surrogate(sur, seed, str(tmp_path / "s.npz")))
    names, mu, _, _ = model._columns(sur["features"] + sur["derived"])
    rows = model._operating_rows(sur, names, mu, np.random.default_rng(7))
    y = model._forward(ref["heads"][head]["arrays"], h["family"], rows)
    share = float(np.mean(y / h["scale"] > h["fire"]["threshold"]))
    assert abs(share - h["fire"]["share"]) < 0.05, share
