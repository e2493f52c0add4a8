"""Fused inference hot path (ISSUE-5): ``Surrogate.predict_heads`` and the
fused ``lasana_step`` must reproduce the per-``predict``-call formulation.

Documented tolerance: stacked same-family head evaluation batches several
heads into one einsum, which reorders float reductions — fused results may
differ from per-call results by a few ULPs (observed <= ~1e-6 relative on
CPU XLA). Single-head groups reuse the exact per-head functions and are
asserted BIT-identical. Network-level: spike decisions threshold far from
the ULP scale, so fused and unfused runs must agree exactly on discrete
records (outputs, events) and to rtol=1e-5 on energy/latency.

Determinism caveat (why exact asserts are safe here): everything is
seeded and jax is pinned, and the workloads sit away from the two
discontinuities — nearest-neighbor ties in stacked table heads and
spike thresholds within ULPs of o_hat — where the reassociation could
amplify into a whole-entry / whole-spike difference. If these asserts
trip after a jax upgrade, check those edges before suspecting the fused
implementation (see docs/architecture.md, "Inference hot path").
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.circuits import LIFNeuron
from repro.core.surrogate import (ALG1_HEADS, FORMAT_VERSION, Manifest,
                                  Surrogate, _augment)
from repro.core.wrapper import (_features, _splice_transition, init_state,
                                lasana_step, stale_circuits)

# documented fused-vs-percall tolerance (see module docstring)
RTOL = 1e-5
ATOL = 1e-7

N_IN, N_P = 3, 4                    # lif raw interface dims
F_RAW = N_IN + 1 + 1 + N_P          # x, v, tau, params
F_AUG = F_RAW + 1                   # + lif derived drive column
F_TR = F_AUG + 2                    # + o_prev, o_new (augmented order)


def _mk_mlp(rng, f, hidden=(24, 12)):
    dims = (f, *hidden, 1)
    a = {}
    for i in range(len(dims) - 1):
        a[f"w{i}"] = rng.normal(size=(dims[i], dims[i + 1])).astype(np.float32)
        a[f"b{i}"] = rng.normal(size=(dims[i + 1],)).astype(np.float32)
    a.update(x_mu=rng.normal(size=(f,)).astype(np.float32),
             x_sd=(1 + rng.random(f)).astype(np.float32),
             y_mu=rng.normal(size=(1,)).astype(np.float32),
             y_sd=(1 + rng.random(1)).astype(np.float32))
    return a


def _mk_linear(rng, f):
    return {"w": rng.normal(size=(f + 1,)).astype(np.float32),
            "mu": rng.normal(size=(f,)).astype(np.float32),
            "sd": (1 + rng.random(f)).astype(np.float32)}


def _mk_table(rng, f, rows=32):
    return {"tx": rng.normal(size=(rows, f)).astype(np.float32),
            "ty": rng.normal(size=(rows,)).astype(np.float32),
            "mu": rng.normal(size=(f,)).astype(np.float32),
            "sd": (1 + rng.random(f)).astype(np.float32)}


def _mk_gbdt(rng, f, n_trees=3, depth=2):
    nodes, leaves = 2 ** depth - 1, 2 ** depth
    return {"feat": rng.integers(0, f, (n_trees, nodes)).astype(np.int32),
            "thr": rng.normal(size=(n_trees, nodes)).astype(np.float32),
            "leaf": rng.normal(size=(n_trees, leaves)).astype(np.float32),
            "base": np.float32(rng.normal())}


def _mk_mean(rng, f):
    return {"mu": np.float32(rng.normal())}


MAKERS = {"mlp": _mk_mlp, "linear": _mk_linear, "table": _mk_table,
          "gbdt": _mk_gbdt, "mean": _mk_mean}

# transition-aware heads see the two extra output columns
_HEAD_DIMS = {"M_O": F_AUG, "M_V": F_AUG, "M_ES": F_AUG,
              "M_ED": F_TR, "M_L": F_TR}


def _make_surrogate(family_per_predictor: dict, seed=0) -> Surrogate:
    """Synthetic lif Surrogate — inference parity needs arrays, not MSE."""
    rng = np.random.default_rng(seed)
    params = {p: {k: jnp.asarray(v) for k, v in
                  MAKERS[fam](rng, _HEAD_DIMS[p]).items()}
              for p, fam in family_per_predictor.items()}
    manifest = Manifest(
        circuit="lif", format_version=FORMAT_VERSION,
        families=tuple(sorted(family_per_predictor.items())),
        scales=tuple(sorted((p, 1e15 if p.startswith("M_E") else 1.0)
                            for p in family_per_predictor)),
        features=())
    return Surrogate(manifest=manifest, params=params)


def _variant_feats(seed=1, n=41):
    """Raw (un-augmented) idle/act/tr matrices with consistent columns."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, N_IN)).astype(np.float32)
    v = rng.normal(size=(n,)).astype(np.float32)
    p = rng.normal(size=(n, N_P)).astype(np.float32)
    tau_i = rng.random(n).astype(np.float32) * 40
    o_prev = rng.normal(size=(n,)).astype(np.float32)
    o_new = rng.normal(size=(n,)).astype(np.float32)
    idle = _features(np.zeros_like(x), v, tau_i, p)
    act = _features(x, v, np.full((n,), 5.0, np.float32), p)
    tr = _features(x, v, np.full((n,), 5.0, np.float32), p,
                   o_prev=o_prev, o_new=o_new)
    return jnp.asarray(idle), jnp.asarray(act), jnp.asarray(tr)


ALL_FAMILY_ASSIGNMENTS = [
    # every predictor on one family each — covers all five families, and
    # every stacked group has >= 2 members somewhere across the variants
    {"M_O": "mlp", "M_V": "mlp", "M_ES": "mlp", "M_ED": "mlp", "M_L": "mlp"},
    {"M_O": "linear", "M_V": "linear", "M_ES": "linear",
     "M_ED": "linear", "M_L": "linear"},
    {"M_O": "table", "M_V": "table", "M_ES": "table",
     "M_ED": "table", "M_L": "table"},
    {"M_O": "mean", "M_V": "mean", "M_ES": "mean",
     "M_ED": "mean", "M_L": "mean"},
    {"M_O": "gbdt", "M_V": "gbdt", "M_ES": "gbdt",
     "M_ED": "gbdt", "M_L": "gbdt"},
    # mixed: one of each family in a single surrogate
    {"M_O": "mlp", "M_V": "linear", "M_ES": "table",
     "M_ED": "gbdt", "M_L": "mean"},
]


@pytest.mark.parametrize("fams", ALL_FAMILY_ASSIGNMENTS,
                         ids=lambda f: "-".join(sorted(set(f.values()))))
def test_predict_heads_matches_predict_all_families(fams):
    """Fused output == per-call predict for every head on every variant
    (documented tolerance; gbdt/mean and single-head groups bit-exact)."""
    sur = _make_surrogate(fams)
    fi, fa, ftr = _variant_feats()
    out = sur.predict_heads(fi, fa, ftr)
    assert set(out) == {"idle", "act", "tr"}
    for variant, mat in (("idle", fi), ("act", fa), ("tr", ftr)):
        assert set(out[variant]) == set(ALG1_HEADS[variant])
        for pname in out[variant]:
            ref = np.asarray(sur.predict(pname, mat))
            got = np.asarray(out[variant][pname])
            np.testing.assert_allclose(
                got, ref, rtol=RTOL, atol=ATOL,
                err_msg=f"{variant}/{pname} ({fams[pname]})")


def test_predict_heads_single_head_groups_bit_identical():
    """A group of one bypasses stacking entirely -> bit-identical."""
    sur = _make_surrogate(ALL_FAMILY_ASSIGNMENTS[-1])   # one family each
    fi, fa, ftr = _variant_feats(seed=3)
    out = sur.predict_heads(fi, fa, ftr)
    for variant, mat in (("idle", fi), ("act", fa), ("tr", ftr)):
        for pname in out[variant]:
            np.testing.assert_array_equal(
                np.asarray(out[variant][pname]),
                np.asarray(sur.predict(pname, mat)),
                err_msg=f"{variant}/{pname}")


def test_predict_heads_annotation_schedule():
    """The annotation-mode subset (no M_O/M_V) evaluates exactly the
    requested heads — nothing more."""
    sur = _make_surrogate(ALL_FAMILY_ASSIGNMENTS[0])
    fi, fa, ftr = _variant_feats(seed=4)
    out = sur.predict_heads(
        fi, fa, ftr,
        heads={"idle": ("M_ES",), "act": ("M_ES",), "tr": ("M_ED", "M_L")})
    assert set(out["idle"]) == {"M_ES"}
    assert set(out["act"]) == {"M_ES"}
    assert set(out["tr"]) == {"M_ED", "M_L"}
    np.testing.assert_allclose(np.asarray(out["act"]["M_ES"]),
                               np.asarray(sur.predict("M_ES", fa)),
                               rtol=RTOL, atol=ATOL)


def test_predict_heads_augmented_passthrough_and_splice():
    """Pre-augmented matrices skip re-augmentation, and the wrapper's
    transition column splice equals building + augmenting from scratch."""
    sur = _make_surrogate(ALL_FAMILY_ASSIGNMENTS[0])
    fi, fa, ftr = _variant_feats(seed=5)
    aug_act = _augment("lif", fa)
    o_prev, o_new = ftr[:, F_RAW], ftr[:, F_RAW + 1]
    spliced = _splice_transition(aug_act, F_RAW, o_prev, o_new)
    np.testing.assert_array_equal(np.asarray(spliced),
                                  np.asarray(_augment("lif", ftr)))
    a = sur.predict_heads(feats_act=fa, heads={"act": ("M_O",)})
    b = sur.predict_heads(feats_act=aug_act, heads={"act": ("M_O",)},
                          augmented=True)
    np.testing.assert_array_equal(np.asarray(a["act"]["M_O"]),
                                  np.asarray(b["act"]["M_O"]))


def test_predict_heads_misuse_raises():
    sur = _make_surrogate(ALL_FAMILY_ASSIGNMENTS[0])
    fi, fa, _ = _variant_feats(seed=6)
    with pytest.raises(ValueError, match="at least one"):
        sur.predict_heads()
    with pytest.raises(ValueError, match="unknown predictor"):
        sur.predict_heads(feats_idle=fi, heads={"idle": ("M_NOPE",)})
    with pytest.raises(ValueError, match="no matching feature"):
        sur.predict_heads(feats_idle=fi, heads={"act": ("M_O",)})


def _event_masks(stimulus: str, n: int, ticks: int = 5):
    """(ticks, n) event masks: every circuit has an event on every tick
    ("every_tick"), or circuits are eventless on chosen ticks ("sparse")
    and go stale on their next event."""
    masks = np.ones((ticks, n), bool)
    if stimulus == "sparse":
        masks[1, :n // 3] = False          # a third of the circuits idle
        masks[3] = False                   # a silent tick
    return masks


# ticks on which a circuit is stale, from a state with stale circuits:
# the fused tick runs its idle stage on these only
IDLE_TICKS = {"every_tick": [True, False, False, False, False],
              "sparse": [True, False, True, False, True]}


@pytest.mark.parametrize("stimulus", list(IDLE_TICKS))
@pytest.mark.parametrize("spiking", [True, False])
def test_lasana_step_fused_matches_percall(spiking, stimulus):
    """Algorithm-1 ticks: fused vs per-call within tolerance, on ticks
    that skip the idle stage (no circuit stale) and on ticks that run it."""
    sur = _make_surrogate(ALL_FAMILY_ASSIGNMENTS[0], seed=8)
    circ = LIFNeuron()
    key = jax.random.PRNGKey(11)
    n = 24
    k1, k2, k3 = jax.random.split(key, 3)
    params = circ.sample_params(k1, n)
    state = init_state(n, params)._replace(
        v=jax.random.uniform(k2, (n,), jnp.float32, 0.0, 1.2),
        t_last=jnp.asarray(np.random.default_rng(0)
                           .choice([0.0, 5.0, 15.0], n).astype(np.float32)))
    ran = []
    for k, changed in enumerate(jnp.asarray(_event_masks(stimulus, n))):
        t = 20.0 + 5.0 * k
        x = circ.sample_inputs(jax.random.fold_in(k3, k), (n,))
        ran.append(bool(jnp.any(stale_circuits(state, changed, t, 5.0))))
        out_f = lasana_step(sur, state, changed, x, t, 5.0,
                            spiking=spiking, fused=True)
        out_u = lasana_step(sur, state, changed, x, t, 5.0,
                            spiking=spiking, fused=False)
        for a, b, name in zip(out_f, out_u, ("state", "e", "l", "o")):
            la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
            for xa, xb in zip(la, lb):
                np.testing.assert_allclose(np.asarray(xa), np.asarray(xb),
                                           rtol=RTOL, atol=ATOL,
                                           err_msg=f"{name}, tick {k}")
        state = out_u[0]
    assert ran == IDLE_TICKS[stimulus]


def test_idle_skip_is_exact():
    """A tick on which no circuit is stale skips the idle heads; the same
    tick with one more circuit stale runs them. The other circuits'
    records and state are bit-identical between the two. The extra
    circuit is there in both, fresh in the first, so that both runs
    compile the same shapes (XLA:CPU's dots round apart at another
    row count)."""
    sur = _make_surrogate(ALL_FAMILY_ASSIGNMENTS[0], seed=8)
    circ = LIFNeuron()
    n = 24
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(17), 3)
    fresh = init_state(n + 1, circ.sample_params(k1, n + 1))._replace(
        v=jax.random.uniform(k2, (n + 1,), jnp.float32, 0.0, 1.2),
        t_last=jnp.full((n + 1,), 15.0))
    stale = fresh._replace(t_last=fresh.t_last.at[n].set(0.0))
    changed = jnp.ones((n + 1,), bool)
    x = circ.sample_inputs(k3, (n + 1,))
    assert not bool(jnp.any(stale_circuits(fresh, changed, 20.0, 5.0)))
    assert bool(stale_circuits(stale, changed, 20.0, 5.0)[n])
    step = jax.jit(lambda st: lasana_step(
        sur, st, changed, x, 20.0, 5.0, spiking=True, fused=True,
        fused_kernel=False))
    for name, a, b in zip(("v", "o", "t_last", "params", "e", "l", "o_out"),
                          jax.tree.leaves(step(fresh)),
                          jax.tree.leaves(step(stale))):
        np.testing.assert_array_equal(np.asarray(a)[:n], np.asarray(b)[:n],
                                      err_msg=name)


def test_lasana_step_fused_annotation_single_dispatch_matches():
    """Annotation mode (the one-dispatch schedule) vs per-call."""
    sur = _make_surrogate(ALL_FAMILY_ASSIGNMENTS[0], seed=9)
    circ = LIFNeuron()
    key = jax.random.PRNGKey(13)
    n = 16
    params = circ.sample_params(key, n)
    state = init_state(n, params)._replace(
        t_last=jnp.full((n,), 5.0), v=jnp.linspace(0, 1, n))
    changed = jnp.ones((n,), bool)
    x = circ.sample_inputs(key, (n,))
    known = jnp.where(jax.random.bernoulli(key, 0.5, (n,)), 1.5, 0.0)
    out_f = lasana_step(sur, state, changed, x, 25.0, 5.0, spiking=True,
                        known_out=known, fused=True)
    out_u = lasana_step(sur, state, changed, x, 25.0, 5.0, spiking=True,
                        known_out=known, fused=False)
    for a, b in zip(jax.tree.leaves(out_f), jax.tree.leaves(out_u)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=RTOL, atol=ATOL)


def test_legacy_bank_without_predict_heads_still_steps(lif_bank):
    """Duck-typed PredictorBank (no predict_heads) silently takes the
    per-call path even with fused=True — no hard requirement on the new
    method for legacy callers."""
    circ = LIFNeuron()
    key = jax.random.PRNGKey(5)
    n = 8
    params = circ.sample_params(key, n)
    state = init_state(n, params)
    changed = jnp.ones((n,), bool)
    x = circ.sample_inputs(key, (n,))
    out_default = lasana_step(lif_bank, state, changed, x, 5.0, 5.0)
    out_percall = lasana_step(lif_bank, state, changed, x, 5.0, 5.0,
                              fused=False)
    for a, b in zip(jax.tree.leaves(out_default),
                    jax.tree.leaves(out_percall)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- network level ------------------------------------------------------------

def _small_net(seed=0, layers=(12, 8, 4)):
    rng = np.random.default_rng(seed)
    ws = [(rng.normal(0, (2.0 / a) ** 0.5, (a, b)) * 2.2).astype(np.float32)
          for a, b in zip(layers[:-1], layers[1:])]
    params = [np.array([0.58, 0.5, 0.5, 0.5], np.float32) for _ in ws]
    spikes = (rng.random((20, 2, layers[0])) < 0.3).astype(np.float32) * 1.5
    return ws, params, spikes


@pytest.mark.parametrize("mode", ["standalone", "annotation"])
def test_network_fused_vs_unfused_parity(lif_bank, mode):
    """Whole-network records: discrete outputs/events identical, analog
    energy/latency within the documented tolerance, both modes."""
    from repro.core.network import NetworkEngine, snn_spec
    sur = lif_bank.to_surrogate()
    ws, params, spikes = _small_net()
    spec = snn_spec(ws, params)
    run_f = NetworkEngine(spec, surrogates=sur, mode=mode).run(spikes)
    run_u = NetworkEngine(spec, surrogates=sur, mode=mode,
                          fused=False).run(spikes)
    np.testing.assert_array_equal(run_f.outputs, run_u.outputs)
    np.testing.assert_array_equal(run_f.events, run_u.events)
    np.testing.assert_array_equal(run_f.out_spikes, run_u.out_spikes)
    np.testing.assert_allclose(run_f.energy, run_u.energy,
                               rtol=RTOL, atol=1e-20)
    np.testing.assert_allclose(run_f.latency, run_u.latency,
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(run_f.flush_energy, run_u.flush_energy,
                               rtol=RTOL, atol=1e-20)


def test_streaming_fused_bit_identical_to_monolithic_fused(lif_bank):
    """The ISSUE-4 streaming contract must survive fusion: chunked fused
    runs stay BIT-identical to the monolithic fused run."""
    from repro.core.network import NetworkEngine, snn_spec
    sur = lif_bank.to_surrogate()
    ws, params, spikes = _small_net(seed=2)
    spec = snn_spec(ws, params)
    eng = NetworkEngine(spec, surrogates=sur, record_hidden=True)
    mono = eng.run(spikes)
    stream = eng.run_stream(spikes, chunk_ticks=7)   # 20 % 7 != 0
    np.testing.assert_array_equal(mono.outputs, stream.outputs)
    np.testing.assert_array_equal(mono.energy, stream.energy)
    np.testing.assert_array_equal(mono.events, stream.events)
    np.testing.assert_array_equal(mono.flush_energy, stream.flush_energy)


def test_fused_zero_recompile_hot_swap(lif_bank, lif_dataset):
    """Surrogate hot-swap through one compiled FUSED program: stacking
    happens inside the traced fn from existing pytree leaves, so swapping
    retrained weights is still zero recompiles."""
    from repro.core.network import NetworkEngine, snn_spec
    from repro.core.predictors import PredictorBank
    sur = lif_bank.to_surrogate()
    sur2 = PredictorBank("lif", families=("mean", "linear")) \
        .fit(lif_dataset).to_surrogate()
    ws, params, spikes = _small_net(seed=3)
    spec = snn_spec(ws, params)
    eng = NetworkEngine(spec, surrogates=sur)
    assert eng.fused
    eng.run(spikes)
    assert eng.compile_count == 1
    eng.run(spikes, surrogates=sur2)
    assert eng.compile_count == 1        # weight swap, not a recompile
    # the unfused baseline is a DIFFERENT program (separate cache key)
    eng_u = NetworkEngine(spec, surrogates=sur, fused=False)
    eng_u.run(spikes)
    assert eng_u.compile_count == 1
