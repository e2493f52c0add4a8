"""Network-level event-driven engine (core/network.py).

Covers the ISSUE-1 acceptance properties: scheduler determinism under a
fixed seed, standalone-vs-annotation mode consistency, and network-level
LASANA-vs-behavioral spike-train parity within the paper tolerance (<2%
behavioral error) on a tiny 2-layer net — plus mesh batch-parallel parity
and report aggregation invariants.

ISSUE-2 adds the heterogeneous graph coverage: crossbar->LIF mixed-circuit
parity, recurrent-edge one-tick delay semantics, typed inter-layer adapter
shape/dtype round-trips, edge validation, and per-layer circuit/backend
attribution in the report.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core.network import (EdgeSpec, NetworkEngine, adapt_signal,
                                crossbar_layer, crossbar_mlp_spec,
                                event_threshold, graph_spec, lif_layer,
                                recurrent_edge, snn_spec)
from repro.core.simulate import run_snn_golden, run_snn_lasana

T_STEPS, BATCH = 40, 4


@pytest.fixture(scope="module")
def net_bank():
    """Quality LIF bank — large enough for <2% network-level parity."""
    from repro.core.dataset import TestbenchConfig, build_dataset
    from repro.core.predictors import PredictorBank
    ds = build_dataset("lif", TestbenchConfig(n_runs=600, n_steps=80, seed=1))
    return PredictorBank("lif", families=("linear", "mlp")).fit(ds)


@pytest.fixture(scope="module")
def tiny_net():
    """2-layer 12-8-4 LIF net + fixed-seed Poisson spike stimulus."""
    key = jax.random.PRNGKey(0)
    w1 = jax.random.normal(key, (12, 8)) * 0.8
    w2 = jax.random.normal(jax.random.PRNGKey(1), (8, 4)) * 0.8
    params = [jnp.asarray([0.58, 0.5, 0.5, 0.5])] * 2
    spec = snn_spec([w1, w2], params)
    spikes = (jax.random.bernoulli(jax.random.PRNGKey(2), 0.2,
                                   (T_STEPS, BATCH, 12)) * 1.5
              ).astype(jnp.float32)
    return spec, spikes


def test_scheduler_deterministic_under_fixed_seed(net_bank, tiny_net):
    """Same spec + same stimulus -> bit-identical runs, engine reuse or not."""
    spec, spikes = tiny_net
    eng = NetworkEngine(spec, backend="lasana", surrogates=net_bank)
    r1 = eng.run(spikes)
    r2 = eng.run(spikes)                                   # cached jit
    r3 = NetworkEngine(spec, backend="lasana", surrogates=net_bank).run(spikes)
    for other in (r2, r3):
        np.testing.assert_array_equal(r1.out_spikes, other.out_spikes)
        np.testing.assert_array_equal(r1.energy, other.energy)
        np.testing.assert_array_equal(r1.events, other.events)
        np.testing.assert_array_equal(r1.flush_energy, other.flush_energy)


def test_standalone_vs_annotation_consistency(net_bank, tiny_net):
    """Annotation mode must reproduce behavioral spikes EXACTLY (it only
    adds energy/latency) and its energy must land near standalone's."""
    spec, spikes = tiny_net
    behav = NetworkEngine(spec, backend="behavioral").run(spikes)
    annot = NetworkEngine(spec, backend="lasana", surrogates=net_bank,
                          mode="annotation").run(spikes)
    stand = NetworkEngine(spec, backend="lasana", surrogates=net_bank).run(spikes)
    np.testing.assert_array_equal(annot.out_spikes, behav.out_spikes)
    for a, b in zip(annot.layer_spikes, behav.layer_spikes):
        np.testing.assert_array_equal(a, b)
    # behavioral alone reports zero energy; annotation fills it in
    assert behav.energy.sum() == 0.0
    e_a = annot.energy.sum() + annot.flush_energy.sum()
    e_s = stand.energy.sum() + stand.flush_energy.sum()
    assert np.isfinite(e_a) and e_a > 0
    assert abs(e_a - e_s) / e_s < 0.5, (e_a, e_s)


def test_lasana_behavioral_spike_parity(net_bank, tiny_net):
    """Paper tolerance: <2% spike-train mismatch across the whole net."""
    spec, spikes = tiny_net
    behav = NetworkEngine(spec, backend="behavioral").run(spikes)
    las = NetworkEngine(spec, backend="lasana", surrogates=net_bank).run(spikes)
    mism = sum(np.sum((b > 0.75) != (l > 0.75)) for b, l in
               zip(behav.layer_spikes, las.layer_spikes))
    total = sum(b.size for b in behav.layer_spikes)
    assert mism / total < 0.02, f"spike mismatch {mism / total:.4f}"


def test_lasana_energy_tracks_golden(net_bank, tiny_net):
    """Event-driven totals (incl. idle flush) land near the golden sim."""
    spec, spikes = tiny_net
    gold = NetworkEngine(spec, backend="golden").run(spikes)
    las = NetworkEngine(spec, backend="lasana", surrogates=net_bank).run(spikes)
    e_g = gold.report()["network"]["energy_j"]
    e_l = las.report()["network"]["energy_j"]
    assert abs(e_l - e_g) / e_g < 0.15, (e_l, e_g)


def test_mesh_batch_parallel_parity(net_bank, tiny_net):
    """shard_map over a 1-device mesh must not change any output."""
    spec, spikes = tiny_net
    mesh = Mesh(np.array(jax.devices()[:1]), ("batch",))
    base = NetworkEngine(spec, backend="lasana", surrogates=net_bank).run(spikes)
    shard = NetworkEngine(spec, backend="lasana", surrogates=net_bank,
                          mesh=mesh).run(spikes)
    np.testing.assert_array_equal(base.out_spikes, shard.out_spikes)
    np.testing.assert_allclose(base.energy, shard.energy, rtol=1e-6)
    np.testing.assert_allclose(base.flush_energy, shard.flush_energy,
                               rtol=1e-6)
    np.testing.assert_array_equal(base.events, shard.events)


def test_report_aggregation(net_bank, tiny_net):
    """The network report must be consistent with the raw per-tick arrays."""
    spec, spikes = tiny_net
    run = NetworkEngine(spec, backend="lasana", surrogates=net_bank).run(spikes)
    rep = run.report()
    assert len(rep["layers"]) == spec.n_layers
    for i, layer in enumerate(rep["layers"]):
        np.testing.assert_allclose(
            layer["energy_j"],
            run.energy[:, i].sum() + run.flush_energy[i], rtol=1e-6)
        assert layer["events"] == int(run.events[:, i].sum())
    np.testing.assert_allclose(
        rep["network"]["energy_j"],
        sum(l["energy_j"] for l in rep["layers"]), rtol=1e-6)
    assert rep["network"]["events"] == int(run.events.sum())
    assert rep["network"]["ticks"] == T_STEPS
    # event-driven scheduling actually skips idle circuits
    assert rep["network"]["events"] < T_STEPS * BATCH * (8 + 4)


def test_golden_backend_matches_simulate_wrapper(tiny_net):
    """The compat wrapper in simulate.py is the engine under the hood."""
    spec, spikes = tiny_net
    run = NetworkEngine(spec, backend="golden").run(spikes)
    counts, energy = run_snn_golden(
        "lif", [l.weight for l in spec.layers],
        spikes, [l.params for l in spec.layers])
    np.testing.assert_array_equal(run.outputs, counts)
    np.testing.assert_allclose(run.energy.sum(), energy, rtol=1e-6)


def test_invalid_configuration_raises(tiny_net):
    spec, spikes = tiny_net
    with pytest.raises(ValueError, match="backend"):
        NetworkEngine(spec, backend="spice")
    # surrogates may be bound at run() time, but running without any raises
    with pytest.raises(ValueError, match="PredictorBank"):
        NetworkEngine(spec, backend="lasana").run(spikes)
    with pytest.raises(ValueError, match="mode"):
        NetworkEngine(spec, backend="lasana", surrogates=object(),
                      mode="oracle")


# --- crossbar (combinational) path -------------------------------------------

@pytest.fixture(scope="module")
def xbar_net():
    rng = np.random.default_rng(7)
    ws = [rng.integers(-1, 2, (40, 8)).astype(np.float32),
          rng.integers(-1, 2, (8, 4)).astype(np.float32)]
    x = rng.uniform(-0.8, 0.8, (4, 40)).astype(np.float32)
    return crossbar_mlp_spec(ws), x


def test_crossbar_golden_vs_behavioral(xbar_net):
    """Ideal settle + ADC quantization: behavioral must equal golden."""
    spec, x = xbar_net
    g = NetworkEngine(spec, backend="golden").run(x)
    b = NetworkEngine(spec, backend="behavioral").run(x)
    assert g.outputs.shape == (4, 4)
    np.testing.assert_allclose(g.outputs, b.outputs, atol=1e-5)
    assert g.report()["network"]["energy_j"] > 0
    assert np.all(g.latency > 0)


def test_crossbar_lasana_smoke(xbar_net, crossbar_dataset):
    from repro.core.predictors import PredictorBank
    spec, x = xbar_net
    bank = PredictorBank("crossbar",
                         families=("mean", "linear")).fit(crossbar_dataset)
    run = NetworkEngine(spec, backend="lasana", surrogates=bank).run(x)
    assert np.all(np.isfinite(run.outputs))
    rep = run.report()
    assert rep["network"]["energy_j"] > 0
    # one row evaluation per segment per output per sample
    assert rep["layers"][0]["events"] == 4 * 8 * 2    # B * n_out * n_seg
    assert rep["layers"][1]["events"] == 4 * 4 * 1


# --- heterogeneous mixed-circuit graphs (ISSUE-2) -----------------------------

T_MIX, B_MIX = 25, 4


@pytest.fixture(scope="module")
def xbar_bank_q():
    """Quality crossbar bank (gbdt rides the physics-informed row-current
    feature; see circuits.CrossbarRow.surrogate_features)."""
    from repro.core.dataset import TestbenchConfig, build_dataset
    from repro.core.predictors import PredictorBank
    ds = build_dataset("crossbar",
                       TestbenchConfig(n_runs=150, n_steps=80, seed=2))
    return PredictorBank("crossbar",
                         families=("linear", "gbdt", "mlp")).fit(ds)


@pytest.fixture(scope="module")
def mixed_net():
    """Crossbar MAC front-end -> LIF bank with a recurrent inhibition
    self-edge, driven by time-varying ternary DAC patterns."""
    rng = np.random.default_rng(3)
    xw = rng.integers(-1, 2, (20, 8)).astype(np.float32)
    lw = (rng.normal(0, 0.5, (8, 6)) * 2.2).astype(np.float32)
    params = jnp.asarray([0.58, 0.5, 0.5, 0.5], jnp.float32)
    inhib = -0.6 * (1 - np.eye(6, dtype=np.float32))
    spec = graph_spec([crossbar_layer(xw), lif_layer(lw, params)],
                      edges=[recurrent_edge(1, 1, inhib)])
    seq = np.empty((T_MIX, B_MIX, 20), np.float32)
    cur = rng.integers(-1, 2, (B_MIX, 20)).astype(np.float32)
    for t in range(T_MIX):          # re-draw ~20% of the DAC lines per tick
        flip = rng.random((B_MIX, 20)) < 0.2
        cur = np.where(flip, rng.integers(-1, 2, (B_MIX, 20)), cur)
        seq[t] = cur * 0.8
    return spec, jnp.asarray(seq)


def test_mixed_crossbar_lif_parity(net_bank, xbar_bank_q, mixed_net):
    """Crossbar->LIF recurrent graph: all three backends run from ONE spec
    and LASANA standalone tracks behavioral spikes within the paper's 2%."""
    spec, seq = mixed_net
    banks = {"lif": net_bank, "crossbar": xbar_bank_q}
    gold = NetworkEngine(spec, backend="golden").run(seq)
    behav = NetworkEngine(spec, backend="behavioral").run(seq)
    las = NetworkEngine(spec, backend="lasana", surrogates=banks).run(seq)
    assert np.all(np.isfinite(gold.outputs))
    assert np.all(np.isfinite(las.outputs))
    # crossbar codes: surrogate tracks the behavioral DC solve closely
    code_err = np.abs(las.layer_spikes[0] - behav.layer_spikes[0])
    assert code_err.mean() < 0.1, code_err.mean()
    # LIF spikes: <2% mismatch across the spiking layer
    mism = np.mean((las.layer_spikes[1] > 0.75)
                   != (behav.layer_spikes[1] > 0.75))
    assert mism < 0.02, f"mixed-graph spike mismatch {mism:.4f}"
    # energy is attributed to every layer of a mixed graph
    rep = las.report()
    assert all(l["energy_j"] > 0 for l in rep["layers"])


def test_mixed_annotation_reproduces_behavioral(net_bank, xbar_bank_q,
                                                mixed_net):
    """Annotation mode on a mixed graph: exact behavioral outputs on every
    layer (codes AND spikes), energies filled in by LASANA."""
    spec, seq = mixed_net
    banks = {"lif": net_bank, "crossbar": xbar_bank_q}
    behav = NetworkEngine(spec, backend="behavioral").run(seq)
    annot = NetworkEngine(spec, backend="lasana", surrogates=banks,
                          mode="annotation").run(seq)
    for a, b in zip(annot.layer_spikes, behav.layer_spikes):
        np.testing.assert_array_equal(a, b)
    assert behav.energy.sum() == 0.0
    assert annot.energy.sum() > 0


def test_recurrent_edge_one_tick_delay():
    """A strong inhibitory self-loop must act exactly one tick late: the
    first spike is unaffected, the *next* tick is suppressed, and the
    deterministic behavioral trace alternates spike / silence."""
    w = jnp.asarray([[2.5]], jnp.float32)          # supra-threshold drive
    params = jnp.asarray([0.58, 0.5, 0.5, 0.5], jnp.float32)
    spikes = jnp.full((12, 1, 1), 1.5, jnp.float32)   # input spike every tick
    base_spec = graph_spec([lif_layer(w, params)])
    rec_spec = graph_spec([lif_layer(w, params)],
                          edges=[recurrent_edge(0, 0,
                                                jnp.asarray([[-10.0]]))])
    base = NetworkEngine(base_spec, backend="behavioral").run(spikes)
    rec = NetworkEngine(rec_spec, backend="behavioral").run(spikes)
    b = (base.out_spikes[:, 0, 0] > 0.75)
    r = (rec.out_spikes[:, 0, 0] > 0.75)
    assert b.all()                       # without the edge: fires every tick
    assert r[0] == b[0]                  # delayed edge can't touch tick 0
    assert not r[1]                      # ...but suppresses tick 1
    np.testing.assert_array_equal(r, np.arange(12) % 2 == 0)   # alternation


def _op_names(spec, x):
    """op_name of every dot and of every operation of the compiled
    programs of one behavioral run of ``spec`` on ``x``."""
    eng = NetworkEngine(spec, backend="behavioral")
    eng.run(x)
    dots, names = [], []
    for text in eng.compiled_hlo():
        for line in text.splitlines():
            name = line.partition('op_name="')[2].partition('"')[0]
            names.append(name)
            if " dot(" in line or " convolution(" in line:
                dots.append(name)
    return dots, names


@pytest.mark.parametrize("dst_kind", ["lif", "crossbar"])
def test_edge_work_is_scoped_edges_inside_drive(dst_kind):
    """A delayed edge's work lowers under the ``drive/edges`` stage scope:
    into a LIF layer both its drive product and its event-detection
    product; into a crossbar layer its volts product. The same graph
    without the edge names no ``edges``."""
    rng = np.random.default_rng(17)
    params = jnp.asarray([0.58, 0.5, 0.5, 0.5], jnp.float32)
    if dst_kind == "lif":
        layers = [lif_layer(rng.normal(0, 0.5, (12, 16)).astype(np.float32),
                            params)]
        edge = recurrent_edge(0, 0, -0.6 * (1 - np.eye(16, dtype=np.float32)))
        x = jnp.asarray(rng.choice([0.0, 1.5], (6, 3, 12)), jnp.float32)
        n_edge_dots = 2
    else:
        layers = [crossbar_layer(rng.integers(-1, 2, (40, 8)
                                              ).astype(np.float32)),
                  lif_layer(rng.normal(0, 0.5, (8, 6)).astype(np.float32),
                            params)]
        edge = recurrent_edge(1, 0, rng.normal(0, 0.1, (6, 40)
                                               ).astype(np.float32))
        x = jnp.asarray(rng.uniform(-0.8, 0.8, (6, 3, 40)), jnp.float32)
        n_edge_dots = 1
    dots, _ = _op_names(graph_spec(layers, edges=[edge]), x)
    scoped = [d for d in dots if "/drive/edges/" in d]
    assert len(scoped) >= n_edge_dots, dots
    assert all("/edges/" not in d for d in dots if d not in scoped)
    _, names = _op_names(graph_spec(layers), x)
    assert not any("edges" in n for n in names)


def test_chains_without_edges_name_no_edges_scope():
    """snn- and crossbar-MLP-shaped chains (the benchmark's accepted
    cells' forms) carry no ``edges`` scope anywhere in their programs."""
    rng = np.random.default_rng(5)
    params = jnp.asarray([0.58, 0.5, 0.5, 0.5], jnp.float32)
    snn = snn_spec([rng.normal(0, 0.5, (12, 8)).astype(np.float32),
                    rng.normal(0, 0.5, (8, 4)).astype(np.float32)],
                   [params, params])
    xbar = crossbar_mlp_spec([rng.integers(-1, 2, (40, 8)).astype(np.float32),
                              rng.integers(-1, 2, (8, 4)).astype(np.float32)])
    for spec, x in ((snn, jnp.asarray(rng.choice([0.0, 1.5], (5, 2, 12)),
                                      jnp.float32)),
                    (xbar, jnp.asarray(rng.uniform(-0.8, 0.8, (5, 2, 40)),
                                       jnp.float32))):
        _, names = _op_names(spec, x)
        assert any("/drive/" in n for n in names)
        assert not any("edges" in n for n in names)


def test_adapter_shape_dtype_round_trips():
    """Every (src, dst) adapter preserves shape + float32 and lands in the
    destination's native range."""
    amp = 1.5
    spikes = jnp.asarray(np.random.default_rng(0)
                         .choice([0.0, amp], (3, 5)), jnp.float32)
    codes = jnp.asarray(np.random.default_rng(1)
                        .normal(0, 2.0, (3, 5)), jnp.float32)
    for y in (spikes, codes):
        for src, dst in (("lif", "lif"), ("lif", "crossbar"),
                         ("crossbar", "lif"), ("crossbar", "crossbar"),
                         ("input", "lif"), ("input", "crossbar")):
            u = adapt_signal(src, dst, y, spike_amp=amp)
            assert u.shape == y.shape
            assert u.dtype == jnp.float32
    # range contracts
    v = adapt_signal("lif", "crossbar", spikes, spike_amp=amp)
    assert float(jnp.abs(v).max()) <= 0.8 + 1e-6          # DAC rails
    u = adapt_signal("crossbar", "lif", codes, spike_amp=amp)
    assert float(jnp.abs(u).max()) <= amp + 1e-6          # rate-encoded amps
    x = adapt_signal("crossbar", "crossbar", codes, spike_amp=amp)
    assert float(jnp.abs(x).max()) <= 0.8 + 1e-6
    # "none" activation passes codes through linearly (scaled only)
    lin = adapt_signal("crossbar", "crossbar", codes, spike_amp=amp,
                       activation="none")
    np.testing.assert_allclose(np.asarray(lin), np.asarray(codes) * 0.8,
                               rtol=1e-6)
    with pytest.raises(ValueError, match="adapter"):
        adapt_signal("lif", "spice", spikes)
    # event discrimination: spikes at half-amplitude, analog at 5%
    assert event_threshold("lif", amp) == pytest.approx(0.75)
    assert event_threshold("crossbar", amp) == pytest.approx(0.075)


def test_report_attributes_circuit_kinds(mixed_net):
    spec, seq = mixed_net
    run = NetworkEngine(spec, backend="behavioral").run(seq)
    rep = run.report()
    assert [l["circuit"] for l in rep["layers"]] == ["crossbar", "lif"]
    assert all(l["backend"] == "behavioral" for l in rep["layers"])
    assert set(rep["by_circuit"]) == {"crossbar", "lif"}
    assert rep["by_circuit"]["lif"]["events"] == sum(
        l["events"] for l in rep["layers"] if l["circuit"] == "lif")


def test_edge_and_bank_validation(mixed_net):
    spec, _ = mixed_net
    # mixed graph with a single surrogate (not a mapping) is rejected
    with pytest.raises(ValueError, match="mixed-circuit"):
        NetworkEngine(spec, backend="lasana", surrogates=object())
    with pytest.raises(ValueError, match="missing a.*PredictorBank"):
        NetworkEngine(spec, backend="lasana", surrogates={"lif": object()})
    # edge shape validation: lif dst wants (n_out[src], n_out[dst])
    w = jnp.ones((4, 3), jnp.float32)
    p = jnp.asarray([0.58, 0.5, 0.5, 0.5], jnp.float32)
    bad = graph_spec([lif_layer(w, p)],
                     edges=[EdgeSpec(0, 0, jnp.ones((3, 2)))])
    with pytest.raises(ValueError, match="weight shape"):
        NetworkEngine(bad, backend="behavioral")
    oob = graph_spec([lif_layer(w, p)], edges=[EdgeSpec(0, 5, jnp.ones((3, 3)))])
    with pytest.raises(ValueError, match="out of range"):
        NetworkEngine(oob, backend="behavioral")


# --- integer event accounting (ISSUE-4) ---------------------------------------

def test_event_counts_are_exact_integers(net_bank, tiny_net):
    """ISSUE-4 regression: per-tick event counts used to accumulate as
    fp32, silently dropping whole events past 2^24 per tick/layer (dry-run
    scales reach 2^27 circuits). The counting primitive must be exact
    where fp32 demonstrably is not, and the run record must carry integer
    counts end-to-end."""
    from repro.core.network import _count_events
    n = 2 ** 24 + 3
    mask = jnp.ones((n,), bool)
    exact = int(_count_events(mask))
    assert exact == n
    # the old fp32 formulation loses the tail at exactly this scale
    fp32 = int(jnp.sum(mask.astype(jnp.float32)))
    assert fp32 != n
    spec, spikes = tiny_net
    run = NetworkEngine(spec, backend="lasana", surrogates=net_bank
                        ).run(spikes)
    assert np.issubdtype(run.events.dtype, np.integer)
    assert (run.events >= 0).all()
    assert run.report()["network"]["events"] == int(run.events.sum())
