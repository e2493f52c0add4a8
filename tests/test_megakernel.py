"""Differential parity + property-test harness for the whole-tick megakernel.

Three generations of the LASANA tick body must agree on every graph the
engine accepts:

  percall  the PR 3 per-``predict``-call formulation (``fused=False``)
  fused    the PR 5 stacked-dispatch path (``fused_kernel=False``)
  mega     the whole-tick megakernel (``fused_kernel=True``) — head packs
           VMEM-resident, idle -> act -> transition chained in one body,
           time-looped over whole chunks where eligible

Contract: discrete records (outputs, spike trains, event counts, t_last)
are BIT-identical across all three; continuous heads (energy/latency)
agree to rtol 1e-5 on trained surrogates (packing only reorders float
reductions). The property tests sweep randomly generated ``NetworkSpec``s
— mixed lif|crossbar kinds, recurrent edges, ragged non-block-multiple
sizes, annotation mode, T % chunk != 0 — through all three engines; the
``lif_chunk`` sweep pins the time-looped golden kernel bit-for-bit to
chained ``LIFNeuron.step``.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                                   # pragma: no cover
    from _hyp_fallback import given, settings, st

import jax
import jax.numpy as jnp

RTOL = 1e-5
ATOL = 1e-7


@pytest.fixture(scope="module")
def crossbar_bank(crossbar_dataset):
    from repro.core.predictors import PredictorBank
    return PredictorBank("crossbar",
                         families=("mean", "linear")).fit(crossbar_dataset)


_KIND = {"l": "lif", "x": "crossbar"}


def _libraries(lif_bank, crossbar_bank, topo):
    banks = {"lif": lif_bank, "crossbar": crossbar_bank}
    return {_KIND[c]: banks[_KIND[c]] for c in set(topo)}


def _rand_spec(seed: int, topo: str, recurrent: bool):
    """A ragged mixed-kind NetworkSpec from a topology string ('l'=lif,
    'x'=crossbar); sizes are deliberately NOT multiples of any block."""
    from repro.core.network import (crossbar_layer, graph_spec, lif_layer,
                                    recurrent_edge)
    rng = np.random.default_rng(seed)
    sizes = [int(rng.integers(3, 14)) for _ in range(len(topo) + 1)]
    layers = []
    for i, kind in enumerate(topo):
        w = (rng.normal(0, 0.5, (sizes[i], sizes[i + 1])) * 2.2
             ).astype(np.float32)
        if kind == "l":
            params = np.array([0.58, 0.5, 0.5, 0.5], np.float32)
            layers.append(lif_layer(w, params))
        else:
            layers.append(crossbar_layer(
                np.clip(np.round(w), -1, 1).astype(np.float32)))
    edges = []
    if recurrent:
        lifs = [i for i, k in enumerate(topo) if k == "l"]
        if lifs:
            i = lifs[-1]
            n = sizes[i + 1]
            inhib = (-0.5 * (1 - np.eye(n))).astype(np.float32)
            edges.append(recurrent_edge(i, i, inhib))
    return graph_spec(layers, edges=edges), sizes[0], topo[0]


def _stimulus(seed: int, t_steps: int, batch: int, fan_in: int,
              first_kind: str):
    rng = np.random.default_rng(seed + 1)
    if first_kind == "l":
        return ((rng.random((t_steps, batch, fan_in)) < 0.35)
                .astype(np.float32) * 1.5)
    return (rng.integers(-1, 2, (t_steps, batch, fan_in)) * 0.8
            ).astype(np.float32)


def _run_three(spec, x, banks, mode="standalone", record_hidden=True):
    from repro.core.network import NetworkEngine
    runs = {}
    for name, kw in (("mega", dict(fused_kernel=True)),
                     ("fused", dict(fused_kernel=False)),
                     ("percall", dict(fused=False))):
        eng = NetworkEngine(spec, surrogates=banks, mode=mode,
                            record_hidden=record_hidden, **kw)
        runs[name] = eng.run(x)
    return runs


def _assert_parity(runs):
    ref = runs["fused"]
    for name in ("mega", "percall"):
        r = runs[name]
        np.testing.assert_array_equal(r.outputs, ref.outputs, err_msg=name)
        if ref.out_spikes is not None:
            np.testing.assert_array_equal(r.out_spikes, ref.out_spikes,
                                          err_msg=name)
        if ref.layer_spikes is not None:
            for h, h0 in zip(r.layer_spikes, ref.layer_spikes):
                np.testing.assert_array_equal(h, h0, err_msg=name)
        np.testing.assert_array_equal(r.events, ref.events, err_msg=name)
        np.testing.assert_allclose(r.energy, ref.energy, rtol=RTOL,
                                   atol=ATOL, err_msg=name)
        np.testing.assert_allclose(r.latency, ref.latency, rtol=RTOL,
                                   atol=1e-4, err_msg=name)
        np.testing.assert_allclose(r.flush_energy, ref.flush_energy,
                                   rtol=RTOL, atol=ATOL, err_msg=name)


# --- the property sweep -----------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=10**6),
       st.sampled_from(["l", "ll", "xl", "lx", "xll"]),
       st.booleans())
def test_random_specs_three_way_parity(lif_bank, crossbar_bank, seed, topo,
                                       recurrent):
    """Random ragged mixed graphs: mega == fused == percall (discrete
    bitwise, continuous rtol 1e-5) on trained surrogates."""
    spec, fan_in, first = _rand_spec(seed, topo, recurrent)
    x = _stimulus(seed, 9, 2, fan_in, first)
    _assert_parity(_run_three(spec, x,
                              _libraries(lif_bank, crossbar_bank, topo)))


@settings(max_examples=3, deadline=None)
@given(st.integers(min_value=0, max_value=10**6),
       st.sampled_from(["ll", "xl"]))
def test_random_specs_annotation_parity(lif_bank, crossbar_bank, seed,
                                        topo):
    """Annotation mode: the behavioral model owns outputs/state, so ALL
    discrete records (including spike trains) are bitwise across paths and
    LASANA's energy/latency annotations agree to rtol 1e-5."""
    spec, fan_in, first = _rand_spec(seed, topo, False)
    x = _stimulus(seed, 7, 2, fan_in, first)
    _assert_parity(_run_three(
        spec, x, _libraries(lif_bank, crossbar_bank, topo),
        mode="annotation"))


def test_mega_streaming_bit_identical(lif_bank, crossbar_bank):
    """Chunked streaming on the mega engine == monolithic, bitwise, for
    T % chunk != 0 (the time-looped kernel must be chunk-size invariant)."""
    from repro.core.network import NetworkEngine
    spec, fan_in, first = _rand_spec(5, "xl", True)
    x = _stimulus(5, 23, 2, fan_in, first)
    eng = NetworkEngine(spec,
                        surrogates=_libraries(lif_bank, crossbar_bank, "xl"),
                        fused_kernel=True)
    mono = eng.run(x)
    for chunk in (1, 7, 23):
        s = eng.run_stream(x, chunk_ticks=chunk)
        np.testing.assert_array_equal(s.outputs, mono.outputs)
        np.testing.assert_array_equal(s.out_spikes, mono.out_spikes)
        np.testing.assert_array_equal(s.energy, mono.energy)
        np.testing.assert_array_equal(s.events, mono.events)
        np.testing.assert_array_equal(s.flush_energy, mono.flush_energy)


def test_chunk_fast_path_matches_generic_scan(lif_bank):
    """Single-LIF-layer graphs take the time-looped fast path; a recurrent
    edge makes the same graph ineligible and falls back to the generic
    scan — the two must agree with each other and with stacked dispatch."""
    from repro.core.network import NetworkEngine, graph_spec, lif_layer, \
        recurrent_edge
    rng = np.random.default_rng(7)
    w = (rng.normal(0, 0.5, (11, 6)) * 2.2).astype(np.float32)
    params = np.array([0.58, 0.5, 0.5, 0.5], np.float32)
    spec = graph_spec([lif_layer(w, params)])
    x = ((rng.random((17, 3, 11)) < 0.35).astype(np.float32) * 1.5)

    eng_m = NetworkEngine(spec, surrogates=lif_bank, fused_kernel=True)
    assert eng_m._chunk_eligible()
    runs = _run_three(spec, x, {"lif": lif_bank})
    _assert_parity(runs)

    zero = np.zeros((6, 6), np.float32)
    spec_r = graph_spec([lif_layer(w, params)],
                        edges=[recurrent_edge(0, 0, zero)])
    eng_r = NetworkEngine(spec_r, surrogates=lif_bank, fused_kernel=True)
    assert not eng_r._chunk_eligible()
    r = eng_r.run(x)                      # zero edge: same math, generic scan
    np.testing.assert_array_equal(r.outputs, runs["mega"].outputs)
    np.testing.assert_array_equal(r.out_spikes, runs["mega"].out_spikes)
    np.testing.assert_array_equal(r.energy, runs["mega"].energy)


def test_mega_shard_map_parity(lif_bank, crossbar_bank):
    """The megakernel body runs shard-local under shard_map; a 1-device
    mesh must reproduce the unsharded run exactly."""
    from jax.sharding import Mesh
    from repro.core.network import NetworkEngine
    spec, fan_in, first = _rand_spec(11, "xl", False)
    x = _stimulus(11, 8, 2, fan_in, first)
    banks = _libraries(lif_bank, crossbar_bank, "xl")
    mesh = Mesh(np.array(jax.devices()[:1]), ("batch",))
    r_plain = NetworkEngine(spec, surrogates=banks, fused_kernel=True
                            ).run(x)
    r_mesh = NetworkEngine(spec, surrogates=banks, fused_kernel=True,
                           mesh=mesh).run(x)
    np.testing.assert_array_equal(r_mesh.outputs, r_plain.outputs)
    np.testing.assert_array_equal(r_mesh.events, r_plain.events)
    np.testing.assert_allclose(r_mesh.energy, r_plain.energy, rtol=RTOL,
                               atol=ATOL)


def test_program_key_separates_megakernel(lif_bank, monkeypatch):
    """Flipping the fused-kernel switch (kwarg OR env) or the megakernel
    launcher must change the compiled-program cache key."""
    from repro.core.network import NetworkEngine, snn_spec
    w = np.eye(4, dtype=np.float32)
    spec = snn_spec([w], [np.array([0.58, 0.5, 0.5, 0.5], np.float32)])
    monkeypatch.delenv("REPRO_FUSED_KERNEL", raising=False)
    monkeypatch.delenv("REPRO_TICK_PALLAS", raising=False)
    banks = {"lif": lif_bank}

    def key(**kw):
        eng = NetworkEngine(spec, surrogates=banks, **kw)
        return eng._program_key("mono", 2, 9, eng._runtime_banks(None))

    base = key()
    assert key(fused_kernel=True) != base
    assert key(fused_kernel=False) == base         # env off == explicit off
    monkeypatch.setenv("REPRO_FUSED_KERNEL", "1")
    assert key() == key(fused_kernel=True)
    assert key(fused_kernel=False) == base
    monkeypatch.setenv("REPRO_TICK_PALLAS", "1")
    k_pallas = key(fused_kernel=True)          # launcher joins the key
    monkeypatch.setenv("REPRO_TICK_PALLAS", "0")
    assert key(fused_kernel=True) != k_pallas


# --- dispatch helper: env and kwarg must agree ------------------------------


def test_fused_kernel_enabled_env_vs_kwarg(monkeypatch):
    """Satellite contract: kernels.ops is the ONE resolution point for the
    REPRO_FUSED_KERNEL / REPRO_TICK_PALLAS knobs — an explicit kwarg always
    wins, env only fills the None case."""
    from repro.kernels import ops
    for env, expect in ((None, False), ("0", False), ("1", True)):
        if env is None:
            monkeypatch.delenv("REPRO_FUSED_KERNEL", raising=False)
        else:
            monkeypatch.setenv("REPRO_FUSED_KERNEL", env)
        assert ops.fused_kernel_enabled() is expect
        assert ops.fused_kernel_enabled(True) is True
        assert ops.fused_kernel_enabled(False) is False

    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._interpret_default() is True
    for env, expect in ((None, False), ("0", False), ("1", True)):
        if env is None:
            monkeypatch.delenv("REPRO_TICK_PALLAS", raising=False)
        else:
            monkeypatch.setenv("REPRO_TICK_PALLAS", env)
        assert ops.tick_pallas_enabled() is expect
        assert ops.tick_pallas_enabled(True) is True
        assert ops.tick_pallas_enabled(False) is False
    # platform default: an accelerator runs the compiled Pallas launcher
    monkeypatch.delenv("REPRO_TICK_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret_default() is False
    assert ops.tick_pallas_enabled() is True


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_enable_compile_cache_location(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and is left to JAX; without it the
    cache sits at a fixed path in the checkout, never a per-process one."""
    import os
    from repro.kernels import ops
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = os.path.join(ops._REPO_ROOT, ".jax_cache")
            assert ops.enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
            assert os.path.isfile(os.path.join(ops._REPO_ROOT, "src", "repro",
                                               "kernels", "ops.py"))
        else:
            path = str(tmp_path / env_dir)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
            assert ops.enable_compile_cache() == path
            assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_surrogate_predict_heads_kwarg_matches_env(lif_bank, monkeypatch):
    """predict_heads(fused_kernel=...) == flipping REPRO_FUSED_KERNEL."""
    sur = lif_bank.to_surrogate()
    rng = np.random.default_rng(0)
    feats = rng.uniform(0, 1, (13, 9)).astype(np.float32)
    monkeypatch.setenv("REPRO_FUSED_KERNEL", "1")
    by_env = sur.predict_heads(feats_act=feats)
    monkeypatch.delenv("REPRO_FUSED_KERNEL")
    by_kwarg = sur.predict_heads(feats_act=feats, fused_kernel=True)
    for v, heads in by_env.items():
        for p, y in heads.items():
            np.testing.assert_array_equal(np.asarray(y),
                                          np.asarray(by_kwarg[v][p]))


# --- per-tick megakernel: Pallas launcher vs jnp body -----------------------


def _tick_inputs(sur, n, seed):
    from repro.core.wrapper import init_state
    rng = np.random.default_rng(seed)
    circ_n_in = 3 if sur.circuit == "lif" else 32
    n_p = 4 if sur.circuit == "lif" else 33
    state = init_state(n, jnp.asarray(
        rng.uniform(0.3, 0.7, (n, n_p)).astype(np.float32)))
    state = state._replace(
        v=jnp.asarray(rng.uniform(0, 1, n).astype(np.float32)),
        t_last=jnp.asarray(
            rng.choice([0.0, 5.0, 25.0], n).astype(np.float32)))
    changed = jnp.asarray(rng.random(n) < 0.6)
    x = jnp.asarray(rng.uniform(-1, 1, (n, circ_n_in)).astype(np.float32))
    return state, changed, x


@pytest.mark.parametrize("n", [5, 256, 300])
def test_network_tick_pallas_matches_jnp(lif_bank, n):
    """ONE pallas_call (interpret mode on CPU) == the jnp tick body:
    discrete records bitwise, continuous heads to rtol 1e-5, at ragged and
    block-multiple N."""
    from repro.kernels import tick_megakernel as mk
    sur = lif_bank.to_surrogate()
    pack, layout = mk.pack_heads(sur)
    assert pack is not None
    state, changed, x = _tick_inputs(sur, n, seed=n)
    t = jnp.float32(30.0)
    outs = {}
    for name, pallas in (("jnp", False), ("pallas", True)):
        ns, e, l, o = mk.megakernel_step(
            pack, "lif", state, changed, x, t, 5.0, spiking=True,
            vdd=1.5, layout=layout, pallas=pallas)
        outs[name] = (ns, e, l, o)
    ns_j, e_j, l_j, o_j = outs["jnp"]
    ns_p, e_p, l_p, o_p = outs["pallas"]
    np.testing.assert_array_equal(np.asarray(o_p), np.asarray(o_j))
    np.testing.assert_array_equal(np.asarray(ns_p.o), np.asarray(ns_j.o))
    np.testing.assert_array_equal(np.asarray(ns_p.t_last),
                                  np.asarray(ns_j.t_last))
    np.testing.assert_allclose(np.asarray(ns_p.v), np.asarray(ns_j.v),
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(e_p), np.asarray(e_j),
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(l_p), np.asarray(l_j),
                               rtol=RTOL, atol=1e-4)


@pytest.mark.parametrize("t_steps", [1, 4, 9])
def test_network_tick_chunk_pallas_matches_jnp(lif_bank, t_steps):
    """The time-looped chunk kernel == scanning the per-tick body: discrete
    records bitwise across every chunk length."""
    from repro.kernels import tick_megakernel as mk
    sur = lif_bank.to_surrogate()
    pack, layout = mk.pack_heads(sur)
    rng = np.random.default_rng(t_steps)
    n = 7
    state, _, _ = _tick_inputs(sur, n, seed=t_steps)
    changed_seq = jnp.asarray(rng.random((t_steps, n)) < 0.6)
    x_seq = jnp.asarray(
        rng.uniform(-1, 1, (t_steps, n, 3)).astype(np.float32))
    t_seq = (jnp.arange(t_steps, dtype=jnp.float32) + 1.0) * 5.0
    outs = {}
    for name, pallas in (("jnp", False), ("pallas", True)):
        ns, o_seq, e_seq, l_seq = mk.megakernel_chunk(
            pack, "lif", state, changed_seq, x_seq, t_seq, 5.0,
            spiking=True, vdd=1.5, layout=layout, pallas=pallas)
        outs[name] = (ns, o_seq, e_seq, l_seq)
    ns_j, o_j, e_j, l_j = outs["jnp"]
    ns_p, o_p, e_p, l_p = outs["pallas"]
    np.testing.assert_array_equal(np.asarray(o_p), np.asarray(o_j))
    np.testing.assert_array_equal(np.asarray(ns_p.o), np.asarray(ns_j.o))
    np.testing.assert_array_equal(np.asarray(ns_p.t_last),
                                  np.asarray(ns_j.t_last))
    np.testing.assert_allclose(np.asarray(e_p), np.asarray(e_j),
                               rtol=RTOL, atol=ATOL)


def test_megakernel_chunk_equals_step_loop(lif_bank):
    """jnp chunk == a python loop of per-tick steps, BITWISE — the scan
    formulation cannot drift from the tick it scans."""
    from repro.kernels import tick_megakernel as mk
    sur = lif_bank.to_surrogate()
    pack, layout = mk.pack_heads(sur)
    rng = np.random.default_rng(3)
    n, t_steps = 9, 6
    state, _, _ = _tick_inputs(sur, n, seed=3)
    changed_seq = jnp.asarray(rng.random((t_steps, n)) < 0.6)
    x_seq = jnp.asarray(
        rng.uniform(-1, 1, (t_steps, n, 3)).astype(np.float32))
    t_seq = (jnp.arange(t_steps, dtype=jnp.float32) + 1.0) * 5.0
    ns_c, o_c, e_c, l_c = mk.megakernel_chunk(
        pack, "lif", state, changed_seq, x_seq, t_seq, 5.0, spiking=True,
        vdd=1.5, layout=layout, pallas=False)
    st = state
    os_, es_, ls_ = [], [], []
    for ti in range(t_steps):
        st, e, l, o = mk.megakernel_step(
            pack, "lif", st, changed_seq[ti], x_seq[ti], t_seq[ti], 5.0,
            spiking=True, vdd=1.5, layout=layout, pallas=False)
        os_.append(o), es_.append(e), ls_.append(l)
    np.testing.assert_array_equal(np.asarray(o_c), np.stack(os_))
    np.testing.assert_array_equal(np.asarray(e_c), np.stack(es_))
    np.testing.assert_array_equal(np.asarray(l_c), np.stack(ls_))
    np.testing.assert_array_equal(np.asarray(ns_c.v), np.asarray(st.v))
    np.testing.assert_array_equal(np.asarray(ns_c.t_last),
                                  np.asarray(st.t_last))


# --- the time-looped golden LIF kernel --------------------------------------


@pytest.mark.parametrize("t_steps", [1, 3, 17])
@pytest.mark.parametrize("n", [5, 256])
def test_lif_chunk_bitwise_matches_circuit_step(t_steps, n):
    """ops.lif_chunk == T chained jitted ``LIFNeuron.step`` calls,
    bit-for-bit in fp32 — state, outputs, energy, latency, spike flags —
    across chunk lengths and ragged N."""
    from repro.core.circuits import LIFNeuron
    from repro.kernels import ops
    circ = LIFNeuron()
    rng = np.random.default_rng(n * 31 + t_steps)
    state = jnp.asarray(rng.uniform(0, 1, (n, 3)).astype(np.float32))
    x_seq = jnp.asarray(
        rng.uniform(0, 1.2, (t_steps, n, 3)).astype(np.float32))
    params = jnp.asarray(rng.uniform(0, 1, (n, 4)).astype(np.float32))
    new_state, obs = ops.lif_chunk(state, x_seq, params)
    step = jax.jit(circ.step)
    st, refs = state, []
    for t in range(t_steps):
        st, ob = step(st, x_seq[t], params)
        refs.append(ob)
    np.testing.assert_array_equal(np.asarray(new_state), np.asarray(st))
    for k in ("output", "energy", "latency", "spiked"):
        ref = np.stack([np.asarray(o[k]) for o in refs])
        np.testing.assert_array_equal(np.asarray(obs[k]), ref, err_msg=k)
