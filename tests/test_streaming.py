"""Streaming chunked network runs (ISSUE-4 tentpole).

Acceptance properties:

  * streaming-vs-monolithic BIT-equivalence — outputs, per-tick
    energy/latency/events, idle flush, spike traces — across chunk sizes
    including T % chunk_ticks != 0, on homogeneous LIF nets and on a
    mixed crossbar->LIF recurrent graph, through the engine and the
    ``lasana.simulate_stream`` facade;
  * zero recompiles on surrogate hot-swap across chunks and on
    chunk-count changes: at most one compiled chunk program per distinct
    chunk shape (<= 2 for any (T, chunk_ticks));
  * donation smoke test: the chunk program actually consumes its carry /
    prev-output / surrogate-leaf buffers (XLA aliases them in place), and
    the caller's surrogate survives streaming untouched;
  * generator variant + StreamingRun/NetworkRun.merge semantics (flush on
    the final chunk only, live totals, iterator stimuli).
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.lasana as lasana
from repro.core.network import (NetworkEngine, NetworkRun, StreamingRun,
                                crossbar_layer, graph_spec, lif_layer,
                                recurrent_edge, snn_spec)

T_STEPS, BATCH = 24, 4


def _assert_runs_identical(mono, st, *, hidden=True):
    np.testing.assert_array_equal(mono.outputs, st.outputs)
    np.testing.assert_array_equal(mono.energy, st.energy)
    np.testing.assert_array_equal(mono.latency, st.latency)
    np.testing.assert_array_equal(mono.events, st.events)
    np.testing.assert_array_equal(mono.flush_energy, st.flush_energy)
    if mono.out_spikes is not None:
        np.testing.assert_array_equal(mono.out_spikes, st.out_spikes)
    if hidden and mono.layer_spikes is not None:
        for a, b in zip(mono.layer_spikes, st.layer_spikes):
            np.testing.assert_array_equal(a, b)


@pytest.fixture(scope="module")
def lif_surrogate(lif_bank):
    return lif_bank.to_surrogate()


@pytest.fixture(scope="module")
def small_net():
    key = jax.random.PRNGKey(0)
    w1 = jax.random.normal(key, (12, 8)) * 0.8
    w2 = jax.random.normal(jax.random.PRNGKey(1), (8, 4)) * 0.8
    params = [jnp.asarray([0.58, 0.5, 0.5, 0.5])] * 2
    spec = snn_spec([w1, w2], params)
    spikes = (jax.random.bernoulli(jax.random.PRNGKey(2), 0.2,
                                   (T_STEPS, BATCH, 12)) * 1.5
              ).astype(jnp.float32)
    return spec, spikes


@pytest.fixture(scope="module")
def mixed_net():
    """Crossbar MAC front-end -> LIF readout + recurrent inhibition."""
    rng = np.random.default_rng(3)
    xw = rng.integers(-1, 2, (20, 8)).astype(np.float32)
    lw = (rng.normal(0, 0.5, (8, 6)) * 2.2).astype(np.float32)
    params = jnp.asarray([0.58, 0.5, 0.5, 0.5], jnp.float32)
    inhib = -0.6 * (1 - np.eye(6, dtype=np.float32))
    spec = graph_spec([crossbar_layer(xw), lif_layer(lw, params)],
                      edges=[recurrent_edge(1, 1, inhib)])
    seq = (rng.integers(-1, 2, (T_STEPS, BATCH, 20)) * 0.8
           ).astype(np.float32)
    return spec, jnp.asarray(seq)


# --- bit-equivalence ----------------------------------------------------------

@pytest.mark.parametrize("chunk_ticks", [T_STEPS, 8, 7, 5, 1])
def test_stream_bitidentical_to_monolithic(lif_surrogate, small_net,
                                           chunk_ticks):
    """Every tested chunk size — divisor or not — reproduces the
    monolithic record bit-for-bit (incl. the single end-of-run flush)."""
    spec, spikes = small_net
    eng = NetworkEngine(spec, backend="lasana", surrogates=lif_surrogate)
    mono = eng.run(spikes)
    st = eng.run_stream(spikes, chunk_ticks=chunk_ticks)
    _assert_runs_identical(mono, st)


@pytest.mark.parametrize("backend", ["behavioral", "golden"])
def test_stream_reference_backends(small_net, backend):
    spec, spikes = small_net
    eng = NetworkEngine(spec, backend=backend)
    _assert_runs_identical(eng.run(spikes),
                           eng.run_stream(spikes, chunk_ticks=7))


def test_stream_crossbar_final_layer():
    """A crossbar-final graph streams too: primary is the LAST tick's
    codes (taken from the last chunk), no spike trace is kept."""
    from repro.core.network import crossbar_mlp_spec
    rng = np.random.default_rng(7)
    ws = [rng.integers(-1, 2, (40, 8)).astype(np.float32),
          rng.integers(-1, 2, (8, 4)).astype(np.float32)]
    spec = crossbar_mlp_spec(ws)
    x = rng.uniform(-0.8, 0.8, (10, 4, 40)).astype(np.float32)
    eng = NetworkEngine(spec, backend="behavioral")
    mono, st = eng.run(x), eng.run_stream(x, chunk_ticks=4)
    _assert_runs_identical(mono, st)
    assert st.out_spikes is None and mono.out_spikes is None


def test_stream_mixed_recurrent_graph(lif_surrogate, small_net, mixed_net,
                                      crossbar_dataset):
    """The acceptance graph: crossbar->LIF with a recurrent edge, bit-
    identical for every tested chunk size through the facade."""
    from repro.core.predictors import PredictorBank
    spec, seq = mixed_net
    banks = {"lif": lif_surrogate,
             "crossbar": PredictorBank("crossbar", families=("mean",
                                                             "linear")
                                       ).fit(crossbar_dataset)}
    mono = lasana.simulate(spec, seq, surrogates=banks, record_hidden=True)
    for chunk in (T_STEPS, 9, 4):
        st = lasana.simulate_stream(spec, seq, chunk_ticks=chunk,
                                    surrogates=banks, record_hidden=True)
        _assert_runs_identical(mono, st)


def test_stream_annotation_mode(lif_surrogate, small_net):
    spec, spikes = small_net
    eng = NetworkEngine(spec, backend="lasana", surrogates=lif_surrogate,
                        mode="annotation")
    _assert_runs_identical(eng.run(spikes),
                           eng.run_stream(spikes, chunk_ticks=5))


def test_stream_iterator_stimulus_rebuffered(lif_surrogate, small_net):
    """Host-generator stimulus blocks are re-buffered to chunk_ticks and
    still merge to the exact monolithic record."""
    spec, spikes = small_net
    x = np.asarray(spikes)
    eng = NetworkEngine(spec, backend="lasana", surrogates=lif_surrogate)
    mono = eng.run(spikes)

    def blocks():
        for a in range(0, T_STEPS, 6):          # 6-tick producer blocks
            yield x[a:a + 6]

    st = eng.run_stream(blocks(), chunk_ticks=9)    # 9-tick chunks
    _assert_runs_identical(mono, st)


def test_stream_mesh_batch_parallel(lif_surrogate, small_net):
    """The chunked path composes with shard_map batch sharding."""
    from jax.sharding import Mesh
    spec, spikes = small_net
    mesh = Mesh(np.array(jax.devices()[:1]), ("batch",))
    eng = NetworkEngine(spec, backend="lasana", surrogates=lif_surrogate,
                        mesh=mesh)
    _assert_runs_identical(eng.run(spikes),
                           eng.run_stream(spikes, chunk_ticks=8))


# --- compile discipline -------------------------------------------------------

def test_chunk_shapes_bound_compiles(lif_surrogate, small_net):
    """<= 2 compiled chunk programs per (T, chunk_ticks): the full-chunk
    shape + the remainder shape; chunk-COUNT changes reuse them all."""
    spec, spikes = small_net
    eng = NetworkEngine(spec, backend="lasana", surrogates=lif_surrogate)
    eng.run_stream(spikes, chunk_ticks=7)        # chunks 7,7,7,3
    assert eng.compile_count == 2
    # longer stream (T=52: chunks 7x7 + 3), same shapes: no new compiles
    longer = jnp.concatenate([spikes, spikes, spikes[:4]], axis=0)
    eng.run_stream(longer, chunk_ticks=7)
    assert eng.compile_count == 2
    # divisor chunking adds at most ONE new shape (no remainder program)
    eng.run_stream(spikes, chunk_ticks=8)
    assert eng.compile_count == 3


def test_surrogate_hot_swap_zero_recompiles(two_stream_surrogates,
                                            small_net):
    """Swapping equal-structure surrogates per chunk mid-stream reuses
    the compiled chunk programs and demonstrably changes the weights."""
    s1, s2 = two_stream_surrogates
    spec, spikes = small_net
    eng = NetworkEngine(spec, backend="lasana")
    base = eng.run_stream(spikes, chunk_ticks=8, surrogates=s1)
    compiles = eng.compile_count
    swapped = eng.run_stream(spikes, chunk_ticks=8,
                             surrogates=itertools.cycle([s1, s2]))
    assert eng.compile_count == compiles
    assert base.energy.sum() != swapped.energy.sum()
    # first chunk used s1 in both runs: identical until the first swap
    np.testing.assert_array_equal(base.energy[:8], swapped.energy[:8])
    assert not np.array_equal(base.energy[8:16], swapped.energy[8:16])


def test_stream_then_monolithic_independent_programs(lif_surrogate,
                                                     small_net):
    """Monolithic and chunked programs cache under distinct keys — one
    run of each compiles exactly one program apiece."""
    spec, spikes = small_net
    eng = NetworkEngine(spec, backend="lasana", surrogates=lif_surrogate)
    eng.run_stream(spikes, chunk_ticks=T_STEPS)      # one full-T chunk
    assert eng.compile_count == 1
    eng.run(spikes)                                  # same shapes, mono key
    assert eng.compile_count == 2


# --- donation -----------------------------------------------------------------

def test_donated_carries_are_consumed(lif_surrogate, small_net):
    """The chunk program must actually donate: carry / prev-output /
    surrogate-leaf input buffers are deleted (aliased into the outputs),
    while the non-donated stimulus buffer survives."""
    spec, spikes = small_net
    eng = NetworkEngine(spec, backend="lasana", surrogates=lif_surrogate)
    b = BATCH
    banks = eng._donatable_banks(eng._runtime_banks(None))
    carries = [eng._init_carry(i, b) for i in range(spec.n_layers)]
    prev = [jnp.zeros((b, l.n_out), jnp.float32) for l in spec.layers]
    k0 = jnp.asarray(0.0, jnp.float32)
    key = eng._program_key("stream", b, T_STEPS, banks)
    compiled, _ = eng._compiled(
        key, lambda: eng._build_stream_step(b, banks),
        (spikes, k0, carries, prev, banks))
    outs = compiled(spikes, k0, carries, prev, banks)
    assert all(a.is_deleted() for a in jax.tree.leaves(carries))
    assert all(a.is_deleted() for a in jax.tree.leaves(prev))
    assert all(a.is_deleted() for a in jax.tree.leaves(banks))
    assert not spikes.is_deleted()
    # the returned state is alive and feeds the next chunk
    assert all(not a.is_deleted() for a in jax.tree.leaves(outs[6]))


def test_callers_surrogate_survives_streaming(lif_surrogate, small_net):
    """Donation must consume the stream's PRIVATE copy, never the
    caller's artifact."""
    spec, spikes = small_net
    eng = NetworkEngine(spec, backend="lasana")
    eng.run_stream(spikes, chunk_ticks=8, surrogates=lif_surrogate)
    for leaf in jax.tree.leaves(lif_surrogate):
        if hasattr(leaf, "is_deleted"):
            assert not leaf.is_deleted()
    feats = np.zeros((1, 9), np.float32)
    assert np.all(np.isfinite(lif_surrogate.predict_np("M_O", feats)))


# --- generator + merge semantics ----------------------------------------------

def test_generator_yields_per_chunk_records(lif_surrogate, small_net):
    spec, spikes = small_net
    # silent final ticks leave every circuit idle before the stream ends,
    # so the flush has trailing idle energy to charge (a circuit with an
    # event on the last tick has none: its flush is exactly zero)
    spikes = spikes.at[-3:].set(0.0)
    eng = NetworkEngine(spec, backend="lasana", surrogates=lif_surrogate)
    recs = list(eng.stream(spikes, chunk_ticks=9))
    assert [r.energy.shape[0] for r in recs] == [9, 9, 6]
    # flush lands exactly once, on the final chunk
    assert all(r.flush_energy.sum() == 0.0 for r in recs[:-1])
    assert recs[-1].flush_energy.sum() > 0.0
    _assert_runs_identical(eng.run(spikes), NetworkRun.merge(recs))


def test_streaming_run_live_totals(lif_surrogate, small_net):
    spec, spikes = small_net
    eng = NetworkEngine(spec, backend="lasana", surrogates=lif_surrogate)
    acc = StreamingRun()
    seen_ticks = []
    for rec in eng.stream(spikes, chunk_ticks=10):
        acc.update(rec)
        seen_ticks.append(acc.ticks)
    assert seen_ticks == [10, 20, 24]            # live mid-stream progress
    run = acc.result()
    assert acc.events == int(run.events.sum())
    np.testing.assert_allclose(acc.energy_j, run.energy.sum(), rtol=1e-7)
    rep = run.report()
    assert rep["network"]["ticks"] == T_STEPS


def test_merge_rejects_mismatched_chunks(lif_surrogate, small_net):
    spec, spikes = small_net
    eng_l = NetworkEngine(spec, backend="lasana", surrogates=lif_surrogate)
    eng_b = NetworkEngine(spec, backend="behavioral")
    a = next(iter(eng_l.stream(spikes, chunk_ticks=8)))
    c = next(iter(eng_b.stream(spikes, chunk_ticks=8)))
    with pytest.raises(ValueError, match="different runs"):
        NetworkRun.merge([a, c])
    with pytest.raises(ValueError, match="before any update"):
        StreamingRun().result()


def test_stream_input_validation(lif_surrogate, small_net):
    spec, spikes = small_net
    eng = NetworkEngine(spec, backend="lasana", surrogates=lif_surrogate)
    with pytest.raises(ValueError, match="chunk_ticks"):
        eng.run_stream(spikes, chunk_ticks=0)
    # argument errors surface at the stream() CALL, not at first next():
    # a dropped generator must not swallow them
    with pytest.raises(ValueError, match="chunk_ticks"):
        eng.stream(spikes, chunk_ticks=-1)
    with pytest.raises(ValueError, match="fan_in"):
        eng.stream(np.zeros((4, 2, 5), np.float32))
    with pytest.raises(ValueError, match="must be"):
        eng.stream(np.zeros((4, 2, 2, 12), np.float32))
    with pytest.raises(ValueError, match="requires surrogates"):
        NetworkEngine(spec, backend="lasana").stream(spikes, chunk_ticks=4)
    with pytest.raises(ValueError, match="fan_in"):
        eng.run_stream(np.zeros((4, 2, 5), np.float32), chunk_ticks=2)
    with pytest.raises(ValueError, match="at least one"):
        eng.run_stream(iter([]), chunk_ticks=2)
    bad_batch = iter([np.zeros((2, BATCH, 12), np.float32),
                      np.zeros((2, BATCH + 1, 12), np.float32)])
    with pytest.raises(ValueError, match="batch"):
        eng.run_stream(bad_batch)


def test_facade_stream_generator(lif_surrogate, small_net):
    """lasana.stream is the facade spelling of the generator variant."""
    spec, spikes = small_net
    recs = list(lasana.stream(spec, spikes, chunk_ticks=8,
                              surrogates=lif_surrogate))
    assert len(recs) == 3
    merged = NetworkRun.merge(recs)
    mono = lasana.simulate(spec, spikes, surrogates=lif_surrogate,
                           record_hidden=False)
    _assert_runs_identical(mono, merged, hidden=False)


# --- generator cleanup + thread safety (ISSUE-8 satellites) -------------------

def test_stream_generator_early_close_settles(lif_surrogate, small_net):
    """Abandoning a stream mid-run (break / close / GC) settles the
    in-flight chunk — donated device buffers are not left dangling — and
    the SAME engine re-streams afterwards with zero recompiles and an
    untouched record."""
    import gc
    spec, spikes = small_net
    eng = NetworkEngine(spec, backend="lasana", surrogates=lif_surrogate)
    mono = eng.run(spikes)
    gen = eng.stream(spikes, chunk_ticks=8)
    next(gen)
    gen.close()                        # explicit close after one chunk
    for rec in eng.stream(spikes, chunk_ticks=8):
        break                          # for-loop break (implicit close)
    dangling = eng.stream(spikes, chunk_ticks=8)
    next(dangling)
    del dangling                       # GC finalization path
    gc.collect()
    compiles = eng.compile_count
    st = NetworkRun.merge(list(eng.stream(spikes, chunk_ticks=8)))
    assert eng.compile_count == compiles
    _assert_runs_identical(mono, st)


def test_concurrent_streams_share_one_program(two_stream_surrogates,
                                              small_net):
    """Two threads streaming through ONE engine — different stimuli,
    different (equal-structure) surrogates — race on first use yet
    compile exactly one chunk program, and each thread's record is
    bit-identical to its sequential run."""
    import threading
    s1, s2 = two_stream_surrogates
    spec, spikes = small_net
    x2 = jnp.roll(spikes, 3, axis=0)
    eng_seq = NetworkEngine(spec, backend="lasana")
    want = {"a": eng_seq.run_stream(spikes, chunk_ticks=8, surrogates=s1),
            "b": eng_seq.run_stream(x2, chunk_ticks=8, surrogates=s2)}
    eng = NetworkEngine(spec, backend="lasana")
    got, errors = {}, []

    def work(name, x, s):
        try:
            got[name] = eng.run_stream(x, chunk_ticks=8, surrogates=s)
        except Exception as err:               # surface in the main thread
            errors.append((name, err))

    threads = [threading.Thread(target=work, args=("a", spikes, s1)),
               threading.Thread(target=work, args=("b", x2, s2))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert eng.compile_count == 1              # the race compiled ONCE
    _assert_runs_identical(want["a"], got["a"])
    _assert_runs_identical(want["b"], got["b"])


@pytest.fixture(scope="module")
def two_stream_surrogates(lif_dataset):
    """Two equal-structure surrogates with different weights (mean+linear
    on disjoint dataset halves would change structure; two seeds keep the
    family selection — and thus the treedef — identical)."""
    import repro.lasana as lasana
    cfg = lambda seed: lasana.TrainConfig(n_runs=50, n_steps=40, seed=seed,
                                          families=("linear",))
    return lasana.train("lif", cfg(1)), lasana.train("lif", cfg(2))
