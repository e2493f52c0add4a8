"""LASANA-as-a-service (ISSUE-8 tentpole): multi-tenant serving parity.

Acceptance properties:

  * continuous-batching parity — every multiplexed request's merged
    record matches a solo ``lasana.simulate`` of the same stimulus:
    bitwise on discrete records (outputs, spike traces, event counts),
    rtol 1e-5 on f32 energy sums (slot-wise reduction reassociates
    float addition) and on latency maxes, which additionally carry a
    one-ULP absolute epsilon from vectorization-width variance in the
    surrogate dots — nothing else differs — including
    mid-stream join/leave, heterogeneous lengths/batches, mixed
    recurrent graphs, annotation mode, and surrogate hot-swap;
  * compiled-program discipline: programs are bounded by shape buckets,
    never by request count or surrogate versions (two versions share one
    compiled slot program, compile_count == bucket count);
  * admission control: round-robin tenant fairness (no starvation),
    bounded-queue backpressure (``ServerBusy``), oversize rejection;
  * fault isolation + lane lifecycle: per-request errors (bad mode,
    engine-rejected surrogates, exploding on_chunk callbacks) fail only
    their own handle; idle lanes retire (bounded lane table, surrogate
    reference dropped with the key) and re-create compile-free;
  * store semantics (immutable versions, latest-resolve, pinned refs)
    and the JSON-lines wire protocol end to end.
"""

import io
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.lasana as lasana
from repro.core.network import (crossbar_layer, graph_spec, lif_layer,
                                recurrent_edge, snn_spec)
from repro.serve import (ArtifactStore, BucketPolicy, ServeConfig,
                         ServerBusy, SimServer, run_stdio, spec_content_key)
from repro.serve.store import parse_ref

CHUNK = 8
PARAMS = [0.58, 0.5, 0.5, 0.5]


def _make_spec(seed=0):
    k1, k2 = jax.random.PRNGKey(seed), jax.random.PRNGKey(seed + 100)
    w1 = jax.random.normal(k1, (12, 8)) * 0.8
    w2 = jax.random.normal(k2, (8, 4)) * 0.8
    return snn_spec([w1, w2], [jnp.asarray(PARAMS)] * 2)


def _stim(rng, t, b, n_in=12, rate=0.2, amp=1.5):
    return (rng.random((t, b, n_in)) < rate).astype(np.float32) * amp


def _assert_request_parity(solo, served, *, hidden=False):
    """Solo-vs-served record equivalence (see module docstring)."""
    np.testing.assert_array_equal(solo.outputs, served.outputs)
    np.testing.assert_array_equal(solo.events, served.events)
    if solo.out_spikes is not None:
        np.testing.assert_array_equal(solo.out_spikes, served.out_spikes)
    if hidden and solo.layer_spikes is not None:
        for a, b in zip(solo.layer_spikes, served.layer_spikes):
            np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(solo.energy, served.energy, rtol=1e-5,
                               atol=0)
    np.testing.assert_allclose(solo.latency, served.latency, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(solo.flush_energy, served.flush_energy,
                               rtol=1e-5, atol=0)


@pytest.fixture(scope="module")
def lif_surrogate(lif_bank):
    return lif_bank.to_surrogate()


@pytest.fixture(scope="module")
def shared_spec():
    """One spec shared by most tests so its facade engine (and compiled
    slot programs) are built once for the whole module."""
    return _make_spec(0)


@pytest.fixture(scope="module")
def two_versions(lif_dataset):
    """Two equal-structure artifacts (different seeds, same families):
    hot-swappable through one compiled program."""
    cfg = lambda seed: lasana.TrainConfig(n_runs=50, n_steps=40, seed=seed,
                                          families=("linear",))
    return lasana.train("lif", cfg(1)), lasana.train("lif", cfg(2))


# --- parity -------------------------------------------------------------------

def test_single_request_matches_simulate(lif_surrogate, shared_spec):
    """One request through the server IS a solo simulate — including
    hidden spike traces — and streams ceil(T/chunk) partial records."""
    rng = np.random.default_rng(0)
    x = _stim(rng, 20, 2)
    srv = SimServer(ServeConfig(slot_widths=(4,), chunk_ticks=CHUNK,
                                record_hidden=True))
    seen = []
    h = srv.submit(shared_spec, x, surrogates=lif_surrogate,
                   on_chunk=seen.append)
    assert not h.done
    srv.run_until_idle()
    assert h.done and len(h.chunks()) == math.ceil(20 / CHUNK) == len(seen)
    solo = lasana.simulate(shared_spec, x, surrogates=lif_surrogate,
                           record_hidden=True)
    _assert_request_parity(solo, h.result(), hidden=True)


def test_multiplexed_join_leave_parity(lif_surrogate, shared_spec):
    """The tentpole property: 7 concurrent requests of heterogeneous
    length/batch multiplexed onto 4 slots — later requests join
    mid-stream as earlier ones leave — and every merged record matches
    its solo run."""
    rng = np.random.default_rng(1)
    jobs = [(24, 2), (9, 1), (5, 1), (16, 2), (24, 1), (9, 1), (16, 1)]
    stims = [_stim(rng, t, b) for t, b in jobs]
    srv = SimServer(ServeConfig(slot_widths=(4,), chunk_ticks=CHUNK))
    handles = [srv.submit(shared_spec, x, surrogates=lif_surrogate,
                          tenant=f"t{i % 3}")
               for i, x in enumerate(stims)]
    srv.run_until_idle()
    stats = srv.stats()
    assert stats["requests_completed"] == len(jobs)
    assert stats["batch_occupancy"] > 0.3        # slots actually shared
    for (t, _b), x, h in zip(jobs, stims, handles):
        assert len(h.chunks()) == math.ceil(t / CHUNK)
        solo = lasana.simulate(shared_spec, x, surrogates=lif_surrogate,
                               record_hidden=False)
        _assert_request_parity(solo, h.result())


def test_versions_share_compiled_programs(two_versions, lif_surrogate):
    """Hot-swap acceptance: two registered versions (one registered
    MID-workload) serve from separate lanes but ONE compiled slot
    program — compile_count == bucket count == 1 — and each request's
    record matches a solo run with the exact version it resolved."""
    s1, s2 = two_versions
    spec = _make_spec(7)                 # fresh spec => clean engine
    rng = np.random.default_rng(2)
    stims = [_stim(rng, 16, 1) for _ in range(4)]
    srv = SimServer(ServeConfig(slot_widths=(4,), chunk_ticks=CHUNK))
    assert srv.register_surrogate("lif", s1) == 1
    h_pin = srv.submit(spec, stims[0], surrogates="lif@1")
    h_old = srv.submit(spec, stims[1], surrogates="lif")     # latest = 1
    srv.run_until_idle()
    assert srv.register_surrogate("lif", s2) == 2            # hot-swap
    h_new = srv.submit(spec, stims[2], surrogates="lif")     # latest = 2
    h_pin2 = srv.submit(spec, stims[3], surrogates="lif@1")  # pinned old
    srv.run_until_idle()
    assert srv.compile_count() == 1
    assert srv.stats()["n_lanes"] == 2
    assert h_pin.surrogate_ref == h_old.surrogate_ref == ("lif", 1)
    assert h_new.surrogate_ref == ("lif", 2)
    assert h_pin2.surrogate_ref == ("lif", 1)
    for h, x, s in [(h_pin, stims[0], s1), (h_old, stims[1], s1),
                    (h_new, stims[2], s2), (h_pin2, stims[3], s1)]:
        _assert_request_parity(
            lasana.simulate(spec, x, surrogates=s, record_hidden=False),
            h.result())
    # the swap demonstrably changed the weights in flight
    assert h_old.result().energy.sum() != h_new.result().energy.sum()


def test_mixed_recurrent_graph_parity(lif_surrogate, crossbar_dataset):
    """The acceptance graph — crossbar MAC front-end -> LIF readout with
    recurrent inhibition — served next to plain SNN requests."""
    from repro.core.predictors import PredictorBank
    rng = np.random.default_rng(3)
    xw = rng.integers(-1, 2, (20, 8)).astype(np.float32)
    lw = (rng.normal(0, 0.5, (8, 6)) * 2.2).astype(np.float32)
    inhib = -0.6 * (1 - np.eye(6, dtype=np.float32))
    spec = graph_spec([crossbar_layer(xw),
                       lif_layer(lw, jnp.asarray(PARAMS, jnp.float32))],
                      edges=[recurrent_edge(1, 1, inhib)])
    banks = {"lif": lif_surrogate,
             "crossbar": PredictorBank("crossbar",
                                       families=("mean", "linear")
                                       ).fit(crossbar_dataset)}
    seqs = [(rng.integers(-1, 2, (t, b, 20)) * 0.8).astype(np.float32)
            for t, b in [(20, 2), (11, 1)]]
    srv = SimServer(ServeConfig(slot_widths=(4,), chunk_ticks=CHUNK))
    handles = [srv.submit(spec, x, surrogates=banks) for x in seqs]
    srv.run_until_idle()
    for x, h in zip(seqs, handles):
        solo = lasana.simulate(spec, x, surrogates=banks,
                               record_hidden=False)
        _assert_request_parity(solo, h.result())


def test_mixed_graph_slots_run_crossbar_blocks(lif_surrogate,
                                              crossbar_dataset):
    """The acceptance graph's crossbar layer takes the block tick in the
    slot program too (the parity above is then block tick against block
    tick), while its LIF layer keeps the per-row fused path."""
    from repro.core.predictors import PredictorBank
    from repro.kernels import ops
    rng = np.random.default_rng(3)
    xw = rng.integers(-1, 2, (20, 8)).astype(np.float32)
    lw = (rng.normal(0, 0.5, (8, 6)) * 2.2).astype(np.float32)
    spec = graph_spec([crossbar_layer(xw),
                       lif_layer(lw, jnp.asarray(PARAMS, jnp.float32))])
    banks = {"lif": lif_surrogate,
             "crossbar": PredictorBank("crossbar",
                                       families=("mean", "linear")
                                       ).fit(crossbar_dataset)}
    x = (rng.integers(-1, 2, (9, 2, 20)) * 0.8).astype(np.float32)
    srv = SimServer(ServeConfig(slot_widths=(4,), chunk_ticks=CHUNK))
    with ops.dispatch_scope() as log:
        h = srv.submit(spec, x, surrogates=banks)
        srv.run_until_idle()
    assert log.count("predict_blocks") >= 3
    assert log.count("predict_blocks") == log.count("predict_heads")
    _assert_request_parity(lasana.simulate(spec, x, surrogates=banks,
                                           record_hidden=False), h.result())


def test_annotation_mode_parity(lif_surrogate, shared_spec):
    rng = np.random.default_rng(4)
    x = _stim(rng, 13, 2)
    srv = SimServer(ServeConfig(slot_widths=(4,), chunk_ticks=CHUNK))
    h = srv.submit(shared_spec, x, surrogates=lif_surrogate,
                   mode="annotation")
    srv.run_until_idle()
    solo = lasana.simulate(shared_spec, x, surrogates=lif_surrogate,
                           mode="annotation", record_hidden=False)
    _assert_request_parity(solo, h.result())


# --- admission control --------------------------------------------------------

def test_round_robin_tenants_no_starvation(lif_surrogate, shared_spec):
    """A chatty tenant (6 queued requests) cannot starve another: the
    second tenant's requests are seated in the very next admission round
    even though they were submitted last."""
    rng = np.random.default_rng(5)
    srv = SimServer(ServeConfig(slot_widths=(2,), chunk_ticks=CHUNK,
                                max_in_flight=2))
    order = []
    def submit(tenant):
        h = srv.submit(shared_spec, _stim(rng, CHUNK, 1),
                       surrogates=lif_surrogate, tenant=tenant)
        h._on_chunk = lambda rec, hid=h.id: order.append(hid)
        return h
    chatty = [submit("chatty") for _ in range(6)]
    polite = [submit("polite") for _ in range(2)]
    srv.run_until_idle()
    assert all(h.done for h in chatty + polite)
    # both polite requests finish within the first two rounds (4 slots of
    # work), ahead of chatty's 3rd..6th
    for p in polite:
        assert order.index(p.id) < order.index(chatty[2].id)
    assert srv.stats()["wait_chunks_max"] >= 1   # someone actually queued


def test_backpressure_and_validation(lif_surrogate, shared_spec):
    rng = np.random.default_rng(6)
    srv = SimServer(ServeConfig(slot_widths=(4,), chunk_ticks=CHUNK,
                                max_queue=2))
    ok = [srv.submit(shared_spec, _stim(rng, CHUNK, 1),
                     surrogates=lif_surrogate) for _ in range(2)]
    with pytest.raises(ServerBusy, match="queue full"):
        srv.submit(shared_spec, _stim(rng, CHUNK, 1),
                   surrogates=lif_surrogate)
    # malformed requests fail synchronously, never enter the queue
    with pytest.raises(ValueError, match="exceeds the widest"):
        srv.submit(shared_spec, _stim(rng, CHUNK, 8),
                   surrogates=lif_surrogate)
    with pytest.raises(ValueError, match="fan_in"):
        srv.submit(shared_spec, np.zeros((4, 1, 5), np.float32),
                   surrogates=lif_surrogate)
    with pytest.raises(KeyError, match="no spec registered"):
        srv.submit("nope", _stim(rng, CHUNK, 1),
                   surrogates=lif_surrogate)
    with pytest.raises(KeyError, match="no surrogate registered"):
        srv.submit(shared_spec, _stim(rng, CHUNK, 1), surrogates="ghost")
    srv.run_until_idle()
    assert all(h.done for h in ok)
    assert srv.stats()["requests_rejected"] == 1


def test_invalid_mode_rejected_synchronously(lif_surrogate, shared_spec):
    """A bad mode raises in submit() — it must never reach the driver
    thread, where the engine's ValueError would have killed it."""
    srv = SimServer(ServeConfig(slot_widths=(4,), chunk_ticks=CHUNK))
    with pytest.raises(ValueError, match="mode must be one of"):
        srv.submit(shared_spec, np.zeros((4, 1, 12), np.float32),
                   surrogates=lif_surrogate, mode="bogus")


def test_bad_request_does_not_kill_server(lif_surrogate, shared_spec):
    """Per-request fault isolation: a request whose lane creation the
    engine rejects (a direct surrogate object submit cannot cheaply
    validate) fails ITS OWN handle — no hang, no driver-thread death,
    no collateral failures — and the started server keeps serving."""
    rng = np.random.default_rng(11)
    x = _stim(rng, 12, 1)
    with lasana.serve(slot_widths=(4,), chunk_ticks=CHUNK) as srv:
        good1 = srv.submit(shared_spec, x, surrogates=lif_surrogate,
                           tenant="a")
        bad = srv.submit(shared_spec, _stim(rng, 12, 1),
                         surrogates={"not-a-kind": object()}, tenant="b")
        good1.result(timeout=120)
        with pytest.raises(Exception):
            bad.result(timeout=120)          # fails, never blocks forever
        good2 = srv.submit(shared_spec, x, surrogates=lif_surrogate,
                           tenant="c")       # driver is still alive
        served = good2.result(timeout=120)
        st = srv.stats()
    solo = lasana.simulate(shared_spec, x, surrogates=lif_surrogate,
                           record_hidden=False)
    _assert_request_parity(solo, served)
    assert st["requests_failed"] == 1
    assert st["requests_in_flight"] == 0     # failed request not leaked


def test_on_chunk_error_fails_only_that_request(lif_surrogate,
                                                shared_spec):
    """A user on_chunk callback raising fails its request, not the
    driver thread or its co-batched neighbours."""
    rng = np.random.default_rng(14)
    x = _stim(rng, 12, 1)

    def boom(rec):
        raise RuntimeError("chunk consumer exploded")

    srv = SimServer(ServeConfig(slot_widths=(4,), chunk_ticks=CHUNK))
    h_bad = srv.submit(shared_spec, _stim(rng, 12, 1),
                       surrogates=lif_surrogate, on_chunk=boom)
    h_good = srv.submit(shared_spec, x, surrogates=lif_surrogate)
    srv.run_until_idle()
    with pytest.raises(RuntimeError, match="chunk consumer exploded"):
        h_bad.result()
    solo = lasana.simulate(shared_spec, x, surrogates=lif_surrogate,
                           record_hidden=False)
    _assert_request_parity(solo, h_good.result())


def test_idle_lane_retirement_and_surrogate_liveness(lif_surrogate,
                                                     shared_spec):
    """Review fixes, both lane-lifecycle halves: (1) the lane holds the
    directly-passed surrogate alive, so the id()-keyed lane identity
    cannot silently alias a new object at a recycled address; (2) lanes
    idle for lane_idle_rounds rounds are retired — dropping key and
    reference together, bounding the lane table — and re-creation is
    compile-free because the engine keeps its compiled programs."""
    import copy
    import gc
    import weakref
    rng = np.random.default_rng(12)
    x = _stim(rng, CHUNK, 1)
    srv = SimServer(ServeConfig(slot_widths=(4,), chunk_ticks=CHUNK,
                                lane_idle_rounds=3))
    dup = copy.copy(lif_surrogate)
    wr = weakref.ref(dup)
    h = srv.submit(shared_spec, x, surrogates=dup)
    del dup
    srv.run_until_idle()
    h.result()
    gc.collect()
    assert wr() is not None                  # lane pins the surrogate
    assert srv.stats()["n_lanes"] == 1
    # solo reference now: its mono program lands on the shared engine
    # BEFORE the compile-count snapshot the retirement path must hold
    solo = lasana.simulate(shared_spec, x, surrogates=lif_surrogate,
                           record_hidden=False)
    compiles = srv.compile_count()
    for _ in range(3):                       # idle rounds -> retirement
        assert not srv.step()
    gc.collect()
    assert wr() is None                      # key + reference both gone
    st = srv.stats()
    assert st["n_lanes"] == 0 and st["lanes_retired"] == 1
    h2 = srv.submit(shared_spec, x, surrogates=lif_surrogate)
    srv.run_until_idle()
    _assert_request_parity(solo, h2.result())
    assert srv.compile_count() == compiles   # re-created, zero recompiles


def test_lifecycle_guards(shared_spec):
    srv = SimServer()
    srv.start()
    with pytest.raises(RuntimeError, match="driver thread"):
        srv.run_until_idle()
    srv.close()
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit(shared_spec, np.zeros((1, 1, 12), np.float32),
                   surrogates="lif")


# --- store + buckets ----------------------------------------------------------

def test_artifact_store_versioning(lif_surrogate):
    store = ArtifactStore()
    assert store.register("lif", lif_surrogate) == 1
    assert store.register("lif", lif_surrogate) == 2
    assert store.register("lif", lif_surrogate, version=9) == 9
    assert store.register("lif", lif_surrogate) == 10
    assert store.resolve("lif")[0] == ("lif", 10)          # latest
    assert store.resolve("lif@2")[0] == ("lif", 2)         # pinned
    assert store.get("lif", 2) is store.get("lif", 1)
    assert store.names() == ["lif"] and store.versions("lif") == [1, 2, 9,
                                                                  10]
    with pytest.raises(ValueError, match="immutable"):
        store.register("lif", lif_surrogate, version=2)
    with pytest.raises(ValueError, match="'@'-free"):
        store.register("a@b", lif_surrogate)
    with pytest.raises(KeyError, match="not registered"):
        store.resolve("lif@3")
    with pytest.raises(KeyError):
        store.resolve("ghost")
    assert parse_ref("a@3") == ("a", 3) and parse_ref("a") == ("a", None)
    with pytest.raises(ValueError, match="not an integer"):
        parse_ref("a@b")
    with pytest.raises(ValueError, match="bad surrogate ref"):
        parse_ref("@3")


def test_bucket_policy_quantization(shared_spec):
    pol = BucketPolicy(slot_widths=(8, 2), chunk_ticks=4)   # sorts
    assert pol.slot_widths == (2, 8) and pol.max_width == 8
    assert [pol.width_for(b) for b in (1, 2, 3, 8)] == [2, 2, 8, 8]
    with pytest.raises(ValueError, match="exceeds the widest"):
        pol.width_for(9)
    with pytest.raises(ValueError, match="slot_widths"):
        BucketPolicy(slot_widths=())
    with pytest.raises(ValueError, match="chunk_ticks"):
        BucketPolicy(chunk_ticks=0)
    key = spec_content_key(shared_spec)
    assert pol.bucket_for(key, 2).key == (key, 2, 4)
    # content keys: rebuilt-equal specs collapse, value changes split
    assert spec_content_key(_make_spec(0)) == key
    assert spec_content_key(_make_spec(1)) != key
    perturbed = snn_spec(
        [np.asarray(l.weight) * 1.01 for l in shared_spec.layers],
        [l.params for l in shared_spec.layers])
    assert spec_content_key(perturbed) != key


def test_stats_report(lif_surrogate, shared_spec):
    rng = np.random.default_rng(8)
    srv = SimServer(ServeConfig(slot_widths=(4,), chunk_ticks=CHUNK))
    srv.register_surrogate("lif", lif_surrogate)
    hs = [srv.submit(shared_spec, _stim(rng, CHUNK, 1), surrogates="lif")
          for _ in range(3)]
    depth = srv.stats()["queue_depth_by_bucket"]
    assert sum(depth.values()) == 3 and len(depth) == 1
    srv.run_until_idle()
    st = srv.stats()
    assert all(h.done for h in hs)
    assert st["requests_submitted"] == st["requests_completed"] == 3
    assert st["queue_depth_by_bucket"] == {}
    assert 0.0 < st["batch_occupancy"] <= 1.0
    assert st["requests_per_sec"] > 0 and st["events_per_sec"] >= 0
    assert st["surrogates"] == {"lif": [1]}
    assert st["n_lanes"] == len(st["lanes"]) == 1
    assert st["lanes"][0]["active_requests"] == 0
    assert isinstance(st["compile_count"], int)


# --- wire protocol ------------------------------------------------------------

def test_protocol_stdio_roundtrip(lif_surrogate):
    """The JSON-lines loop end to end over a STARTED server: register a
    spec, run simulate + the continuous-batching simulate_batch op,
    survive a malformed op, report stats, shut down."""
    rng = np.random.default_rng(9)
    w1 = (rng.normal(0, 0.8, (6, 5))).astype(np.float32)
    w2 = (rng.normal(0, 0.8, (5, 3))).astype(np.float32)
    script = [
        {"op": "register_spec", "name": "net",
         "snn": {"weights": [w1.tolist(), w2.tolist()],
                 "params": [PARAMS, PARAMS]}},
        {"op": "simulate", "id": "r0", "spec": "net", "surrogate": "lif",
         "stimulus_spikes": {"t": 12, "b": 2, "rate": 0.25, "seed": 5}},
        {"op": "simulate_batch", "requests": [
            {"id": f"b{i}", "spec": "net", "surrogate": "lif",
             "tenant": f"t{i}",
             "stimulus_spikes": {"t": 6 + 3 * i, "b": 1, "seed": i}}
            for i in range(3)]},
        {"op": "simulate", "id": "bad", "spec": "ghost",
         "surrogate": "lif", "stimulus_spikes": {"t": 4, "b": 1}},
        {"op": "stats"},
        {"op": "shutdown"},
        {"op": "never_reached"},
    ]
    fin = io.StringIO("\n".join(json.dumps(o) for o in script) + "\n")
    fout = io.StringIO()
    with lasana.serve(slot_widths=(4,), chunk_ticks=CHUNK) as srv:
        srv.register_surrogate("lif", lif_surrogate)
        handled = run_stdio(srv, fin, fout)
    assert handled == 6                       # shutdown stops the loop
    resps = [json.loads(l) for l in fout.getvalue().splitlines()]
    assert [r["ok"] for r in resps] == [True, True, True, False, True,
                                        True]
    assert resps[1]["id"] == "r0" and resps[1]["ticks"] == 12
    assert resps[1]["energy_j"] > 0
    assert np.asarray(resps[1]["outputs"]).shape == (2, 3)
    batch = resps[2]["results"]
    assert [r["id"] for r in batch] == ["b0", "b1", "b2"]
    assert [r["ticks"] for r in batch] == [6, 9, 12]
    assert resps[3]["id"] == "bad" and "no spec" in resps[3]["error"]
    st = resps[4]["stats"]
    assert st["requests_completed"] == 4 and st["compile_count"] >= 1


def test_protocol_spec_registry_survives_reconnect(lif_surrogate):
    """Review fixes on the wire path: (1) spec names registered on one
    connection resolve on the next — _submit falls back to the server-
    side registry; (2) a simulate_batch that fails partway still
    collects the already-submitted requests' results."""
    rng = np.random.default_rng(13)
    w = rng.normal(0, 0.8, (6, 3)).astype(np.float32)
    conn1 = [{"op": "register_spec", "name": "net",
              "snn": {"weights": [w.tolist()], "params": [PARAMS]}}]
    conn2 = [
        {"op": "simulate", "id": "r", "spec": "net", "surrogate": "lif",
         "stimulus_spikes": {"t": 8, "b": 1, "seed": 3}},
        {"op": "simulate_batch", "requests": [
            {"id": "ok", "spec": "net", "surrogate": "lif",
             "stimulus_spikes": {"t": 8, "b": 1, "seed": 4}},
            {"id": "bad", "spec": "ghost", "surrogate": "lif",
             "stimulus_spikes": {"t": 8, "b": 1}}]},
    ]
    feed = lambda ops: io.StringIO(
        "\n".join(json.dumps(o) for o in ops) + "\n")
    out1, out2 = io.StringIO(), io.StringIO()
    with lasana.serve(slot_widths=(4,), chunk_ticks=CHUNK) as srv:
        srv.register_surrogate("lif", lif_surrogate)
        run_stdio(srv, feed(conn1), out1)    # first "connection"
        run_stdio(srv, feed(conn2), out2)    # reconnect: fresh specs dict
    r1 = [json.loads(l) for l in out1.getvalue().splitlines()]
    r2 = [json.loads(l) for l in out2.getvalue().splitlines()]
    assert r1[0]["ok"]
    assert r2[0]["ok"] and r2[0]["ticks"] == 8        # registry fallback
    batch = r2[1]
    assert not batch["ok"] and "ghost" in batch["error"]
    assert [r["id"] for r in batch["results"]] == ["ok"]  # partials kept
    assert batch["results"][0]["ticks"] == 8
