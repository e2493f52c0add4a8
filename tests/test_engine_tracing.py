"""The engine's own spans, counters and stage scopes.

``NetworkEngine.run`` writes host spans ``lasana.*`` with counters as
stats, and the tick cascade carries ``jax.named_scope`` stages into every
operation's ``op_name``. Traced here under ``jax.profiler`` on the CPU and
read back from the xplane with ``ProfileData``.
"""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.lasana as lasana
from repro.core.circuits import get_circuit
from repro.core.network import crossbar_mlp_spec, snn_spec
from repro.core.surrogate import (FORMAT_VERSION, Manifest, Surrogate,
                                  _augment)

STAGES = ("drive", "features", "heads", "update", "flush")
CHILDREN = ("lasana.stimulus", "lasana.prepare", "lasana.execute",
            "lasana.fetch")


def _mlp(rng, f, hidden=(24, 12)):
    dims = (f, *hidden, 1)
    a = {}
    for i in range(len(dims) - 1):
        a[f"w{i}"] = rng.normal(size=dims[i:i + 2]).astype(np.float32) * 0.3
        a[f"b{i}"] = rng.normal(size=dims[i + 1]).astype(np.float32) * 0.1
    a.update(x_mu=np.zeros(f, np.float32), x_sd=np.ones(f, np.float32),
             y_mu=np.zeros(1, np.float32), y_sd=np.ones(1, np.float32))
    return {k: jnp.asarray(v) for k, v in a.items()}


def _surrogate(kind: str, seed: int = 0) -> Surrogate:
    """MLP heads of the production shape with drawn weights: tracing
    needs the program's structure, not a fit."""
    c = get_circuit(kind)
    f = _augment(kind, np.zeros((1, c.n_inputs + 2 + c.n_params),
                                np.float32)).shape[1]
    dims = {"M_O": f, "M_V": f, "M_ES": f, "M_ED": f + 2, "M_L": f + 2}
    rng = np.random.default_rng(seed)
    manifest = Manifest(
        circuit=kind, format_version=FORMAT_VERSION,
        families=tuple(sorted((p, "mlp") for p in dims)),
        scales=tuple(sorted((p, 1e15 if p.startswith("M_E") else 1.0)
                            for p in dims)),
        features=())
    return Surrogate(manifest=manifest,
                     params={p: _mlp(rng, d) for p, d in dims.items()})


@pytest.fixture(scope="module")
def nets():
    rng = np.random.default_rng(0)
    lif = snn_spec([rng.normal(size=(12, 8)).astype(np.float32),
                    rng.normal(size=(8, 4)).astype(np.float32)],
                   [jnp.asarray([0.58, 0.5, 0.5, 0.5])] * 2)
    xbar = crossbar_mlp_spec([
        np.sign(rng.normal(size=(40, 6))).astype(np.float32),
        np.sign(rng.normal(size=(6, 3))).astype(np.float32)])
    x_lif = ((rng.random((40, 4, 12)) < 0.3) * 1.5).astype(np.float32)
    x_xbar = (rng.random((6, 3, 40)) * 0.3).astype(np.float32)
    return {"lif": (lif, _surrogate("lif"), x_lif),
            "xbar": (xbar, _surrogate("crossbar"), x_xbar)}


def _events(log_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("lasana."):
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns),
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.fixture(scope="module")
def traced(nets, tmp_path_factory):
    """Two LIF calls and one crossbar call under one profiler session."""
    log_dir = str(tmp_path_factory.mktemp("trace"))
    lif, lsur, x_lif = nets["lif"]
    xbar, xsur, x_xbar = nets["xbar"]
    jax.profiler.start_trace(log_dir)
    try:
        runs = [lasana.simulate(lif, x_lif, surrogates=lsur),
                lasana.simulate(lif, x_lif, surrogates=lsur),
                lasana.simulate(xbar, x_xbar, surrogates=xsur)]
    finally:
        jax.profiler.stop_trace()
    events = _events(log_dir)
    calls = []
    for name, s, e, stats in events:
        if name == "lasana.run":
            inside = [ev for ev in events
                      if ev[0] != "lasana.run" and s <= ev[1] and ev[2] <= e]
            calls.append(((name, s, e, stats), inside))
    return runs, calls, (x_lif, x_lif, x_xbar)


def test_spans_nest_and_tile_the_call(traced):
    runs, calls, stimuli = traced
    assert len(calls) == 3
    for ((_, s, e, stats), inside), x in zip(calls, stimuli):
        assert (stats["ticks"], stats["batch"]) == x.shape[:2]
        children = [ev for ev in inside if ev[0] in CHILDREN]
        assert [ev[0] for ev in children] == list(CHILDREN)
        assert {ev[3]["call"] for ev in children} == {stats["call"]}
        for a, b in zip(children, children[1:]):
            assert a[2] <= b[1]                   # in order, no overlap
        tiled = sum(ev[2] - ev[1] for ev in children)
        assert tiled >= 0.95 * (e - s)
        prepare = children[1]
        for ev in inside:
            if ev[0] == "lasana.compile":
                assert prepare[1] <= ev[1] and ev[2] <= prepare[2]


def test_compile_span_on_the_first_call_only(traced):
    _, calls, _ = traced
    compiles = [[ev[3] for ev in inside if ev[0] == "lasana.compile"]
                for _, inside in calls]
    # the crossbar net is a new engine: its first call compiles too
    assert compiles == [[{"kind": "mono"}], [], [{"kind": "mono"}]]


def test_byte_counters(traced):
    runs, calls, stimuli = traced
    for ((_, _, _, _), inside), run, x in zip(calls, runs, stimuli):
        got = {ev[0]: ev[3].get("bytes") for ev in inside}
        assert got["lasana.stimulus"] == x.nbytes
        # the records, and one idle-stage flag a (tick, layer)
        records = [run.outputs, run.energy, run.latency,
                   run.events.astype(np.int32), run.flush_energy,
                   run.events.astype(bool), *run.layer_spikes]
        if run.out_spikes is not None:
            records.append(run.out_spikes)
        assert got["lasana.fetch"] == sum(a.nbytes for a in records)


def _stale_pairs(spec, x, run) -> int:
    """(tick, layer) pairs on which some circuit of a LIF chain is stale:
    it has an event, and its last event is more than one tick back (none
    yet reads as tick -1). Events from the stimulus and the recorded
    spikes, which are 0 or the spike amplitude."""
    pre, pairs = np.asarray(x) > 0, 0
    for layer, spikes in zip(spec.layers, run.layer_spikes):
        changed = (pre.astype(np.float32)
                   @ (np.abs(layer.weight) > 0).astype(np.float32)) > 0
        last = np.full(changed.shape[1:], -1)
        for k, c in enumerate(changed):
            pairs += bool(np.any(c & (last < k - 1)))
            last[c] = k
        pre = spikes > 0
    return pairs


@pytest.fixture(scope="module")
def idle_traced(nets, tmp_path_factory):
    """The LIF chain under two stimuli: every input spiking on every tick,
    and the module's sparse stimulus with silent ticks and lanes."""
    lif, lsur, x_lif = nets["lif"]
    sparse = x_lif.copy()
    sparse[5] = 0.0
    sparse[17, :2] = 0.0
    stimuli = {"every_tick": np.full_like(x_lif, 1.5), "sparse": sparse}
    log_dir = str(tmp_path_factory.mktemp("idle"))
    jax.profiler.start_trace(log_dir)
    try:
        runs = {k: lasana.simulate(lif, x, surrogates=lsur)
                for k, x in stimuli.items()}
    finally:
        jax.profiler.stop_trace()
    stats = [ev[3] for ev in _events(log_dir) if ev[0] == "lasana.fetch"]
    return lif, stimuli, runs, dict(zip(stimuli, stats))


@pytest.mark.parametrize("stimulus", ["every_tick", "sparse"])
def test_idle_stage_ticks_counts_stale_pairs(idle_traced, stimulus):
    spec, stimuli, runs, stats = idle_traced
    want = _stale_pairs(spec, stimuli[stimulus], runs[stimulus])
    assert stats[stimulus]["idle_stage_ticks"] == want
    if stimulus == "every_tick":
        assert want == 0
    else:
        assert want > 0


def test_idle_stage_ticks_on_the_chunk_kernel_path(nets, idle_traced,
                                                   tmp_path_factory):
    """A single LIF layer runs the time-looped chunk kernel with the
    fused-kernel switch on: that program emits no stale flags, so its
    span carries no count, while the scan's span counts the stale pairs
    and both records agree."""
    spec, stimuli, _, _ = idle_traced
    one = snn_spec([spec.layers[0].weight], [spec.layers[0].params])
    sur = nets["lif"][1]
    assert lasana.engine(one, fused_kernel=True)._chunk_eligible()
    log_dir = str(tmp_path_factory.mktemp("chunk"))
    jax.profiler.start_trace(log_dir)
    try:
        runs = [lasana.simulate(one, stimuli["sparse"], surrogates=sur,
                                fused_kernel=fk) for fk in (True, False)]
    finally:
        jax.profiler.stop_trace()
    stats = [ev[3] for ev in _events(log_dir) if ev[0] == "lasana.fetch"]
    want = _stale_pairs(one, stimuli["sparse"], runs[1])
    assert want > 0
    assert "idle_stage_ticks" not in stats[0]
    assert stats[1]["idle_stage_ticks"] == want
    np.testing.assert_array_equal(runs[0].events, runs[1].events)


def test_wall_seconds_inside_execute(traced):
    runs, calls, _ = traced
    for run, (_, inside) in zip(runs, calls):
        (_, s, e, _), = [ev for ev in inside if ev[0] == "lasana.execute"]
        assert 0 < run.wall_seconds <= (e - s) * 1e-9


@pytest.mark.parametrize("net", ["lif", "xbar"])
def test_every_dot_and_concatenate_under_a_stage(nets, traced, net):
    spec, sur, x = nets[net]
    text, = lasana.engine(spec).compiled_hlo()      # the mono program
    seen = set()
    for line in text.splitlines():
        op = re.search(r"= \S+ (dot|concatenate)\(", line)
        if not op:
            continue
        name = re.search(r'op_name="([^"]*)"', line).group(1)
        stage = [s for s in name.split("/")[:-1] if s in STAGES]
        assert stage, line
        seen.add(stage[-1])
    assert {"features", "heads"} <= seen
