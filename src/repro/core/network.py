"""Heterogeneous network-level event-driven LASANA engine (paper §V-E at scale).

Composes circuit banks of *different kinds* — event-driven LIF neuron layers
and combinational PCM crossbar-row layers — into one layered dataflow graph
(the MENAGE-style mixed-signal composition: analog crossbar MACs feeding
spiking neuron banks, with optional recurrent feedback) and runs the paper's
Algorithm 1 across the whole graph:

  * per-layer ``circuit`` kinds: every :class:`LayerSpec` names the circuit
    bank it instantiates (``"lif"`` | ``"crossbar"``) plus the bank's local
    knobs (LIF bias params, crossbar segment width / ADC bits / digital
    activation);
  * typed inter-layer adapters (:func:`adapt_signal`): spike trains become
    crossbar input volts (spike -> DAC drive), crossbar ADC codes become
    rate-encoded LIF current drive, crossbar codes become the next crossbar's
    DAC volts — every (src kind, dst kind) pair has one documented signal
    conversion, so heterogeneous layers compose without per-network glue;
  * batched per-tick event queues — each tick, the signal published by layer
    i-1 is the event queue consumed by layer i; per-circuit ``changed`` masks
    mark which circuits received an input event (spike arrival through a
    nonzero weight for LIF banks, a live sample-and-hold input for crossbar
    rows), so idle circuits are skipped and later caught up with ONE merged
    E2 event (core/wrapper.py);
  * recurrent edges (:class:`EdgeSpec`): extra layer->layer connections
    (layer to an *earlier* layer or to itself) that deliver the source
    layer's previous-tick output with a one-tick delay — lateral inhibition,
    feedback loops, winner-take-all circuits;
  * one unified ``_build_sim`` for every graph and all three backends:
      golden      — sub-step ODE integration of every circuit every tick
      behavioral  — SV-RNM ideal discrete update (no energy/latency)
      lasana      — Algorithm 1 over trained :class:`Surrogate` artifacts
                    (a :class:`SurrogateLibrary` with one per circuit
                    kind), in ``standalone`` mode (surrogate predicts output
                    + state + energy/latency) or ``annotation`` mode
                    (behavioral model supplies outputs, LASANA adds
                    energy/latency). Surrogates enter the compiled program
                    as traced pytree arguments: retraining or hot-swapping
                    a surrogate never recompiles the network program;
  * ``shard_map`` batch parallelism over the device mesh via
    core/distributed.py — circuits are batch-local, so a whole network tick
    shards over the flattened mesh with only diagnostic psums;
  * a network-level report attributing per-layer energy / latency / event
    counts to each layer's circuit kind, plus an end-of-run flush that
    charges the static energy of still-idle circuits.

Public API
----------
:class:`LayerSpec` / :class:`EdgeSpec` / :class:`NetworkSpec`
    the graph description (pure data, hashable layer tuples)
:func:`lif_layer` / :func:`crossbar_layer` / :func:`recurrent_edge`
    per-layer/per-edge constructors
:func:`snn_spec` / :func:`crossbar_mlp_spec` / :func:`graph_spec`
    whole-graph constructors (homogeneous SNN, tiled crossbar MLP, arbitrary
    mixed graph)
:func:`adapt_signal` / :func:`event_threshold`
    the typed inter-layer signal adapters
:class:`NetworkEngine` / :class:`NetworkRun`
    the simulator and its run record / report
:meth:`NetworkEngine.run_stream` / :meth:`NetworkEngine.stream` /
:class:`StreamingRun`
    streaming chunked execution: donated chunk-to-chunk carries, async
    host fetch, records bit-identical to the monolithic run

Usage (the facade ``repro.lasana`` wraps this in one documented entry
point — ``lasana.train`` / ``lasana.simulate``)::

    from repro.core.network import (NetworkEngine, crossbar_layer, graph_spec,
                                    lif_layer, recurrent_edge, snn_spec)

    spec = snn_spec(weights, params_per_layer)        # homogeneous LIF net
    golden = NetworkEngine(spec, backend="golden").run(spike_seq)
    lasana = NetworkEngine(spec, backend="lasana",
                           surrogates=surrogate).run(spike_seq)
    print(lasana.report()["network"])                 # energy, events/s, ...

    mixed = graph_spec(                               # MENAGE-style graph
        [crossbar_layer(ternary_w),                   # analog MAC front-end
         lif_layer(readout_w, lif_params)],           # spiking readout
        edges=[recurrent_edge(1, 1, inhibit_w)])      # lateral inhibition
    run = NetworkEngine(mixed, backend="lasana",
                        surrogates={"crossbar": xsur, "lif": lsur}).run(x_seq)

Spiking inputs are (T, B, n_in) spike amplitudes; a 2-D (B, n_in) input is
promoted to one combinational wave (T=1, the pure-crossbar MLP case).
Pass ``mesh=Mesh(...)`` to shard the batch axis.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
import warnings
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.circuits import CrossbarRow, LIFNeuron, get_circuit
from repro.core.distributed import batch_spec, shard_over_batch
from repro.core.surrogate import Surrogate, SurrogateLibrary, as_surrogate
from repro.core.wrapper import (LasanaState, RowBlocks, init_state,
                                lasana_step, row_blocks_ok, stale_circuits)

P_REPL = P()                     # replicated diagnostics spec
BACKENDS = ("golden", "behavioral", "lasana")
MODES = ("standalone", "annotation")
CIRCUIT_KINDS = ("lif", "crossbar")

# a crossbar row-segment has an input event iff any of its sample-and-hold
# input lines carries a live (nonzero) voltage this tick
_XBAR_EVENT_EPS = 1e-6


def _span(name: str, **stats):
    """The host span ``lasana.<name>`` with ``stats`` as its counters, on
    the profiler's clock; inert unless a ``jax.profiler`` session is on."""
    return jax.profiler.TraceAnnotation("lasana." + name, **stats)


# --- network specification ----------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One bank of circuits of a single ``circuit`` kind.

    weight      (fan_in, n_out) — synaptic matrix (lif) or the ternary
                matrix tiled onto ``seg_width``-input crossbar rows
    params      lif: (n_p,) broadcast knobs or (n_out, n_p); crossbar: None
    circuit     "lif" | "crossbar"
    seg_width   crossbar: row segment width (must equal the circuit's
                ``n_inputs``)
    adc_bits    crossbar: ADC resolution applied to each row output
    activation  crossbar: digital activation applied to this layer's ADC
                codes before they drive any downstream layer ("tanh"|"none")
    """

    weight: Any
    params: Any = None
    circuit: str = "lif"
    seg_width: int = 32
    adc_bits: int = 8
    activation: str = "tanh"

    @property
    def fan_in(self) -> int:
        return self.weight.shape[0]

    @property
    def n_out(self) -> int:
        return self.weight.shape[1]

    @property
    def n_seg(self) -> int:
        return -(-self.fan_in // self.seg_width)

    def n_circuits(self, batch: int) -> int:
        """Circuit instances this layer simulates for one batch."""
        if self.circuit == "crossbar":
            return batch * self.n_out * self.n_seg
        return batch * self.n_out


@dataclasses.dataclass(frozen=True)
class EdgeSpec:
    """An extra (typically recurrent) connection between two layers.

    Every edge is delivered with a ONE-TICK DELAY: at tick t the destination
    layer receives the source layer's output published at tick t-1 (zeros at
    t=0).  This makes self-loops and layer->earlier-layer feedback
    well-defined inside the single-tick feed-forward cascade.

    weight   (n_out[src], n_out[dst]) for a lif destination (maps straight
             into the destination's synaptic drive) or
             (n_out[src], fan_in[dst]) for a crossbar destination (maps into
             the destination's DAC input volts).
    """

    src: int
    dst: int
    weight: Any


def recurrent_edge(src: int, dst: int, weight) -> EdgeSpec:
    """One-tick-delayed edge from layer ``src``'s output to layer ``dst``."""
    return EdgeSpec(src=src, dst=dst,
                    weight=jnp.asarray(weight, jnp.float32))


@dataclasses.dataclass(frozen=True)
class NetworkSpec:
    """A layered circuit graph: a feed-forward chain + optional extra edges.

    The chain network-input -> layers[0] -> layers[1] -> ... is evaluated
    within one tick (a combinational cascade); every :class:`EdgeSpec` in
    ``edges`` adds a one-tick-delayed connection on top.
    """

    layers: tuple
    edges: tuple = ()
    spike_amp: float = 1.5      # V_dd spike amplitude on the event queues

    # repro.lasana attaches its compiled-engine cache to the spec (so the
    # executables die with it); that runtime state — holding unpicklable
    # XLA executables — is not spec data and must not serialize
    def __getstate__(self):
        return {k: v for k, v in self.__dict__.items()
                if not k.startswith("_lasana")}

    def __setstate__(self, state):
        self.__dict__.update(state)

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    @property
    def circuits(self) -> tuple:
        return tuple(l.circuit for l in self.layers)

    def edges_into(self, i: int) -> tuple:
        return tuple(e for e in self.edges if e.dst == i)


def lif_layer(weight, params, **kw) -> LayerSpec:
    """LIF neuron bank: weight (fan_in, n_out), params (n_p,) | (n_out, n_p)."""
    return LayerSpec(weight=jnp.asarray(weight, jnp.float32),
                     params=jnp.asarray(params, jnp.float32),
                     circuit="lif", **kw)


def crossbar_layer(weight, *, seg_width: int = 32, adc_bits: int = 8,
                   activation: str = "tanh") -> LayerSpec:
    """Ternary matrix (fan_in, n_out) tiled onto seg_width-input rows."""
    return LayerSpec(weight=jnp.asarray(weight, jnp.float32), params=None,
                     circuit="crossbar", seg_width=seg_width,
                     adc_bits=adc_bits, activation=activation)


def snn_spec(weights, params_per_layer, *, spike_amp: float = 1.5,
             edges=()) -> NetworkSpec:
    """Feed-forward SNN of LIF banks: weights[i] (fan_in_i, n_out_i)."""
    layers = tuple(lif_layer(w, p)
                   for w, p in zip(weights, params_per_layer))
    return NetworkSpec(layers=layers, edges=tuple(edges),
                       spike_amp=spike_amp)


def crossbar_mlp_spec(weights, *, seg_width: int = 32, adc_bits: int = 8,
                      activation: str = "tanh") -> NetworkSpec:
    """Ternary-weight MLP tiled onto ``seg_width``-input crossbar rows."""
    layers = tuple(crossbar_layer(w, seg_width=seg_width, adc_bits=adc_bits,
                                  activation=activation) for w in weights)
    return NetworkSpec(layers=layers)


def graph_spec(layers, *, edges=(), spike_amp: float = 1.5) -> NetworkSpec:
    """Arbitrary mixed-circuit graph from LayerSpecs + EdgeSpecs."""
    return NetworkSpec(layers=tuple(layers), edges=tuple(edges),
                       spike_amp=spike_amp)


# --- typed inter-layer adapters -----------------------------------------------

def _digital_activation(y, activation: str):
    if activation == "tanh":
        return jnp.tanh(y)
    return y


def adapt_signal(src_kind: str, dst_kind: str, y, *, spike_amp: float = 1.5,
                 activation: str = "tanh"):
    """Convert a source layer's published output to dst-native input units.

    Published outputs are: lif — spike amplitudes in {0, spike_amp} volts;
    crossbar — post-ADC, gain-compensated codes in weight-sum units;
    "input" — the network stimulus, already in the first layer's native
    units (spike amplitudes for a lif front layer, DAC volts for crossbar).

    Conversions (``activation`` is the SOURCE crossbar layer's digital
    activation block):

      lif      -> lif       identity (spikes are the drive currency)
      lif      -> crossbar  spike -> DAC volts: s * input_hi / spike_amp
      crossbar -> lif       ADC code -> rate-encoded drive:
                            act(y) * spike_amp  (signed; |u| <= spike_amp)
      crossbar -> crossbar  ADC code -> DAC volts: act(y) * input_hi
    """
    if src_kind == "input":
        return y
    xb = get_circuit("crossbar")
    if src_kind == "lif" and dst_kind == "lif":
        return y
    if src_kind == "lif" and dst_kind == "crossbar":
        return (y * (xb.input_hi / spike_amp)).astype(jnp.float32)
    if src_kind == "crossbar" and dst_kind == "lif":
        return (_digital_activation(y, activation)
                * spike_amp).astype(jnp.float32)
    if src_kind == "crossbar" and dst_kind == "crossbar":
        return (_digital_activation(y, activation)
                * xb.input_hi).astype(jnp.float32)
    raise ValueError(f"no adapter for {src_kind!r} -> {dst_kind!r}")


def event_threshold(src_kind: str, spike_amp: float) -> float:
    """|u| above this counts as an input event at a LIF destination.

    Spiking sources emit V_dd pulses (half-amplitude discriminator);
    analog crossbar sources count any appreciable rate-encoded drive.
    """
    if src_kind in ("input", "lif"):
        return 0.5 * spike_amp
    return 0.05 * spike_amp


def drive_to_circuit_inputs(drive, *, spike_amp: float = 1.5,
                            n_spk: float = 5.0):
    """Aggregate synaptic drive -> (w, x, n) LIF circuit inputs.

    ``spike_amp`` is the presynaptic spike amplitude (the source circuit's
    V_dd) and ``n_spk`` the spikes-per-period ceiling the LIF testbench
    trains against; both used to be hardcoded at the 1.5-V_dd defaults,
    which would silently mis-drive any future non-1.5-V_dd LIF circuit."""
    w = jnp.clip(drive, -1.0, 1.0)
    x = jnp.full_like(drive, spike_amp)
    n = jnp.full_like(drive, n_spk)
    return jnp.stack([w, x, n], axis=-1)


def _count_events(changed) -> jax.Array:
    """Exact integer count of a ``changed`` mask.

    Event counts used to accumulate as fp32, which silently drops whole
    events once a tick/layer exceeds 2^24 of them (dry-run scales reach
    2^27 circuits); int32 keeps every count exact to 2^31."""
    return jnp.sum(changed, dtype=jnp.int32)


def _any_stale(backend: str, carry, changed, t, clock_ns) -> jax.Array:
    """Whether some circuit of a layer is stale this tick, due Algorithm
    1's idle catch-up: the default standalone tick evaluates that stage
    only then. Only the lasana backend has the stage."""
    if backend != "lasana":
        return jnp.zeros((), bool)
    return jnp.any(stale_circuits(carry, changed, t, clock_ns))


def _tile_params(p, b: int, n_out: int):
    p = jnp.asarray(p, jnp.float32)
    if p.ndim == 1:                       # one knob set for the whole layer
        return jnp.broadcast_to(p[None], (b * n_out, p.shape[0]))
    return jnp.tile(p, (b, 1))            # per-neuron knobs, batch-tiled

def _row_segments(w, seg_width: int):
    """(n_in, n_out) ternary matrix -> (n_out * n_seg, seg_width + 1)
    crossbar row params (last column is the bias row, unused here)."""
    w = np.asarray(w)
    n_in, n_out = w.shape
    n_seg = -(-n_in // seg_width)
    pad = n_seg * seg_width - n_in
    wp = np.pad(w, ((0, pad), (0, 0)))
    segs = (wp.reshape(n_seg, seg_width, n_out)
            .transpose(2, 0, 1).reshape(-1, seg_width))
    return np.concatenate([segs, np.zeros((len(segs), 1))],
                          axis=1).astype(np.float32)


def _iter_chunks(stimulus, chunk_ticks, fan_in: int, skip_ticks: int = 0):
    """Yield (t_i, B, fan_in) stimulus chunks for the streaming path.

    ``stimulus`` is either one (T, B, fan_in) array — sliced into
    ``chunk_ticks``-tick chunks without ever putting more than one chunk
    on device when it lives in host memory — or an iterator of
    (t_i, B, fan_in) blocks, re-buffered to ``chunk_ticks`` ticks when a
    chunk size is given (the last chunk may be short). 2-D (B, fan_in)
    blocks promote to one tick. ``skip_ticks`` drops the leading ticks
    before chunking (checkpoint resume: the caller re-supplies the FULL
    original stimulus and the consumed prefix is skipped here, so the
    tail re-chunks exactly as the uninterrupted run would have)."""
    if chunk_ticks is not None and chunk_ticks <= 0:
        raise ValueError(f"chunk_ticks must be positive: {chunk_ticks}")

    def check(blk):
        if blk.ndim == 2:
            blk = blk[None]
        if blk.ndim != 3:
            raise ValueError(f"stimulus chunks must be (T, B, n_in), got "
                             f"shape {tuple(blk.shape)}")
        if blk.shape[-1] != fan_in:
            raise ValueError(f"input width {blk.shape[-1]} != layer-0 "
                             f"fan_in {fan_in}")
        return blk

    skip = int(skip_ticks)
    if hasattr(stimulus, "ndim"):              # one whole array
        x = check(stimulus)[skip:]
        step = int(chunk_ticks) if chunk_ticks else x.shape[0]
        for a in range(0, x.shape[0], step):
            yield x[a:a + step]
        return
    parts, have = [], 0                        # iterator of blocks
    for block in stimulus:
        blk = check(np.asarray(block, np.float32))
        if skip:                               # resume: drop consumed prefix
            if blk.shape[0] <= skip:
                skip -= blk.shape[0]
                continue
            blk = blk[skip:]
            skip = 0
        if chunk_ticks is None:
            yield blk
            continue
        parts.append(blk)
        have += blk.shape[0]
        while have >= chunk_ticks:             # one concat per emitted chunk
            buf = parts[0] if len(parts) == 1 \
                else np.concatenate(parts, axis=0)
            yield buf[:chunk_ticks]
            rest = buf[chunk_ticks:]
            parts = [rest] if rest.shape[0] else []
            have = rest.shape[0]
    if have:
        yield parts[0] if len(parts) == 1 else np.concatenate(parts, axis=0)


# --- run record ---------------------------------------------------------------

@dataclasses.dataclass
class NetworkRun:
    """Record of one network simulation over T ticks (combinational: T=1)."""

    backend: str
    mode: str
    outputs: np.ndarray           # lif last layer: (B, n_cls) spike counts;
                                  # crossbar last layer: (B, n_cls) codes
    out_spikes: Optional[np.ndarray]   # lif last layer: (T, B, n_cls) amps
    layer_spikes: Optional[list]  # per layer (T, B, n_i) published outputs
    energy: np.ndarray            # (T, L) joules per tick per layer
    latency: np.ndarray           # (T, L) ns — max over the layer's circuits
    events: np.ndarray            # (T, L) input events processed
    flush_energy: np.ndarray      # (L,) end-of-run idle static energy
    n_circuits: np.ndarray        # (L,) circuits per layer (B-included)
    clock_ns: float
    wall_seconds: float           # steady-state execution only (no compile)
    circuits: tuple = ()          # (L,) per-layer circuit kind
    compile_seconds: float = 0.0  # one-time trace+compile of this program
    checkpoint: Optional[Any] = None   # StreamCheckpoint when this chunk
                                  # closed a checkpoint interval (streaming
                                  # with checkpoint_every=; see
                                  # repro.resilience.checkpoint); merge/
                                  # StreamingRun ignore it

    def report(self) -> dict:
        """Aggregate per-layer energy/latency/events + network totals.

        Each layer entry names its ``circuit`` kind and the ``backend`` that
        produced it, so mixed-graph energy breakdowns stay attributable."""
        t_steps, n_layers = self.energy.shape
        circuits = self.circuits or ("?",) * n_layers
        # ONE host transfer for every reduction below (fields may still be
        # device arrays), then vectorized per-layer aggregation — report()
        # on a fresh run must not issue 5 blocking fetches per layer
        energy, latency, events, flush_energy, n_circuits = (
            np.asarray(a) for a in jax.device_get(
                (self.energy, self.latency, self.events,
                 self.flush_energy, self.n_circuits)))
        e_layer = energy.sum(axis=0) + flush_energy             # (L,)
        ev_layer = events.sum(axis=0)                           # (L,)
        max_lat = latency.max(axis=0, initial=0.0)              # (L,)
        # a zero-tick run (T=0: e.g. a drained stream's empty tail chunk)
        # has no ticks to average over — report 0.0, not NaN + a numpy
        # RuntimeWarning from mean() on the empty slice
        mean_lat = (latency.mean(axis=0) if t_steps
                    else np.zeros(n_layers, np.float64))
        layers = []
        for i in range(n_layers):
            layers.append({
                "layer": i,
                "circuit": circuits[i],
                "backend": self.backend,
                "n_circuits": int(n_circuits[i]),
                "energy_j": float(e_layer[i]),
                "flush_energy_j": float(flush_energy[i]),
                "events": int(ev_layer[i]),
                "max_latency_ns": float(max_lat[i]),
                "mean_tick_latency_ns": float(mean_lat[i]),
            })
        total_events = int(ev_layer.sum()) if n_layers else 0
        by_kind: dict = {}
        for l in layers:
            agg = by_kind.setdefault(l["circuit"],
                                     {"energy_j": 0.0, "events": 0})
            agg["energy_j"] += l["energy_j"]
            agg["events"] += l["events"]
        return {
            "backend": self.backend,
            "mode": self.mode,
            "layers": layers,
            "by_circuit": by_kind,
            "network": {
                "ticks": t_steps,
                "sim_time_ns": t_steps * self.clock_ns,
                "energy_j": float(sum(l["energy_j"] for l in layers)),
                "events": total_events,
                "events_per_sec": total_events / max(self.wall_seconds, 1e-9),
                "wall_seconds": self.wall_seconds,
                "compile_seconds": self.compile_seconds,
            },
        }

    @classmethod
    def merge(cls, chunks) -> "NetworkRun":
        """Merge consecutive per-chunk records into one whole-run record.

        ``chunks`` is the sequence :meth:`NetworkEngine.stream` yields (in
        order). The merged record is bit-identical to the monolithic
        :meth:`NetworkEngine.run` over the concatenated stimulus: spike
        counts sum exactly (integer chunk partials), per-tick diagnostics
        concatenate, and the end-of-run flush — present only on the final
        chunk — is applied exactly once. ``wall_seconds`` /
        ``compile_seconds`` sum, which for records from one stream equals
        the end-to-end steady/compile split."""
        acc = StreamingRun()
        for c in chunks:
            acc.update(c)
        return acc.result()


class StreamingRun:
    """Incremental accumulator of per-chunk :class:`NetworkRun` records.

    The streaming counterpart of a monolithic run record:
    :meth:`NetworkEngine.run_stream` feeds it one chunk at a time and
    :meth:`result` freezes a :class:`NetworkRun` bit-identical to the
    monolithic run (see :meth:`NetworkRun.merge`). Live totals —
    :attr:`ticks`, :attr:`events`, :attr:`energy_j` — update as chunks
    arrive, so a dashboard can read progress mid-stream.
    """

    def __init__(self):
        self._first: Optional[NetworkRun] = None
        self._last: Optional[NetworkRun] = None
        self._counts = None            # lif last layer: running spike counts
        self._out_chunks: list = []
        self._hidden_chunks: list = []
        self._energy: list = []
        self._latency: list = []
        self._events: list = []
        self._flush = None
        self.ticks = 0                 # ticks accumulated so far
        self.events = 0                # input events accumulated so far
        self.energy_j = 0.0            # joules accumulated so far (no flush)
        self.wall_seconds = 0.0
        self.compile_seconds = 0.0

    def update(self, chunk: NetworkRun) -> "StreamingRun":
        """Fold the next consecutive chunk record in; returns ``self``."""
        if self._first is None:
            self._first = chunk
            self._flush = np.zeros_like(chunk.flush_energy)
        elif (chunk.backend != self._first.backend
                or chunk.mode != self._first.mode
                or chunk.circuits != self._first.circuits):
            raise ValueError("cannot merge chunks from different runs: "
                             f"{chunk.backend}/{chunk.mode} vs "
                             f"{self._first.backend}/{self._first.mode}")
        self._last = chunk
        if chunk.circuits and chunk.circuits[-1] == "lif":
            c = np.asarray(chunk.outputs, np.int64)
            self._counts = c if self._counts is None else self._counts + c
            self._out_chunks.append(chunk.out_spikes)
        if chunk.layer_spikes is not None:
            self._hidden_chunks.append(chunk.layer_spikes)
        self._energy.append(chunk.energy)
        self._latency.append(chunk.latency)
        self._events.append(chunk.events)
        self._flush = self._flush + chunk.flush_energy
        self.ticks += chunk.energy.shape[0]
        self.events += int(chunk.events.sum())
        self.energy_j += float(chunk.energy.sum())
        self.wall_seconds += chunk.wall_seconds
        self.compile_seconds += chunk.compile_seconds
        return self

    def result(self) -> NetworkRun:
        """Freeze the accumulated chunks into one :class:`NetworkRun`."""
        if self._first is None or self._last is None:
            raise ValueError("StreamingRun.result() before any update()")
        first, last = self._first, self._last
        last_lif = first.circuits and first.circuits[-1] == "lif"
        if last_lif:
            outputs = self._counts.astype(first.outputs.dtype)
            out_spikes = np.concatenate(self._out_chunks, axis=0)
        else:
            outputs = last.outputs
            out_spikes = None
        hidden = None
        if self._hidden_chunks:
            hidden = [np.concatenate([h[i] for h in self._hidden_chunks],
                                     axis=0)
                      for i in range(len(self._hidden_chunks[0]))]
        return NetworkRun(
            backend=first.backend, mode=first.mode,
            outputs=outputs, out_spikes=out_spikes, layer_spikes=hidden,
            energy=np.concatenate(self._energy, axis=0),
            latency=np.concatenate(self._latency, axis=0),
            events=np.concatenate(self._events, axis=0),
            flush_energy=self._flush,
            n_circuits=first.n_circuits, clock_ns=first.clock_ns,
            wall_seconds=self.wall_seconds, circuits=first.circuits,
            compile_seconds=self.compile_seconds)


@dataclasses.dataclass(frozen=True)
class SlotPrograms:
    """The compiled continuous-batching program family for one
    (batch width, chunk ticks, surrogate structure) bucket — what the
    serving layer's scheduler drives (see :meth:`NetworkEngine.
    slot_programs` for the calling conventions and parity contract)."""

    step: Any                      # chunk program, donated carries
    flush: Any                     # per-slot leave-time idle flush
    join: Any                      # masked slot (re)initialization
    compile_seconds: float         # 0.0 when every program was cached


# --- the engine ----------------------------------------------------------------

class NetworkEngine:
    """Heterogeneous circuit graph under one jitted event-driven scheduler.

    backend  "golden" | "behavioral" | "lasana"
    mode     lasana only: "standalone" (surrogate closes the loop) or
             "annotation" (behavioral supplies outputs/state, LASANA adds
             energy/latency)
    surrogates  backend="lasana": a trained :class:`Surrogate` (homogeneous
             graphs) or a :class:`SurrogateLibrary` / ``{circuit kind:
             Surrogate}`` mapping (mixed graphs). Surrogates enter the
             compiled network program as a *traced pytree argument*, so one
             program serves every retrained surrogate with matching
             manifest/shapes — swap at :meth:`run` time with zero
             recompiles. May be omitted here and supplied per ``run()``.
    bank     deprecated alias of ``surrogates``; legacy ``PredictorBank``
             values (single or mapping) are frozen into Surrogates.
    mesh     optional jax Mesh: shard the batch axis over every mesh axis
    record_hidden  keep per-layer output traces (tests/parity); disable for
             large sweeps to save host memory
    fused    lasana only: take the fused inference hot path
             (``Surrogate.predict_heads`` — one feature build + stacked
             same-family predictor passes per tick) in every compiled
             program: monolithic, streaming, and shard_map. Default True;
             ``fused=False`` compiles the per-``predict``-call
             formulation (the benchmark A/B baseline — results agree
             within a few ULPs, see tests/test_fused.py).
    fused_kernel  lasana only: tri-state override of the
             ``REPRO_FUSED_KERNEL`` switch (resolved through
             ``kernels.ops.fused_kernel_enabled``). ``True`` engages the
             whole-tick megakernel hot path (``kernels.tick_megakernel``:
             cross-kind head packs, one fused idle->act->transition body
             per tick, Pallas launcher per ``REPRO_TICK_PALLAS``);
             ``False`` forces the stacked-dispatch path regardless of the
             env; ``None`` (default) defers to the env var.
    """

    def __init__(self, spec: NetworkSpec, backend: str = "lasana", *,
                 surrogates=None, bank=None, mode: str = "standalone",
                 mesh=None, record_hidden: bool = True, fused: bool = True,
                 fused_kernel: bool | None = None):
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}: {backend}")
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}: {mode}")
        for layer in spec.layers:
            if layer.circuit not in CIRCUIT_KINDS:
                raise ValueError(f"unknown circuit kind {layer.circuit!r}; "
                                 f"registered kinds: {CIRCUIT_KINDS}")
        self.spec = spec
        self.backend = backend
        self.mode = mode if backend == "lasana" else "standalone"
        self.mesh = mesh
        self.record_hidden = record_hidden
        self.fused = bool(fused)
        self.fused_kernel = (None if fused_kernel is None
                             else bool(fused_kernel))
        self.circs = tuple(get_circuit(l.circuit) for l in spec.layers)
        if bank is not None:
            warnings.warn(
                "NetworkEngine(bank=...) is deprecated; pass surrogates= "
                "(repro.lasana.train / Surrogate.from_bank)",
                DeprecationWarning, stacklevel=2)
            if surrogates is None:
                surrogates = bank
        if surrogates is not None and backend != "lasana":
            # same guard run() applies: never silently ignore a surrogate
            raise ValueError(
                f"backend={backend!r} does not use surrogates; pass "
                "surrogates= only with backend='lasana'")
        self.surrogates = (self._normalize_surrogates(surrogates)
                           if surrogates is not None else None)
        for i, (layer, circ) in enumerate(zip(spec.layers, self.circs)):
            if isinstance(circ, LIFNeuron) and spec.spike_amp != circ.vdd:
                # spike amplitude IS the circuit's V_dd: the wrapper's spike
                # threshold (0.5 * 1.5) and behavioral/golden outputs are all
                # V_dd-referenced, so other amplitudes would silently diverge
                # across backends
                raise ValueError(
                    f"spike_amp {spec.spike_amp} != circuit V_dd "
                    f"{circ.vdd}; the LIF event queues carry V_dd spikes")
            if isinstance(circ, CrossbarRow) \
                    and layer.seg_width != circ.n_inputs:
                raise ValueError(
                    f"layer {i}: seg_width {layer.seg_width} != crossbar "
                    f"row n_inputs {circ.n_inputs}")
        self._validate_edges()
        # the network tick is one global digital clock; per-layer event
        # features/timestamps use each circuit's native clock (see _lif_tick)
        self.clock_ns = max(c.clock_ns for c in self.circs)
        self._sim_cache: dict = {}
        # serializes first-compile of a program key so concurrent streams
        # on one engine (the serving layer, threaded clients) compile each
        # program exactly once and never race the cache dict
        self._compile_lock = threading.Lock()
        self.compile_count = 0        # distinct compiled network programs
        self._trace_count = 0         # times a sim body was (re)traced
        self._calls = itertools.count()   # ``call`` stat of the run spans
        self._call = threading.local()    # the thread's call, for ``_run``

    def _normalize_surrogates(self, src) -> SurrogateLibrary:
        """Coerce surrogates/bank input into a validated SurrogateLibrary."""
        kinds = set(self.spec.circuits)
        if isinstance(src, SurrogateLibrary):
            mapping = dict(src.items())
        elif isinstance(src, dict):
            mapping = dict(src)
        else:
            if len(kinds) > 1:
                raise ValueError(
                    "mixed-circuit graphs need a {circuit: Surrogate} "
                    "library (legacy {circuit: PredictorBank} mappings are "
                    f"converted), got a single surrogate for kinds "
                    f"{sorted(kinds)}")
            mapping = {next(iter(kinds)): src}
        missing = kinds - set(mapping)
        if missing:
            raise ValueError(
                "backend='lasana' is missing a Surrogate (or legacy "
                f"PredictorBank) for circuit kind(s) {sorted(missing)}")
        lib = {}
        for kind in sorted(kinds):
            s = as_surrogate(mapping[kind])
            if s.circuit != kind:
                raise ValueError(
                    f"surrogate trained for circuit {s.circuit!r} bound to "
                    f"layer kind {kind!r}")
            lib[kind] = s
        return SurrogateLibrary(lib)

    def _validate_edges(self):
        spec = self.spec
        n = spec.n_layers
        for e in spec.edges:
            if not (0 <= e.src < n and 0 <= e.dst < n):
                raise ValueError(f"edge {e.src}->{e.dst} out of range for "
                                 f"{n} layers")
            dst = spec.layers[e.dst]
            want = (spec.layers[e.src].n_out,
                    dst.n_out if dst.circuit == "lif" else dst.fan_in)
            got = tuple(np.shape(e.weight))
            if got != want:
                raise ValueError(
                    f"edge {e.src}->{e.dst} weight shape {got} != {want} "
                    f"(src n_out, dst {'n_out' if dst.circuit == 'lif' else 'fan_in'})")

    # --- public entry point ---------------------------------------------------

    def run(self, inputs, *, surrogates=None) -> NetworkRun:
        """inputs: (T, B, n_in) per-tick stimulus in the first layer's native
        units (spike amplitudes for lif, DAC volts for crossbar); a 2-D
        (B, n_in) input is promoted to one combinational wave (T=1).

        ``surrogates`` overrides the engine-bound library for THIS run only:
        because surrogates are traced arguments of the compiled program,
        swapping a retrained library with identical manifests/shapes reuses
        the cached executable (zero recompiles).

        Under ``jax.profiler`` the call is the host span ``lasana.run``,
        tiled by ``lasana.stimulus``, ``lasana.prepare`` (holding
        ``lasana.compile`` on a cache miss), ``lasana.execute`` and
        ``lasana.fetch`` (docs/architecture.md, "Tracing")."""
        call = self._call.n = next(self._calls)
        with _span("run", call=call) as span:
            with _span("stimulus", call=call) as stim:
                x = jnp.asarray(inputs, jnp.float32)
                stim.set_metadata(bytes=x.nbytes)
            if x.ndim == 2:
                x = x[None]
            if x.shape[-1] != self.spec.layers[0].fan_in:
                raise ValueError(f"input width {x.shape[-1]} != layer-0 "
                                 f"fan_in {self.spec.layers[0].fan_in}")
            span.set_metadata(ticks=x.shape[0], batch=x.shape[1])
            return self._run(x, surrogates=surrogates)

    def run_stream(self, stimulus, *, chunk_ticks: Optional[int] = None,
                   surrogates=None) -> NetworkRun:
        """Streaming-chunked :meth:`run`: same record, bounded memory.

        The T axis is cut into ``chunk_ticks``-tick chunks; each chunk
        runs through one donated-carry compiled program (chunk-to-chunk
        state and surrogate leaves are aliased in place, never copied)
        while the PREVIOUS chunk's per-tick records stream to the host —
        device compute and host fetch double-buffer. The merged
        :class:`NetworkRun` is bit-identical to ``run()`` on the full
        stimulus: identical per-tick energy/latency/events, identical
        spike counts, and the end-of-run idle flush charged exactly once
        at the true stream end. At most two chunk programs compile (full
        chunk + remainder when ``T % chunk_ticks != 0``) regardless of
        stream length, so unbounded-T simulation runs at steady-state
        speed in bounded device memory.

        stimulus    (T, B, fan_in) array — sliced into chunks — or an
                    iterator of (t_i, B, fan_in) blocks (e.g. a host
                    generator producing stimulus on the fly); blocks are
                    re-buffered to ``chunk_ticks`` when it is given.
        chunk_ticks ticks per chunk (default: one chunk = whole stimulus).
        surrogates  as :meth:`run`; additionally an *iterator* of
                    surrogate libraries hot-swaps predictor weights per
                    chunk (``None`` entries / exhaustion hold the last) —
                    equal-structure swaps reuse the compiled programs with
                    zero recompiles.
        """
        acc = StreamingRun()
        for chunk in self.stream(stimulus, chunk_ticks=chunk_ticks,
                                 surrogates=surrogates):
            acc.update(chunk)
        return acc.result()

    def stream(self, stimulus, *, chunk_ticks: Optional[int] = None,
               surrogates=None, checkpoint_every: Optional[int] = None,
               resume_from=None):
        """Generator variant of :meth:`run_stream` for live consumers.

        Yields one :class:`NetworkRun` per chunk as its records land on
        the host (chunk ``k`` is fetched while chunk ``k+1`` computes);
        only the final chunk carries ``flush_energy``. Feed the records to
        :class:`StreamingRun` / :meth:`NetworkRun.merge` for the exact
        whole-run record, or consume them incrementally (dashboards,
        online monitors). Arguments as :meth:`run_stream`, plus:

        checkpoint_every  attach a resumable
                    :class:`~repro.resilience.checkpoint.StreamCheckpoint`
                    to every Nth chunk's record (``.checkpoint``; the
                    flush-bearing final chunk never carries one).
                    Requires ``chunk_ticks`` — checkpoints sit at chunk
                    boundaries so a resumed tail re-chunks (and reuses
                    the compiled chunk program) exactly. Taking a
                    checkpoint synchronizes on that chunk's carries (one
                    device fetch) — that is its entire cost.
        resume_from  a ``StreamCheckpoint`` (from a previous stream's
                    record): restore carries/offset and continue. The
                    caller re-supplies the FULL original stimulus — the
                    consumed prefix is skipped — and only post-resume
                    chunks are yielded; merge them onto
                    ``resume_from.acc_run`` (``lasana.resume`` does) for
                    the whole-run record, bit-identical to the
                    uninterrupted run.

        Argument errors (bad ``chunk_ticks``, array-stimulus shape
        mismatch, missing surrogates, checkpoint/engine mismatch) raise
        HERE, not at the first ``next()`` — a dropped or late-consumed
        generator must not hide them."""
        spec = self.spec
        if chunk_ticks is not None and chunk_ticks <= 0:
            raise ValueError(f"chunk_ticks must be positive: {chunk_ticks}")
        if checkpoint_every is not None:
            if checkpoint_every <= 0:
                raise ValueError("checkpoint_every must be positive: "
                                 f"{checkpoint_every}")
            if chunk_ticks is None:
                raise ValueError(
                    "checkpoint_every requires chunk_ticks: checkpoints "
                    "sit at chunk boundaries")
        if resume_from is not None:
            resume_from.verify_engine(self, spec)
            if chunk_ticks is None:
                chunk_ticks = resume_from.chunk_ticks
            elif chunk_ticks != resume_from.chunk_ticks:
                raise ValueError(
                    f"chunk_ticks {chunk_ticks} != checkpoint's "
                    f"{resume_from.chunk_ticks}: the resumed tail must "
                    "re-chunk exactly as the original stream")
        if hasattr(stimulus, "ndim"):
            if stimulus.ndim not in (2, 3):
                raise ValueError("stimulus must be (T, B, n_in) or "
                                 f"(B, n_in), got shape "
                                 f"{tuple(stimulus.shape)}")
            if stimulus.shape[-1] != spec.layers[0].fan_in:
                raise ValueError(f"input width {stimulus.shape[-1]} != "
                                 f"layer-0 fan_in "
                                 f"{spec.layers[0].fan_in}")
        sur_iter, static_banks = None, None
        if surrogates is not None and hasattr(surrogates, "__next__"):
            sur_iter = surrogates
        else:
            static_banks = self._runtime_banks(surrogates)
        return self._stream_gen(stimulus, chunk_ticks, static_banks,
                                sur_iter, checkpoint_every, resume_from)

    def _stream_gen(self, stimulus, chunk_ticks, static_banks, sur_iter,
                    checkpoint_every=None, resume_from=None):
        from repro.resilience import faults
        spec = self.spec
        chunks = _iter_chunks(stimulus, chunk_ticks,
                              spec.layers[0].fan_in,
                              skip_ticks=(resume_from.k0
                                          if resume_from is not None else 0))

        cur = next(chunks, None)
        if cur is None:
            raise ValueError("streaming run needs at least one stimulus "
                             "tick" + (" past the checkpoint offset"
                                       if resume_from is not None else ""))
        b = cur.shape[1]
        self._check_mesh_batch(b)
        n_layers = spec.n_layers
        last_lif = spec.circuits[-1] == "lif"
        carries = [self._init_carry(i, b) for i in range(n_layers)]
        prev_ys = [jnp.zeros((b, l.n_out), jnp.float32)
                   for l in spec.layers]
        k0 = 0
        if resume_from is not None:
            carries, prev_ys = self._restore_state(resume_from, carries,
                                                   prev_ys, b)
            k0 = int(resume_from.k0)
        banks_dev = None
        if sur_iter is None:
            banks_dev = self._donatable_banks(static_banks)

        # checkpoint bookkeeping: the accumulator mirrors every yielded
        # record so a checkpoint can carry the exact merged prefix; a
        # snapshot taken at dispatch time attaches to ITS chunk's record
        # when that record is finalized one iteration later
        acc = None
        if checkpoint_every is not None:
            acc = StreamingRun()
            if resume_from is not None:
                acc.update(resume_from.acc_run)
        ckpt_pending = None            # (carry snapshot, prev snapshot, k0)

        mark = time.time()             # segment boundary for wall split
        comp_seg = 0.0                 # compile seconds in current segment
        pending = None                 # prior chunk's device refs + meta

        def finalize(pend, flush, attach_ckpt=True):
            nonlocal mark, comp_seg, ckpt_pending
            primary, out_seq, hidden, e_tl, l_tl, ev_tl, comp_s = pend
            if not last_lif:
                out_seq = None       # unused (primary == last tick's codes):
                                     # skip the per-chunk D2H of the trace
            primary, out_seq, hidden, e_tl, l_tl, ev_tl = jax.device_get(
                (primary, out_seq, hidden, e_tl, l_tl, ev_tl))
            now = time.time()
            wall = max(now - mark - comp_seg, 0.0)
            mark, comp_seg = now, 0.0
            run = NetworkRun(
                backend=self.backend, mode=self.mode,
                outputs=np.asarray(primary),
                out_spikes=np.asarray(out_seq) if last_lif else None,
                layer_spikes=[np.asarray(h) for h in hidden]
                if self.record_hidden else None,
                energy=np.asarray(e_tl), latency=np.asarray(l_tl),
                events=np.asarray(ev_tl, np.int64),
                flush_energy=flush,
                n_circuits=np.asarray([l.n_circuits(b)
                                       for l in spec.layers]),
                clock_ns=self.clock_ns, wall_seconds=wall,
                circuits=spec.circuits, compile_seconds=comp_s)
            if acc is not None:
                acc.update(run)
                if ckpt_pending is not None and attach_ckpt:
                    snap_c, snap_p, snap_k = ckpt_pending
                    ckpt_pending = None
                    run.checkpoint = self._make_checkpoint(
                        snap_c, snap_p, snap_k, int(chunk_ticks), b, acc)
            return run

        inflight = None               # latest dispatched chunk's device refs
        try:
            while cur is not None:
                faults.stall("chunk.stall")
                x_chunk = jnp.asarray(cur, jnp.float32)
                if x_chunk.shape[1] != b:
                    raise ValueError(
                        f"stimulus chunk batch {x_chunk.shape[1]} "
                        f"!= first chunk batch {b}")
                if sur_iter is not None:
                    swap = next(sur_iter, None)
                    if swap is not None:
                        banks_dev = self._donatable_banks(
                            self._runtime_banks(swap))
                    elif banks_dev is None:
                        raise ValueError("surrogate iterator must yield a "
                                         "library for the first chunk")
                tc = x_chunk.shape[0]
                k0_arr = jnp.asarray(k0, jnp.float32)
                key = self._program_key("stream", b, tc, banks_dev)
                compiled, comp_s = self._compiled(
                    key, lambda: self._build_stream_step(b, banks_dev),
                    (x_chunk, k0_arr, carries, prev_ys, banks_dev))
                comp_seg += comp_s
                # dispatch chunk k (async), then fetch chunk k-1's records —
                # device compute and host transfer overlap (double buffering)
                outs = compiled(x_chunk, k0_arr, carries, prev_ys, banks_dev)
                inflight = outs
                carries, prev_ys, banks_dev = outs[6], outs[7], outs[8]
                if pending is not None:
                    yield finalize(pending,
                                   np.zeros((n_layers,), np.float32))
                pending = (*outs[:6], comp_s)
                k0 += tc
                if acc is not None:
                    n_chunk = k0 // int(chunk_ticks) \
                        + bool(k0 % int(chunk_ticks))
                    if n_chunk % checkpoint_every == 0:
                        # synchronizing on this chunk's carries is the
                        # checkpoint's whole cost; the snapshot attaches
                        # to this chunk's record at its finalize
                        ckpt_pending = (*jax.device_get((carries,
                                                         prev_ys)), k0)
                if k0 > 2 ** 24 and k0 - tc <= 2 ** 24:
                    # the simulator's time axis (tick index,
                    # LasanaState.t_last) is f32: past 2^24 ticks consecutive
                    # tick times collide, so tau-dependent records (merged-E2
                    # idle energy, flush) lose precision — the stream keeps
                    # running, but say so once
                    warnings.warn(
                        f"stream passed tick 2^24 ({k0} ticks): f32 tick "
                        "times can no longer distinguish consecutive ticks; "
                        "tau-dependent energy records degrade beyond here",
                        RuntimeWarning, stacklevel=2)
                cur = next(chunks, None)

            if self.backend == "lasana":
                t_ends = jnp.asarray([np.float32(k0 * c.clock_ns)
                                      for c in self.circs])
                fkey = self._program_key("flush", b, None, banks_dev)
                flush_fn, comp_s = self._compiled(
                    fkey, lambda: self._build_flush(b, banks_dev),
                    (carries, t_ends, banks_dev))
                comp_seg += comp_s
                flush = np.asarray(jax.device_get(
                    flush_fn(carries, t_ends, banks_dev)))
            else:
                flush = np.zeros((n_layers,), np.float32)
            # the final chunk never carries a checkpoint: its record holds
            # the end-of-run flush, which a resumed tail would re-charge
            yield finalize(pending, flush, attach_ckpt=False)
        finally:
            # a consumer that breaks / cancels mid-stream closes this
            # generator at a yield with one chunk still in flight on
            # device; drain it before dropping the refs so the donated
            # carries settle and the engine is immediately reusable
            if inflight is not None:
                jax.block_until_ready(inflight)

    def _restore_state(self, ckpt, init_carries, init_prev, b: int):
        """Rebuild device carries/prev_ys from a checkpoint's host leaves.

        ``init_carries``/``init_prev`` are fresh tick-0 structures for
        batch ``b`` — they supply the pytree treedefs (and the shape
        oracle) that the flat npz leaves are poured back into. Shape
        mismatches fail loudly here, at resume, not as silent divergence
        mid-stream."""
        if ckpt.batch != b:
            raise ValueError(f"checkpoint batch {ckpt.batch} != stimulus "
                             f"batch {b}")
        flat, treedef = jax.tree_util.tree_flatten(init_carries)
        if len(ckpt.carry_leaves) != len(flat):
            raise ValueError(
                f"checkpoint has {len(ckpt.carry_leaves)} carry leaves, "
                f"engine expects {len(flat)} — different network or "
                "backend")
        leaves = []
        for ref, leaf in zip(flat, ckpt.carry_leaves):
            if tuple(ref.shape) != tuple(np.shape(leaf)):
                raise ValueError(
                    f"checkpoint carry leaf shape {tuple(np.shape(leaf))} "
                    f"!= engine's {tuple(ref.shape)}")
            leaves.append(jnp.asarray(leaf, ref.dtype))
        carries = jax.tree_util.tree_unflatten(treedef, leaves)
        if len(ckpt.prev_ys) != len(init_prev):
            raise ValueError(
                f"checkpoint has {len(ckpt.prev_ys)} prev_ys entries, "
                f"engine expects {len(init_prev)}")
        prev_ys = []
        for ref, p in zip(init_prev, ckpt.prev_ys):
            if tuple(ref.shape) != tuple(np.shape(p)):
                raise ValueError(
                    f"checkpoint prev_ys shape {tuple(np.shape(p))} != "
                    f"engine's {tuple(ref.shape)}")
            prev_ys.append(jnp.asarray(p, jnp.float32))
        return carries, prev_ys

    def _make_checkpoint(self, snap_carries, snap_prev, k0: int,
                         chunk_ticks: int, b: int, acc):
        """Freeze one dispatch-time snapshot into a StreamCheckpoint."""
        from repro.resilience.checkpoint import StreamCheckpoint, spec_key_of
        leaves = [np.asarray(l)
                  for l in jax.tree_util.tree_flatten(snap_carries)[0]]
        return StreamCheckpoint(
            k0=int(k0), chunk_ticks=int(chunk_ticks), batch=int(b),
            spec_key=spec_key_of(self.spec), backend=self.backend,
            mode=self.mode, record_hidden=self.record_hidden,
            carry_leaves=leaves,
            prev_ys=[np.asarray(p) for p in snap_prev],
            acc_run=acc.result())

    @staticmethod
    def _donatable_banks(banks):
        """Private on-device copy of a surrogate library.

        The streaming chunk program DONATES its surrogate leaves (they
        alias straight through to the next chunk), and donation
        invalidates the caller's buffers — so the stream works on its own
        copy and the user's surrogate stays usable."""
        return jax.tree.map(lambda a: jnp.array(a, copy=True), banks)

    # --- per-layer state ------------------------------------------------------

    def _xbar_row_params(self, i: int, b: int):
        layer = self.spec.layers[i]
        segs = jnp.asarray(_row_segments(layer.weight, layer.seg_width))
        return jnp.broadcast_to(segs[None], (b, *segs.shape)
                                ).reshape(-1, layer.seg_width + 1)

    def _init_carry(self, i: int, b: int):
        layer = self.spec.layers[i]
        circ = self.circs[i]
        if layer.circuit == "crossbar":
            n_rows = layer.n_circuits(b)
            pall = self._xbar_row_params(i, b)
            if self.backend == "golden":
                return circ.init_state(n_rows), pall    # ((n_rows, 1), ...)
            if self.backend == "behavioral":
                return jnp.zeros((n_rows,), jnp.float32), pall
            return init_state(n_rows, pall)
        n = layer.n_circuits(b)
        params = _tile_params(layer.params, b, layer.n_out)
        if self.backend == "golden":
            return circ.init_state(n), params
        if self.backend == "behavioral":
            return jnp.zeros((n,), jnp.float32), params
        # lasana: annotation mode keeps the behavioral voltage in .v
        return init_state(n, params)

    # --- per-layer tick functions ---------------------------------------------

    def _lif_tick(self, i: int, slot_records: bool = False):
        """Returns tick(carry, drive, changed, k, bank, pack, layout) ->
        (carry', spikes (B, n), e, l, events, stale); ``drive`` is the
        pre-combined synaptic drive and ``bank`` the layer kind's (traced)
        Surrogate, None outside the lasana backend. ``pack``/``layout``
        are the kind's megakernel head pack (built once per program call
        by :meth:`_mk_pack`) or None for the stacked-dispatch path.
        ``slot_records`` switches the event count from one scalar to a
        per-batch-slot (B,) int32 vector (the continuous-batching server
        attributes records per tenant; layouts are batch-major).
        ``stale`` is a bool scalar: whether some circuit is stale
        (:func:`stale_circuits`), due Algorithm 1's idle catch-up; False
        outside the lasana backend."""
        layer = self.spec.layers[i]
        amp = self.spec.spike_amp
        circ = self.circs[i]
        clock = circ.clock_ns
        n_out = layer.n_out
        backend, mode = self.backend, self.mode
        fused = self.fused
        fused_kernel = self.fused_kernel

        def tick(carry, drive, changed, k, bank, pack=None, layout=None):
            # drive is (B_local, n_out): under shard_map the batch dim is
            # shard-local, so every shape below derives from the input
            t = (k + 1.0) * clock
            with jax.named_scope("drive"):
                xin = drive_to_circuit_inputs(drive, spike_amp=amp
                                              ).reshape(-1, 3)
            stale = _any_stale(backend, carry, changed, t, clock)

            if backend == "golden":
                state, params = carry
                new_state, obs = circ.step(state, xin, params)
                spikes = jnp.where(obs["spiked"], amp, 0.0)
                e, l = obs["energy"], jnp.where(obs["spiked"],
                                                obs["latency"], 0.0)
                carry = (new_state, params)
            elif backend == "behavioral":
                v, params = carry
                xin_m = jnp.where(changed[:, None], xin, 0.0)
                v_new, out = circ.behavioral_step(v, xin_m, params)
                spikes = out
                e = jnp.zeros_like(v)
                l = jnp.zeros_like(v)
                carry = (v_new, params)
            elif mode == "annotation":
                xin_m = jnp.where(changed[:, None], xin, 0.0)
                v_new, out = circ.behavioral_step(carry.v, xin_m,
                                                  carry.params)
                ns, e, l, _ = lasana_step(bank, carry, changed, xin, t,
                                          clock, spiking=True, vdd=amp,
                                          known_out=out, fused=fused,
                                          fused_kernel=fused_kernel,
                                          megakernel_pack=pack,
                                          megakernel_layout=layout)
                spikes = out
                carry = ns._replace(v=v_new, o=out)
            else:                                           # standalone
                ns, e, l, o = lasana_step(bank, carry, changed, xin, t,
                                          clock, spiking=True, vdd=amp,
                                          fused=fused,
                                          fused_kernel=fused_kernel,
                                          megakernel_pack=pack,
                                          megakernel_layout=layout)
                with jax.named_scope("update"):
                    spikes = jnp.where(changed, o, 0.0)
                carry = ns

            with jax.named_scope("update"):
                spikes = spikes.reshape(-1, n_out)
                if slot_records:
                    ev = jnp.sum(changed.reshape(spikes.shape[0], -1),
                                 axis=1, dtype=jnp.int32)
                else:
                    ev = _count_events(changed)
            return carry, spikes, e, l, ev, stale

        return tick

    def _xbar_tick(self, i: int, slot_records: bool = False):
        """Returns tick(carry, x_volts (B, fan_in), k, bank, pack, layout)
        -> (carry', codes (B, n_out), e, l, events, stale); ``bank``/
        ``pack``/``layout``/``slot_records``/``stale`` as in
        :meth:`_lif_tick`.

        Rows are combinational with sample-and-hold inputs: a row-segment
        fires an input event iff any of its input lines is live (|x| > eps)
        this tick; event-less rows hold their previous settled output."""
        layer = self.spec.layers[i]
        circ = self.circs[i]
        seg_w, n_seg, n_out = layer.seg_width, layer.n_seg, layer.n_out
        fan_in = layer.fan_in
        clock = circ.clock_ns
        gain = -circ.r_f * circ.g_unit
        levels = 2 ** layer.adc_bits - 1
        backend, mode = self.backend, self.mode
        fused = self.fused
        fused_kernel = self.fused_kernel
        # row parameters per (output, segment): every lane's rows share
        # them (the carry holds them tiled per lane)
        rows = _row_segments(layer.weight, seg_w).reshape(n_out, n_seg, -1)

        def by_blocks(bank, pack) -> bool:
            from repro.kernels import ops
            return (backend == "lasana" and mode == "standalone" and fused
                    and pack is None
                    and not ops.fused_kernel_enabled(fused_kernel)
                    and row_blocks_ok(bank))

        def tick(carry, x, k, bank, pack=None, layout=None):
            # x is (B_local, fan_in) volts: under shard_map the batch dim is
            # shard-local, so every shape below derives from the input; the
            # per-row path's row params ride in the carry so they shard
            # with the rows
            b_l = x.shape[0]
            t = (k + 1.0) * clock
            with jax.named_scope("drive"):
                xp = jnp.pad(x, ((0, 0), (0, n_seg * seg_w - fan_in)))
                x_seg = xp.reshape(b_l, n_seg, seg_w)
                live = jnp.any(jnp.abs(x_seg) > _XBAR_EVENT_EPS, axis=-1)
                changed = jnp.broadcast_to(live[:, None],
                                           (b_l, n_out, n_seg)).reshape(-1)
            stale = _any_stale(backend, carry, changed, t, clock)

            if by_blocks(bank, pack):
                rb = RowBlocks(x=x_seg[:, None],
                               params=jnp.asarray(rows)[None])
                ns, e, l, _ = lasana_step(bank, carry, changed, rb, t, clock)
                carry, v = ns, ns.o
            else:
                with jax.named_scope("drive"):
                    xin = jnp.broadcast_to(x_seg[:, None],
                                           (b_l, n_out, n_seg, seg_w)
                                           ).reshape(-1, seg_w)
                if backend == "golden":
                    state, pall = carry
                    v_prev = state[:, 0]
                    _, obs = circ.step(state, xin, pall)
                    v = jnp.where(changed, obs["output"], v_prev)
                    e = jnp.where(changed, obs["energy"], 0.0)
                    l = jnp.where(changed, obs["latency"], 0.0)
                    carry = (v[:, None], pall)
                elif backend == "behavioral":
                    held, pall = carry
                    _, settled = circ.behavioral_step(held, xin, pall)
                    v = jnp.where(changed, settled, held)
                    e = jnp.zeros_like(v)
                    l = jnp.zeros_like(v)
                    carry = (v, pall)
                else:
                    known = None
                    if mode == "annotation":
                        _, known = circ.behavioral_step(carry.v, xin,
                                                        carry.params)
                    ns, e, l, _ = lasana_step(bank, carry, changed, xin, t,
                                              clock, known_out=known,
                                              fused=fused,
                                              fused_kernel=fused_kernel,
                                              megakernel_pack=pack,
                                              megakernel_layout=layout)
                    if known is not None:
                        # behavioral value is both published output and state
                        ns = ns._replace(v=ns.o)
                    carry = ns
                    v = ns.o

            with jax.named_scope("update"):
                # adc_bits ADC over [-v_sat, v_sat], then digital gain comp
                v_adc = (jnp.round((v + circ.v_sat) / (2 * circ.v_sat)
                                   * levels)
                         / levels * 2 * circ.v_sat - circ.v_sat)
                y = v_adc.reshape(-1, n_out, n_seg).sum(-1) / gain
                if slot_records:
                    ev = jnp.sum(changed.reshape(b_l, -1),
                                 axis=1, dtype=jnp.int32)
                else:
                    ev = _count_events(changed)
            return carry, y, e, l, ev, stale

        return tick

    def _flush(self, carry, i: int, t_end_ns, bank):
        """Charge trailing-idle static energy (merged E2 to the run end).

        ``t_end_ns`` is the run-end time in the layer's native clock units
        — a Python float in the monolithic program (baked constant), a
        traced f32 scalar in the streaming flush program (one program
        serves every total-T, so chunk-count changes never recompile).

        Only stateful event-driven kinds (lif) are flushed: combinational
        sample-and-hold crossbar rows charge nothing in the golden
        reference while their inputs are dead, so predicting M_ES static
        energy for their idle tail would break golden comparability."""
        if self.backend != "lasana":
            return jnp.zeros(())
        if self.spec.layers[i].circuit == "crossbar":
            return jnp.zeros(())
        circ = self.circs[i]
        lst = carry
        tau = t_end_ns - lst.t_last
        n_in = circ.n_inputs
        feats = jnp.concatenate(
            [jnp.zeros((lst.v.shape[0], n_in), jnp.float32),
             lst.v[:, None], tau[:, None], lst.params], axis=1)
        e = bank.predict("M_ES", feats)
        return jnp.sum(jnp.where(tau > 0, e, 0.0))

    # --- the unified graph builder --------------------------------------------

    def _make_cascade(self, slot_records: bool = False):
        """Build the one-network-tick cascade shared by every program.

        Returns ``cascade(banks, carries, prev_ys, u_in, k) ->
        (new_carries, new_ys, e (L,), l (L,), events (L,) int32,
        stale (L,) bool)`` — the exact per-tick dataflow (adapters, event
        detection, bank steps); ``stale`` marks the layers with a stale
        circuit (:func:`stale_circuits`).
        The monolithic program and the streaming chunk program both scan
        THIS closure, which is what makes chunked runs bit-identical to
        monolithic ones.

        ``slot_records=True`` is the continuous-batching variant (the
        slot-masked programs behind :meth:`slot_programs`): energy /
        latency / event reductions stay per batch slot — ``(L, B)``
        instead of ``(L,)`` — and the cascade accepts an extra
        ``live (B,)`` bool mask. Non-live slots are frozen: their LIF
        event detection is forced off and their crossbar input volts are
        zeroed (below the sample-and-hold event epsilon), so a dead or
        empty slot processes no events, charges no energy, and holds its
        carry — which is exactly what keeps each multiplexed request
        bit-identical to running alone."""
        spec = self.spec
        n_layers = spec.n_layers
        kinds = spec.circuits
        amp = spec.spike_amp
        ticks = [self._lif_tick(i, slot_records) if kinds[i] == "lif"
                 else self._xbar_tick(i, slot_records)
                 for i in range(n_layers)]

        # pre-resolved connection tables (weights, connectivity masks,
        # adapter arguments) — one entry per incoming connection per layer
        ff_conn = []                   # lif layers: (|w| > 0) masks
        rec = [[] for _ in range(n_layers)]
        for i in range(n_layers):
            w = spec.layers[i].weight
            ff_conn.append((jnp.abs(w) > 0).astype(jnp.float32)
                           if kinds[i] == "lif" else None)
            for e in spec.edges_into(i):
                we = jnp.asarray(e.weight, jnp.float32)
                # connectivity mask feeds lif event detection only; crossbar
                # destinations detect events from live input lines instead
                conn = ((jnp.abs(we) > 0).astype(jnp.float32)
                        if kinds[i] == "lif" else None)
                rec[i].append((e.src, we, conn))

        def src_activation(src_idx: Optional[int]) -> str:
            if src_idx is None:
                return "tanh"
            return spec.layers[src_idx].activation

        def cascade(banks, carries, prev_ys, u_in, k, packs=None,
                    live=None):
            packs = packs or {}
            bsz = u_in.shape[0]
            cur, src_kind, src_idx = u_in, "input", None
            new_carries, new_ys = [], []
            es, ls, evs, stales = [], [], [], []
            for i in range(n_layers):
                layer = spec.layers[i]
                pk, ly = packs.get(kinds[i], (None, None))
                if kinds[i] == "lif":
                    with jax.named_scope("drive"):
                        # combine feed-forward + delayed-edge synaptic drive
                        u = adapt_signal(src_kind, "lif", cur, spike_amp=amp,
                                         activation=src_activation(src_idx))
                        drive = (u @ layer.weight) / amp
                        pre = (jnp.abs(u) > event_threshold(src_kind, amp)
                               ).astype(jnp.float32)
                        incoming = (pre @ ff_conn[i]) > 0.5
                        for src, we, conn in rec[i]:
                            with jax.named_scope("edges"):
                                ur = adapt_signal(
                                    kinds[src], "lif", prev_ys[src],
                                    spike_amp=amp,
                                    activation=src_activation(src))
                                drive = drive + (ur @ we) / amp
                                pr = (jnp.abs(ur)
                                      > event_threshold(kinds[src], amp)
                                      ).astype(jnp.float32)
                                incoming = incoming | ((pr @ conn) > 0.5)
                        if live is not None:
                            incoming = incoming & live[:, None]
                        changed = incoming.reshape(-1)
                    carry, y, e, l, ev, st = ticks[i](carries[i], drive,
                                                      changed, k,
                                                      banks.get(kinds[i]),
                                                      pk, ly)
                else:
                    circ = self.circs[i]
                    with jax.named_scope("drive"):
                        xv = adapt_signal(src_kind, "crossbar", cur,
                                          spike_amp=amp,
                                          activation=src_activation(src_idx))
                        for src, we, _ in rec[i]:
                            with jax.named_scope("edges"):
                                xv = xv + adapt_signal(
                                    kinds[src], "crossbar", prev_ys[src],
                                    spike_amp=amp,
                                    activation=src_activation(src)) @ we
                        xv = jnp.clip(xv, circ.input_lo, circ.input_hi)
                        if live is not None:
                            xv = jnp.where(live[:, None], xv, 0.0)
                    carry, y, e, l, ev, st = ticks[i](carries[i], xv, k,
                                                      banks.get(kinds[i]),
                                                      pk, ly)
                new_carries.append(carry)
                new_ys.append(y)
                with jax.named_scope("update"):
                    if slot_records:   # per-tenant attribution: per slot
                        es.append(jnp.sum(e.reshape(bsz, -1), axis=1))
                        ls.append(jnp.max(l.reshape(bsz, -1), axis=1))
                    else:
                        es.append(jnp.sum(e))
                        ls.append(jnp.max(l))
                evs.append(ev)
                stales.append(st)
                cur, src_kind, src_idx = y, kinds[i], i
            with jax.named_scope("update"):
                records = (jnp.stack(es), jnp.stack(ls), jnp.stack(evs),
                           jnp.stack(stales))
            return (new_carries, new_ys, *records)

        return cascade

    def _mk_pack(self, banks):
        """``{kind: (pack, PackLayout)}`` for the megakernel hot path.

        Empty unless the engine runs the lasana fused path AND the
        fused-kernel switch resolves on (``fused_kernel=`` override, else
        ``REPRO_FUSED_KERNEL``). Prefers ONE cross-kind
        ``pack_library`` pack (every kind shares a resident weight block,
        addressed by static offsets); if any kind is ineligible, packable
        kinds still get their own single-kind packs and the rest fall back
        to stacked dispatch inside ``lasana_step``."""
        if self.backend != "lasana" or not self.fused:
            return {}
        from repro.kernels import ops
        if not ops.fused_kernel_enabled(self.fused_kernel):
            return {}
        from repro.kernels import tick_megakernel as mk
        pack, layouts = mk.pack_library(banks)
        if pack is not None:
            return {kind: (pack, lo) for kind, lo in layouts.items()}
        packs = {}
        for kind in banks.kinds():
            p, lo = mk.pack_heads(banks.get(kind))
            if p is not None:
                packs[kind] = (p, lo)
        return packs

    def _chunk_eligible(self) -> bool:
        """Whether :meth:`_chunk_fast_path` can replace the generic scan:
        a single-LIF-layer standalone lasana graph with no delayed edges
        (the cascade then has no cross-layer or cross-tick dataflow beyond
        the LIF carry itself, which the time-looped kernel owns)."""
        spec = self.spec
        return (self.backend == "lasana" and self.mode == "standalone"
                and self.fused and spec.n_layers == 1
                and spec.circuits == ("lif",) and not spec.edges)

    def _chunk_fast_path(self, pack_layout, carries, input_seq, ks):
        """The whole chunk as ONE time-looped megakernel.

        Event detection and synaptic drive vectorize over the chunk up
        front (they have no tick-to-tick dependence); the LIF carry — the
        only sequential dataflow — then advances inside
        ``megakernel_chunk``, whose jnp body is a ``lax.scan`` of the
        exact per-tick step (bit-identical to the generic scan) and whose
        Pallas body keeps v/o/t_last VMEM-resident across the chunk.
        Returns the same ``((carries, prev_ys), outs)`` as the scan, with
        None for its stale flags."""
        from repro.kernels.tick_megakernel import megakernel_chunk
        spec = self.spec
        layer = spec.layers[0]
        amp = spec.spike_amp
        clock = self.circs[0].clock_ns
        pack, layout = pack_layout
        t_steps, b = input_seq.shape[0], input_seq.shape[1]

        u = input_seq                       # "input" -> lif is the identity
        drive = (u @ layer.weight) / amp
        conn = (jnp.abs(layer.weight) > 0).astype(jnp.float32)
        pre = (jnp.abs(u) > event_threshold("input", amp)
               ).astype(jnp.float32)
        changed_seq = ((pre @ conn) > 0.5).reshape(t_steps, -1)
        xin_seq = drive_to_circuit_inputs(drive, spike_amp=amp
                                          ).reshape(t_steps, -1, 3)
        t_seq = (ks + 1.0) * clock
        new_state, o_seq, e_seq, l_seq = megakernel_chunk(
            pack, layer.circuit, carries[0], changed_seq, xin_seq, t_seq,
            clock, spiking=True, vdd=amp, layout=layout)
        spikes = jnp.where(changed_seq, o_seq, 0.0
                           ).reshape(t_steps, b, layer.n_out)
        es = jnp.sum(e_seq, axis=1)[:, None]
        ls = jnp.max(l_seq, axis=1)[:, None]
        evs = jnp.sum(changed_seq, axis=1, dtype=jnp.int32)[:, None]
        # the stale flags are the scan's alone (None: not counted here)
        out = (spikes, (spikes,) if self.record_hidden else (), es, ls, evs,
               None)
        return ([new_state], [spikes[-1]]), out

    def _scan_chunk(self, cascade, banks, carries, prev_ys, input_seq, ks):
        """lax.scan the cascade over one contiguous block of ticks.

        Megakernel head packs are built HERE, once per program call and
        OUTSIDE the scan, from the traced surrogate leaves — so the pack
        rides the hot-swap contract (retrained weights reuse the program)
        without rebuilding per tick. Eligible single-layer graphs skip the
        scan entirely for the time-looped :meth:`_chunk_fast_path`."""
        record_hidden = self.record_hidden
        packs = self._mk_pack(banks)
        if "lif" in packs and self._chunk_eligible():
            return self._chunk_fast_path(packs["lif"], carries,
                                         input_seq, ks)

        def tick(state, xs):
            carries, prev_ys = state
            u_in, k = xs
            new_carries, new_ys, es, ls, evs, stales = cascade(
                banks, carries, prev_ys, u_in, k, packs)
            out = (new_ys[-1],
                   tuple(new_ys) if record_hidden else (),
                   es, ls, evs, stales)
            return (new_carries, new_ys), out

        return jax.lax.scan(tick, (list(carries), list(prev_ys)),
                            (input_seq, ks))

    def _shard_specs(self, b: int, banks):
        """(carry, prev, seq, hidden, bank) PartitionSpecs for shard_map."""
        mesh = self.mesh
        cspec = batch_spec(mesh)                     # flattened (B*n,) arrays
        carry_specs = [jax.tree.map(lambda _: cspec, self._init_carry(i, b))
                       for i in range(self.spec.n_layers)]
        bspec2 = batch_spec(mesh, ndim=2)
        prev_specs = [bspec2 for _ in range(self.spec.n_layers)]
        seq_spec = batch_spec(mesh, ndim=3, axis=1)
        hidden_spec = tuple(seq_spec for _ in range(self.spec.n_layers)) \
            if self.record_hidden else ()
        # predictor weights replicate across the mesh (batch is the only
        # sharded axis); they still enter as traced arguments
        bank_specs = jax.tree.map(lambda _: P_REPL, banks)
        return carry_specs, prev_specs, bspec2, seq_spec, hidden_spec, \
            bank_specs

    def _build_sim(self, b: int, banks: SurrogateLibrary):
        """Build the jitted monolithic network program for batch ``b``.

        ``banks`` is used only for its pytree *structure* (shard specs);
        the returned program takes the library as a traced argument."""
        spec = self.spec
        n_layers = spec.n_layers
        kinds = spec.circuits
        amp = spec.spike_amp
        cascade = self._make_cascade()
        last_lif = kinds[-1] == "lif"
        sharded = self.mesh is not None
        axes = tuple(self.mesh.axis_names) if sharded else ()

        def sim(input_seq, carries, prev0, banks):
            self._trace_count += 1
            t_steps = input_seq.shape[0]
            ks = jnp.arange(t_steps, dtype=jnp.float32)
            (carries, _), (out_seq, hidden, e_tl, l_tl, ev_tl, st_tl) = \
                self._scan_chunk(cascade, banks, carries, prev0,
                                 input_seq, ks)
            if last_lif:
                primary = jnp.sum(out_seq > 0.5 * amp, axis=0)
            else:
                primary = out_seq[-1]
            with jax.named_scope("flush"):
                flush = jnp.stack([
                    self._flush(carries[i], i,
                                t_steps * self.circs[i].clock_ns,
                                banks.get(kinds[i]))
                    for i in range(n_layers)])
            if sharded:        # diagnostics are the only collectives
                e_tl = jax.lax.psum(e_tl, axes)
                l_tl = jax.lax.pmax(l_tl, axes)
                ev_tl = jax.lax.psum(ev_tl, axes)
                flush = jax.lax.psum(flush, axes)
                if st_tl is not None:
                    st_tl = jax.lax.pmax(st_tl.astype(jnp.int32), axes) > 0
            return primary, out_seq, hidden, e_tl, l_tl, ev_tl, flush, st_tl

        if not sharded:
            return jax.jit(sim)

        carry_specs, prev_specs, bspec2, seq_spec, hidden_spec, bank_specs \
            = self._shard_specs(b, banks)
        out_specs = (bspec2, seq_spec, hidden_spec,
                     P_REPL, P_REPL, P_REPL, P_REPL, P_REPL)
        return shard_over_batch(
            sim, self.mesh,
            in_specs=(seq_spec, carry_specs, prev_specs, bank_specs),
            out_specs=out_specs)

    def _build_stream_step(self, b: int, banks: SurrogateLibrary):
        """Build the donated-carry chunk program for the streaming path.

        ``step(input_seq, k0, carries, prev_ys, banks)`` runs one chunk of
        ticks starting at global tick ``k0`` (a traced f32 scalar — chunk
        position never recompiles) and returns

            (primary, out_seq, hidden, e_tl, l_tl, ev_tl,
             new_carries, new_prev_ys, banks)

        with ``carries``/``prev_ys``/``banks`` DONATED: XLA aliases the
        chunk-to-chunk state (and the surrogate leaves) in place, so an
        unbounded-T stream runs in bounded device memory with zero
        per-chunk copies of state or predictor weights. ``primary`` is the
        chunk-local reduction of the monolithic program's primary output
        (per-chunk spike counts for a spiking last layer, last-tick codes
        otherwise) so :class:`StreamingRun` can merge exactly."""
        spec = self.spec
        amp = spec.spike_amp
        cascade = self._make_cascade()
        last_lif = spec.circuits[-1] == "lif"
        sharded = self.mesh is not None
        axes = tuple(self.mesh.axis_names) if sharded else ()

        def step(input_seq, k0, carries, prev_ys, banks):
            self._trace_count += 1
            t_steps = input_seq.shape[0]
            ks = k0 + jnp.arange(t_steps, dtype=jnp.float32)
            (carries, prev_ys), (out_seq, hidden, e_tl, l_tl, ev_tl, _) = \
                self._scan_chunk(cascade, banks, carries, prev_ys,
                                 input_seq, ks)
            if last_lif:
                primary = jnp.sum(out_seq > 0.5 * amp, axis=0)
            else:
                primary = out_seq[-1]
            if sharded:        # diagnostics are the only collectives
                e_tl = jax.lax.psum(e_tl, axes)
                l_tl = jax.lax.pmax(l_tl, axes)
                ev_tl = jax.lax.psum(ev_tl, axes)
            return (primary, out_seq, hidden, e_tl, l_tl, ev_tl,
                    carries, prev_ys, banks)

        donate = (2, 3, 4)             # carries, prev_ys, surrogate leaves
        if not sharded:
            return jax.jit(step, donate_argnums=donate)

        carry_specs, prev_specs, bspec2, seq_spec, hidden_spec, bank_specs \
            = self._shard_specs(b, banks)
        return shard_over_batch(
            step, self.mesh,
            in_specs=(seq_spec, P_REPL, carry_specs, prev_specs, bank_specs),
            out_specs=(bspec2, seq_spec, hidden_spec, P_REPL, P_REPL, P_REPL,
                       carry_specs, prev_specs, bank_specs),
            donate_argnums=donate)

    def _build_flush(self, b: int, banks: SurrogateLibrary):
        """Build the end-of-stream flush program.

        ``flush_fn(carries, t_ends, banks) -> (L,)`` charges the trailing
        idle static energy from the FINAL carries, with ``t_ends`` the
        per-layer run-end times (f32, layer-native clocks) as traced
        scalars — one compiled flush serves every stream length. Runs the
        same :meth:`_flush` math the monolithic program embeds, applied
        exactly once at the true end of the stream."""
        spec = self.spec
        kinds = spec.circuits
        n_layers = spec.n_layers
        sharded = self.mesh is not None

        def flush_fn(carries, t_ends, banks):
            with jax.named_scope("flush"):
                flush = jnp.stack([self._flush(carries[i], i, t_ends[i],
                                               banks.get(kinds[i]))
                                   for i in range(n_layers)])
            if sharded:
                flush = jax.lax.psum(flush, tuple(self.mesh.axis_names))
            return flush

        if not sharded:
            return jax.jit(flush_fn)
        carry_specs, _, _, _, _, bank_specs = self._shard_specs(b, banks)
        return shard_over_batch(flush_fn, self.mesh,
                                in_specs=(carry_specs, P_REPL, bank_specs),
                                out_specs=P_REPL)

    # --- continuous-batching slot programs (the serving layer) ----------------

    def _build_slot_step(self, b: int, banks: SurrogateLibrary):
        """Build the slot-masked chunk program for continuous batching.

        ``step(input_seq, k0, end_ks, carries, prev_ys, banks)`` is the
        streaming chunk program with two serving extensions:

          * ``end_ks (b,)`` f32 — each slot's *global end tick*; at tick
            ``k`` only slots with ``k < end_ks[slot]`` are live.  Dead
            slots (request finished mid-chunk, or seat empty) are frozen
            by the cascade's ``live`` mask: no events, no energy, carry
            held — so one compiled program serves every mix of request
            lengths without per-request padding artifacts.
          * per-slot records — energy/latency ``(T, L, b)`` and event
            counts ``(T, L, b)`` int32 stay per batch slot, so the
            scheduler can slice each tenant's rows out of the shared
            batch and the merged per-request :class:`NetworkRun` is
            bit-identical (rtol 1e-5 on f32 energy sums) to running that
            request alone.

        ``carries``/``prev_ys``/``banks`` are DONATED exactly as in
        :meth:`_build_stream_step`."""
        spec = self.spec
        amp = spec.spike_amp
        cascade = self._make_cascade(slot_records=True)
        last_lif = spec.circuits[-1] == "lif"
        record_hidden = self.record_hidden

        def step(input_seq, k0, end_ks, carries, prev_ys, banks):
            self._trace_count += 1
            t_steps = input_seq.shape[0]
            ks = k0 + jnp.arange(t_steps, dtype=jnp.float32)
            packs = self._mk_pack(banks)

            def tick(state, xs):
                carries, prev_ys = state
                u_in, k = xs
                live = k < end_ks
                new_carries, new_ys, es, ls, evs, _ = cascade(
                    banks, carries, prev_ys, u_in, k, packs, live=live)
                out = (new_ys[-1],
                       tuple(new_ys) if record_hidden else (),
                       es, ls, evs)
                return (new_carries, new_ys), out

            (carries, prev_ys), (out_seq, hidden, e_tl, l_tl, ev_tl) = \
                jax.lax.scan(tick, (list(carries), list(prev_ys)),
                             (input_seq, ks))
            if last_lif:
                primary = jnp.sum(out_seq > 0.5 * amp, axis=0)
            else:
                primary = out_seq
            return (primary, out_seq, hidden, e_tl, l_tl, ev_tl,
                    carries, prev_ys, banks)

        return jax.jit(step, donate_argnums=(3, 4, 5))

    def _build_slot_flush(self, b: int, banks: SurrogateLibrary):
        """Build the per-slot leave-time flush program.

        ``flush_fn(carries, t_ends, banks) -> (L, b)`` is :meth:`_flush`
        with a per-layer per-slot end time ``t_ends (L, b)`` (f32,
        layer-native clocks) and per-slot energy sums — when a request
        leaves its slots mid-stream, the scheduler charges ITS trailing
        idle energy from the live carries without disturbing the other
        tenants (the carries are read, not donated). Slots whose
        ``t_ends`` entry is in the past (tau <= 0, e.g. every slot not
        owned by the leaving request) charge exactly zero."""
        spec = self.spec
        kinds = spec.circuits
        n_layers = spec.n_layers

        @jax.named_scope("flush")
        def flush_fn(carries, t_ends, banks):
            rows = []
            for i in range(n_layers):
                if self.backend != "lasana" or kinds[i] == "crossbar":
                    rows.append(jnp.zeros((b,), jnp.float32))
                    continue
                circ = self.circs[i]
                lst = carries[i]
                n_per = spec.layers[i].n_circuits(b) // b
                tau = jnp.repeat(t_ends[i], n_per) - lst.t_last
                feats = jnp.concatenate(
                    [jnp.zeros((lst.v.shape[0], circ.n_inputs),
                               jnp.float32),
                     lst.v[:, None], tau[:, None], lst.params], axis=1)
                e = banks.get(kinds[i]).predict("M_ES", feats)
                e = jnp.where(tau > 0, e, 0.0)
                rows.append(jnp.sum(e.reshape(b, -1), axis=1))
            return jnp.stack(rows)

        return jax.jit(flush_fn)

    def _build_slot_join(self, b: int):
        """Build the masked slot (re)initialization program.

        ``join_fn(carries, prev_ys, mask, g0) -> (carries, prev_ys)``
        resets the slots selected by ``mask (b,)`` to a fresh request
        start at global tick ``g0`` (traced f32 — joins never recompile):
        state back to :meth:`_init_carry` values, published outputs
        zeroed, and — lasana backend — ``t_last`` set to ``g0`` in each
        layer's native clock. Because simulation time enters the
        surrogate features only through ``tau = t - t_last``, a request
        whose slot starts life at offset ``g0`` sees exactly the tau
        sequence of a request started at tick 0: that time-translation
        invariance is what makes mid-stream joins bit-identical to solo
        runs. Unmasked slots pass through untouched (``carries`` /
        ``prev_ys`` are donated and alias in place)."""
        spec = self.spec
        n_layers = spec.n_layers

        def join_fn(carries, prev_ys, mask, g0):
            new_carries, new_prev = [], []
            for i in range(n_layers):
                init = self._init_carry(i, b)
                n_per = spec.layers[i].n_circuits(b) // b
                m = jnp.repeat(mask, n_per)

                def sel(new_leaf, old_leaf):
                    mm = m.reshape(m.shape[0],
                                   *([1] * (old_leaf.ndim - 1)))
                    return jnp.where(mm, new_leaf, old_leaf)

                carry = jax.tree.map(sel, init, carries[i])
                if self.backend == "lasana":
                    clock = self.circs[i].clock_ns
                    carry = carry._replace(
                        t_last=jnp.where(m, g0 * clock, carry.t_last))
                new_carries.append(carry)
                new_prev.append(jnp.where(mask[:, None], 0.0, prev_ys[i]))
            return new_carries, new_prev

        return jax.jit(join_fn, donate_argnums=(0, 1))

    def slot_programs(self, b: int, chunk_ticks: int,
                      surrogates=None) -> SlotPrograms:
        """Compile (or fetch) the continuous-batching program family.

        One :class:`SlotPrograms` per (``b``, ``chunk_ticks``, surrogate
        structure) — the serving layer's shape bucket. The scheduler owns
        the calling protocol: :meth:`_build_slot_join` seats joining
        requests, :meth:`_build_slot_step` advances all live slots one
        chunk, :meth:`_build_slot_flush` charges leavers' trailing idle
        energy. Programs are cached in the engine's AOT cache (only the
        ``step`` tick-scan counts toward :attr:`compile_count`) and take
        surrogates as traced arguments, so same-structure hot-swaps and
        multiple co-resident surrogate versions share one executable."""
        if self.backend not in ("lasana", "behavioral"):
            # behavioral is the serve layer's graceful-degradation
            # fallback (quarantined specs re-admit on the paper's
            # annotation substrate); golden stays out — its ODE stepping
            # is orders of magnitude off serving latency budgets
            raise ValueError("slot_programs requires backend='lasana' or "
                             f"'behavioral' (got {self.backend!r})")
        if self.mesh is not None:
            raise ValueError("slot_programs does not support mesh "
                             "sharding yet")
        if chunk_ticks <= 0:
            raise ValueError(f"chunk_ticks must be positive: {chunk_ticks}")
        banks = self._runtime_banks(surrogates)
        spec = self.spec
        carries = [self._init_carry(i, b) for i in range(spec.n_layers)]
        prev0 = [jnp.zeros((b, l.n_out), jnp.float32)
                 for l in spec.layers]
        x0 = jnp.zeros((chunk_ticks, b, spec.layers[0].fan_in),
                       jnp.float32)
        scal = jnp.zeros((), jnp.float32)
        total = 0.0
        step, cs = self._compiled(
            self._program_key("slot", b, chunk_ticks, banks),
            lambda: self._build_slot_step(b, banks),
            (x0, scal, jnp.zeros((b,), jnp.float32), carries, prev0,
             banks))
        total += cs
        flush, cs = self._compiled(
            self._program_key("slotflush", b, None, banks),
            lambda: self._build_slot_flush(b, banks),
            (carries, jnp.zeros((spec.n_layers, b), jnp.float32), banks))
        total += cs
        join, cs = self._compiled(
            self._program_key("slotjoin", b, None, banks),
            lambda: self._build_slot_join(b),
            (carries, prev0, jnp.zeros((b,), bool), scal))
        total += cs
        return SlotPrograms(step=step, flush=flush, join=join,
                            compile_seconds=total)

    def _runtime_banks(self, surrogates) -> SurrogateLibrary:
        if self.backend != "lasana":
            if surrogates is not None:
                raise ValueError(
                    f"backend={self.backend!r} does not use surrogates; "
                    "pass surrogates= only with backend='lasana' (or drop "
                    "the argument to run the reference backend)")
            return SurrogateLibrary()
        banks = (self._normalize_surrogates(surrogates)
                 if surrogates is not None else self.surrogates)
        if banks is None:
            raise ValueError(
                "backend='lasana' requires surrogates: pass surrogates= (a "
                "Surrogate or {circuit: Surrogate} library; legacy "
                "PredictorBank values are converted) to NetworkEngine or "
                "run()")
        return banks

    def _program_key(self, kind: str, b: int, t_steps, banks) -> tuple:
        """Cache key of a compiled program: shapes + surrogate structure.

        ``kind`` separates the monolithic (``"mono"``), streaming-chunk
        (``"stream"``), stream-flush (``"flush"``) and continuous-batching
        (``"slot"`` / ``"slotflush"`` / ``"slotjoin"``) programs; the
        engine's ``fused`` flag, the resolved fused-kernel switch
        (``fused_kernel=`` override else ``REPRO_FUSED_KERNEL``) and the
        resolved megakernel launcher (``REPRO_TICK_PALLAS``) are part of
        the key because each selects a different traced inference body
        (without them in the key, flipping a switch after the first run
        would silently reuse the old program). Two libraries with equal
        treedefs (manifests included) and equal leaf shapes/dtypes share
        one executable — a retrained surrogate is a weight swap, not a
        recompile. The surrogate part of the key is
        ``surrogate.structure_key``, shared with the DSE sweep engine so
        the hot-swap contract cannot drift between the two."""
        from repro.core.surrogate import structure_key
        from repro.kernels import ops
        return (kind, self.fused,
                ops.fused_kernel_enabled(self.fused_kernel),
                ops.tick_pallas_enabled(), b, t_steps,
                structure_key(banks))

    def _compiled(self, key, build, example_args):
        """AOT lower+compile ``build()`` once per cache key.

        Returns ``(compiled, compile_seconds)`` where ``compile_seconds``
        is 0.0 on cache hits; tick-scan programs (``mono``/``stream``/
        ``slot``) count toward :attr:`compile_count`, the tiny flush and
        join helpers do not (they are stream/serve bookkeeping, not
        network programs). Thread-safe: concurrent callers racing on one
        uncompiled key serialize on :attr:`_compile_lock` and share the
        single resulting executable (exactly one compile)."""
        entry = self._sim_cache.get(key)
        if entry is not None:
            return entry[0], 0.0
        with self._compile_lock:
            entry = self._sim_cache.get(key)
            if entry is not None:
                return entry[0], 0.0
            with _span("compile", kind=key[0]):
                fn = build()
                t0 = time.time()
                compiled = fn.lower(*example_args).compile()
                compile_s = time.time() - t0
            self._sim_cache[key] = (compiled, compile_s)
            if key[0] in ("mono", "stream", "slot"):
                self.compile_count += 1
        return compiled, compile_s

    def compiled_hlo(self) -> list:
        """The optimized HLO text of every program this engine has
        compiled, oldest first; reading it changes nothing. A profiler
        trace names each device operation as this text does, and its
        ``metadata={op_name=...}`` carries the tick cascade's stage scopes
        (docs/architecture.md, "Tracing")."""
        return [compiled.as_text()
                for compiled, _ in list(self._sim_cache.values())]

    def _check_mesh_batch(self, b: int):
        if self.mesh is not None:
            n_dev = int(np.prod([self.mesh.shape[a]
                                 for a in self.mesh.axis_names]))
            if b % n_dev:
                raise ValueError(f"batch {b} not divisible by mesh size "
                                 f"{n_dev}")

    def _run(self, x, *, surrogates=None) -> NetworkRun:
        spec = self.spec
        call = self._call.n
        with _span("prepare", call=call):
            t_steps, b, _ = x.shape
            self._check_mesh_batch(b)
            banks = self._runtime_banks(surrogates)
            carries = [self._init_carry(i, b) for i in range(spec.n_layers)]
            prev0 = [jnp.zeros((b, l.n_out), jnp.float32)
                     for l in spec.layers]
            # AOT-compile once per (shapes, surrogate structure): later
            # runs — including runs with swapped surrogate weights — only
            # execute
            key = self._program_key("mono", b, t_steps, banks)
            compiled, compile_s = self._compiled(
                key, lambda: self._build_sim(b, banks),
                (x, carries, prev0, banks))
            if compile_s == 0.0:
                compile_s = self._sim_cache[key][1]    # historical build time

        with _span("execute", call=call):
            t0 = time.perf_counter()
            primary, out_seq, hidden, e_tl, l_tl, ev_tl, flush, st_tl = \
                jax.block_until_ready(compiled(x, carries, prev0, banks))
            wall = time.perf_counter() - t0
        last_lif = spec.circuits[-1] == "lif"
        with _span("fetch", call=call) as span:
            run = NetworkRun(
                backend=self.backend, mode=self.mode,
                outputs=np.asarray(primary),
                out_spikes=np.asarray(out_seq) if last_lif else None,
                layer_spikes=[np.asarray(h) for h in hidden]
                if self.record_hidden else None,
                energy=np.asarray(e_tl), latency=np.asarray(l_tl),
                events=np.asarray(ev_tl, np.int64),
                flush_energy=np.asarray(flush),
                n_circuits=np.asarray([l.n_circuits(b)
                                       for l in spec.layers]),
                clock_ns=self.clock_ns, wall_seconds=wall,
                circuits=spec.circuits, compile_seconds=compile_s)
            fetched = [primary, e_tl, l_tl, ev_tl, flush, *hidden]
            if st_tl is not None:       # the scan's flags; not the chunk
                fetched.append(st_tl)   # kernel's, which emits none
                span.set_metadata(
                    idle_stage_ticks=int(np.sum(np.asarray(st_tl))))
            if last_lif:
                fetched.append(out_seq)
            span.set_metadata(bytes=sum(a.nbytes for a in fetched))
        return run
