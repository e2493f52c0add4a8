"""Trace-time program auditor for the LASANA hot paths.

Every invariant the benchmarks enforce dynamically has a static shadow
here, checked from the *traced program* before anything compiles or runs:

  * **dispatch budgets** — ``Surrogate.predict`` / ``predict_heads`` and
    the whole-tick megakernel report each surrogate dispatch through
    ``ops.record_dispatch`` at trace time; scan bodies trace once, so the
    per-trace count is the per-tick dispatch count. Architectural
    ceilings (fused <= 3, annotation/megakernel == 1, per-call == 7) are
    hard-coded per entrypoint and cannot be regenerated away.
  * **dot/scan/pallas counts** — a recursive jaxpr walk (descending into
    ``pjit``/``scan``/``cond`` sub-jaxprs) frozen per entrypoint in
    ``tests/data/program_budgets.json`` (the ``check_api.py`` pattern:
    drift fails, ``--regen`` accepts).
  * **donation discipline** — donating programs are lowered and every
    ``donate_argnums`` leaf must surface as a ``tf.aliasing_output``
    marker; a "donated buffers were not usable" warning is a failure.
  * **dtype/callback hygiene** — no fp64/complex128 aval anywhere in the
    traced body, no host-callback/infeed primitive (worst inside a scan
    body, where it would sync every tick).
  * **cache-key completeness** — a registry of every engine/program cache
    whose key function must mention its declared discriminators and must
    never call ``id(...)`` (the class of bug behind the PR 6 mesh-cache
    and PR 8 lane-identity fixes), plus a *dynamic* sensitivity check
    that flips each knob and asserts the network program key changes.
  * **environment discipline** — ``kernels/ops.py`` is the single module
    allowed to *read* ``os.environ`` under ``src/repro``/``benchmarks``
    (writes, e.g. the dry-run launchers pinning ``XLA_FLAGS``, are fine).

Entrypoints are built from **synthetic surrogates** (zero-weight MLP
heads of the production 3-layer shape): structure — and therefore every
metric here — is exactly that of a trained artifact, with none of the
training cost or cross-platform fit variance.
"""

from __future__ import annotations

import ast
import collections
import contextlib
import dataclasses
import inspect
import json
import os
import pathlib
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]

# primitives that escape to the host (a hidden sync per dispatch — fatal
# inside a tick scan, unacceptable anywhere on the hot path)
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed",
})
WIDE_DTYPES = ("float64", "complex128")
DONATION_MARKER = "tf.aliasing_output"
DONATION_WARNING = "donated buffers were not usable"


@dataclasses.dataclass(frozen=True)
class Finding:
    """One auditor violation: the check that fired, on what, and why."""

    check: str     # e.g. "dispatch-budget", "donation", "cache-key"
    entry: str     # entrypoint / cache / file the finding names
    message: str

    def __str__(self):
        return f"[{self.check}] {self.entry}: {self.message}"


# --- jaxpr walking ------------------------------------------------------------

@dataclasses.dataclass
class ProgramMetrics:
    """Static shape of one traced entrypoint (the frozen-budget row)."""

    dispatches: dict = dataclasses.field(default_factory=dict)
    dots: int = 0
    scans: int = 0
    pallas_calls: int = 0
    donated: int = 0                   # tf.aliasing_output markers
    callbacks: list = dataclasses.field(default_factory=list)
    wide_dtypes: list = dataclasses.field(default_factory=list)

    def budget_row(self) -> dict:
        """The JSON-stable slice frozen in program_budgets.json."""
        return {"dispatches": dict(sorted(self.dispatches.items())),
                "dots": self.dots, "scans": self.scans,
                "pallas_calls": self.pallas_calls, "donated": self.donated}


def _iter_sub_jaxprs(params):
    """Yield every (Closed)Jaxpr nested in an eqn's params (pjit bodies,
    scan bodies, cond branches, custom_* funs)."""
    stack = list(params.values())
    while stack:
        x = stack.pop()
        if hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
            yield x.jaxpr                            # ClosedJaxpr
        elif hasattr(x, "eqns"):                     # Jaxpr
            yield x
        elif isinstance(x, (tuple, list)):
            stack.extend(x)


def _check_aval(var, metrics, in_scan, seen):
    aval = getattr(var, "aval", None)
    dtype = getattr(aval, "dtype", None)
    if dtype is not None and str(dtype) in WIDE_DTYPES:
        key = (str(aval), in_scan)
        if key not in seen:
            seen.add(key)
            metrics.wide_dtypes.append(key)


def walk_jaxpr(jaxpr, metrics: ProgramMetrics, *, in_scan: bool = False,
               _seen=None) -> ProgramMetrics:
    """Accumulate dot/scan/callback/dtype metrics over ``jaxpr`` and every
    nested sub-jaxpr (the traced body of each pjit/scan/cond eqn)."""
    seen = set() if _seen is None else _seen
    for var in (*jaxpr.invars, *jaxpr.constvars, *jaxpr.outvars):
        _check_aval(var, metrics, in_scan, seen)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            metrics.dots += 1
        elif name == "scan":
            metrics.scans += 1
        elif "pallas" in name:
            metrics.pallas_calls += 1
        if name in CALLBACK_PRIMITIVES:
            metrics.callbacks.append((name, in_scan))
        for var in eqn.outvars:
            _check_aval(var, metrics, in_scan, seen)
        inner_scan = in_scan or name in ("scan", "while")
        for sub in _iter_sub_jaxprs(eqn.params):
            walk_jaxpr(sub, metrics, in_scan=inner_scan, _seen=seen)
    return metrics


# --- synthetic surrogates -----------------------------------------------------

def synthetic_surrogate(circuit_name: str, *, family: str = "mlp",
                        hidden: tuple = (8, 4)):
    """A structurally-production :class:`Surrogate` with zero weights.

    Carries all five Algorithm-1 predictors as ``family`` heads sized to
    the circuit's augmented feature widths (so the megakernel pack
    eligibility, head stacking, and program cache keys behave exactly as
    for a trained artifact) — without golden simulation or fitting, and
    with bitwise-identical *structure* on every platform. Budgets frozen
    from these surrogates are therefore deterministic."""
    from repro.core.circuits import augment_features, get_circuit
    from repro.core.surrogate import (FORMAT_VERSION, Manifest, Surrogate,
                                      _feature_names)
    circ = get_circuit(circuit_name)
    f_raw = circ.n_inputs + 2 + circ.n_params
    f_aug = int(augment_features(
        circ, jnp.zeros((1, f_raw), jnp.float32)).shape[1])
    f_tr = int(augment_features(
        circ, jnp.zeros((1, f_raw + 2), jnp.float32)).shape[1])
    h1, h2 = hidden
    predictors = ("M_ED", "M_ES", "M_L", "M_O", "M_V")
    transition = ("M_ED", "M_L")

    def head(f):
        if family == "linear":
            return {"mu": jnp.zeros((f,), jnp.float32),
                    "sd": jnp.ones((f,), jnp.float32),
                    "w": jnp.zeros((f + 1,), jnp.float32)}
        if family == "mlp":
            return {"x_mu": jnp.zeros((f,), jnp.float32),
                    "x_sd": jnp.ones((f,), jnp.float32),
                    "y_mu": jnp.zeros((1,), jnp.float32),
                    "y_sd": jnp.ones((1,), jnp.float32),
                    "w0": jnp.zeros((f, h1), jnp.float32),
                    "b0": jnp.zeros((h1,), jnp.float32),
                    "w1": jnp.zeros((h1, h2), jnp.float32),
                    "b1": jnp.zeros((h2,), jnp.float32),
                    "w2": jnp.zeros((h2, 1), jnp.float32),
                    "b2": jnp.zeros((1,), jnp.float32)}
        raise ValueError(f"unsupported synthetic family: {family!r}")

    params = {p: head(f_tr if p in transition else f_aug)
              for p in predictors}
    manifest = Manifest(
        circuit=circuit_name, format_version=FORMAT_VERSION,
        families=tuple((p, family) for p in predictors),
        scales=tuple((p, 1.0) for p in predictors),
        features=_feature_names(circuit_name))
    return Surrogate(manifest=manifest, params=params, fit_info=None)


# --- the entrypoint registry --------------------------------------------------

@dataclasses.dataclass
class TracedEntry:
    """What one registered builder hands the auditor: a traceable callable,
    example args, its declared donation, and hard dispatch ceilings."""

    fn: object
    args: tuple
    donate: tuple = ()
    max_dispatch: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class AuditContext:
    """Shared fixtures every entrypoint builder draws from."""

    lif: object                        # synthetic lif Surrogate
    xbar: object                       # synthetic crossbar Surrogate
    spec: object                       # tiny 2-layer LIF NetworkSpec
    b: int = 2
    chunk: int = 3


def build_context() -> AuditContext:
    from repro.core.network import snn_spec
    w1 = np.linspace(-1.0, 1.0, 6, dtype=np.float32).reshape(2, 3)
    w2 = np.linspace(1.0, -1.0, 6, dtype=np.float32).reshape(3, 2)
    params = [np.asarray([0.58, 0.5, 0.5, 0.5], np.float32)] * 2
    return AuditContext(lif=synthetic_surrogate("lif"),
                        xbar=synthetic_surrogate("crossbar"),
                        spec=snn_spec([w1, w2], params))


def _tick_args(circuit_name: str, n: int = 4):
    from repro.core.circuits import get_circuit
    from repro.core.wrapper import init_state
    circ = get_circuit(circuit_name)
    state = init_state(n, jnp.zeros((n, circ.n_params), jnp.float32))
    changed = jnp.ones((n,), bool)
    x = jnp.zeros((n, circ.n_inputs), jnp.float32)
    t = jnp.float32(3 * circ.clock_ns)
    return circ, state, changed, x, t


@ops.register_entrypoint("tick_fused_standalone")
def _entry_tick_fused(ctx: AuditContext) -> TracedEntry:
    """Single-bank Algorithm-1 tick, fused predict_heads path (PR 5)."""
    from repro.core import wrapper
    circ, state, changed, x, t = _tick_args("lif")

    def fn(sur, state, changed, x, t):
        return wrapper.lasana_step(sur, state, changed, x, t, circ.clock_ns,
                                   spiking=True, fused=True,
                                   fused_kernel=False)
    return TracedEntry(fn=fn, args=(ctx.lif, state, changed, x, t),
                       max_dispatch={"predict_heads": 3, "predict": 0,
                                     "megakernel_step": 0})


@ops.register_entrypoint("tick_fused_annotation")
def _entry_tick_annotation(ctx: AuditContext) -> TracedEntry:
    """Annotation-mode tick: no data dependencies -> ONE stacked pass."""
    from repro.core import wrapper
    circ, state, changed, x, t = _tick_args("lif")

    def fn(sur, state, changed, x, t, known):
        return wrapper.lasana_step(sur, state, changed, x, t, circ.clock_ns,
                                   spiking=True, known_out=known,
                                   fused=True, fused_kernel=False)
    known = jnp.zeros(state.v.shape, jnp.float32)
    return TracedEntry(fn=fn, args=(ctx.lif, state, changed, x, t, known),
                       max_dispatch={"predict_heads": 1, "predict": 0})


@ops.register_entrypoint("tick_percall")
def _entry_tick_percall(ctx: AuditContext) -> TracedEntry:
    """Per-predict baseline: seven dispatches, the A/B comparison arm."""
    from repro.core import wrapper
    circ, state, changed, x, t = _tick_args("lif")

    def fn(sur, state, changed, x, t):
        return wrapper.lasana_step(sur, state, changed, x, t, circ.clock_ns,
                                   spiking=True, fused=False)
    return TracedEntry(fn=fn, args=(ctx.lif, state, changed, x, t),
                       max_dispatch={"predict": 7, "predict_heads": 0})


@ops.register_entrypoint("tick_megakernel")
def _entry_tick_megakernel(ctx: AuditContext) -> TracedEntry:
    """Whole-tick megakernel (PR 7): the entire tick is ONE dispatch."""
    from repro.core import wrapper
    circ, state, changed, x, t = _tick_args("lif")

    def fn(sur, state, changed, x, t):
        return wrapper.lasana_step(sur, state, changed, x, t, circ.clock_ns,
                                   spiking=True, fused=True,
                                   fused_kernel=True)
    return TracedEntry(fn=fn, args=(ctx.lif, state, changed, x, t),
                       max_dispatch={"megakernel_step": 1,
                                     "predict_heads": 0, "predict": 0})


@ops.register_entrypoint("tick_xbar_fused")
def _entry_tick_xbar(ctx: AuditContext) -> TracedEntry:
    """Crossbar-bank tick on the fused path (mixed-graph second kind)."""
    from repro.core import wrapper
    circ, state, changed, x, t = _tick_args("crossbar")

    def fn(sur, state, changed, x, t):
        return wrapper.lasana_step(sur, state, changed, x, t, circ.clock_ns,
                                   spiking=False, fused=True,
                                   fused_kernel=False)
    return TracedEntry(fn=fn, args=(ctx.xbar, state, changed, x, t),
                       max_dispatch={"predict_heads": 3, "predict": 0})


@ops.register_entrypoint("explore_pricing")
def _entry_explore(ctx: AuditContext) -> TracedEntry:
    """The DSE sweep's vectorized pricing pass (PR 6): two fused passes
    (act: M_O, then tr: M_ED/M_L chained on the resolved output)."""
    from repro.core.explore import DSEEngine
    eng = DSEEngine(n_samples=8)

    def fn(sur, v_dd, tile):
        return eng._tile_eval(sur, v_dd, tile)
    return TracedEntry(
        fn=fn, args=(ctx.xbar, jnp.full((4,), 1.5, jnp.float32),
                     jnp.full((4,), 32, jnp.int32)),
        max_dispatch={"predict_heads": 2, "predict": 0})


def _network_engine(ctx: AuditContext):
    from repro.core.network import NetworkEngine
    return NetworkEngine(ctx.spec, backend="lasana", record_hidden=False)


def _network_state(eng, ctx):
    banks = eng._runtime_banks(ctx.lif)
    carries = [eng._init_carry(i, ctx.b)
               for i in range(ctx.spec.n_layers)]
    prev0 = [jnp.zeros((ctx.b, l.n_out), jnp.float32)
             for l in ctx.spec.layers]
    x_seq = jnp.zeros((ctx.chunk, ctx.b, ctx.spec.layers[0].fan_in),
                      jnp.float32)
    return banks, carries, prev0, x_seq


@ops.register_entrypoint("network_mono")
def _entry_network_mono(ctx: AuditContext) -> TracedEntry:
    """The monolithic tick-scan network program (lasana.simulate)."""
    eng = _network_engine(ctx)
    banks, carries, prev0, x_seq = _network_state(eng, ctx)
    L = ctx.spec.n_layers
    # the monolithic program ends with the idle-energy flush: one
    # per-predict M_ES pass per layer on top of the fused tick scan
    return TracedEntry(fn=eng._build_sim(ctx.b, banks),
                       args=(x_seq, carries, prev0, banks),
                       max_dispatch={"predict_heads": 3 * L, "predict": L})


@ops.register_entrypoint("network_stream_chunk")
def _entry_stream_chunk(ctx: AuditContext) -> TracedEntry:
    """The donated-carry streaming chunk program (lasana.stream)."""
    eng = _network_engine(ctx)
    banks, carries, prev0, x_seq = _network_state(eng, ctx)
    L = ctx.spec.n_layers
    return TracedEntry(fn=eng._build_stream_step(ctx.b, banks),
                       args=(x_seq, jnp.float32(0.0), carries, prev0,
                             banks),
                       donate=(2, 3, 4),
                       max_dispatch={"predict_heads": 3 * L, "predict": 0})


@ops.register_entrypoint("network_stream_flush")
def _entry_stream_flush(ctx: AuditContext) -> TracedEntry:
    """End-of-stream idle-energy flush (one M_ES pass per LIF layer)."""
    eng = _network_engine(ctx)
    banks, carries, _, _ = _network_state(eng, ctx)
    L = ctx.spec.n_layers
    t_ends = jnp.zeros((L,), jnp.float32)
    return TracedEntry(fn=eng._build_flush(ctx.b, banks),
                       args=(carries, t_ends, banks),
                       max_dispatch={"predict": L, "predict_heads": 0})


@ops.register_entrypoint("serve_slot_step")
def _entry_slot_step(ctx: AuditContext) -> TracedEntry:
    """The serving layer's slot-masked chunk program (Lane.step)."""
    eng = _network_engine(ctx)
    banks, carries, prev0, x_seq = _network_state(eng, ctx)
    L = ctx.spec.n_layers
    end_ks = jnp.zeros((ctx.b,), jnp.float32)
    return TracedEntry(fn=eng._build_slot_step(ctx.b, banks),
                       args=(x_seq, jnp.float32(0.0), end_ks, carries,
                             prev0, banks),
                       donate=(3, 4, 5),
                       max_dispatch={"predict_heads": 3 * L, "predict": 0})


@ops.register_entrypoint("serve_slot_flush")
def _entry_slot_flush(ctx: AuditContext) -> TracedEntry:
    """Per-slot leave-time flush (Lane leavers' trailing idle energy)."""
    eng = _network_engine(ctx)
    banks, carries, _, _ = _network_state(eng, ctx)
    L = ctx.spec.n_layers
    t_ends = jnp.zeros((L, ctx.b), jnp.float32)
    return TracedEntry(fn=eng._build_slot_flush(ctx.b, banks),
                       args=(carries, t_ends, banks),
                       max_dispatch={"predict": L, "predict_heads": 0})


@ops.register_entrypoint("serve_slot_step_behavioral")
def _entry_slot_step_behavioral(ctx: AuditContext) -> TracedEntry:
    """Graceful-degradation slot chunk: the behavioral-backend lane the
    server falls back to after repeated surrogate faults. No surrogate
    banks — zero predict dispatches is the ceiling AND the point."""
    from repro.core.network import NetworkEngine
    eng = NetworkEngine(ctx.spec, backend="behavioral",
                        record_hidden=False)
    banks = eng._runtime_banks(None)
    carries = [eng._init_carry(i, ctx.b)
               for i in range(ctx.spec.n_layers)]
    prev0 = [jnp.zeros((ctx.b, l.n_out), jnp.float32)
             for l in ctx.spec.layers]
    x_seq = jnp.zeros((ctx.chunk, ctx.b, ctx.spec.layers[0].fan_in),
                      jnp.float32)
    end_ks = jnp.zeros((ctx.b,), jnp.float32)
    return TracedEntry(fn=eng._build_slot_step(ctx.b, banks),
                       args=(x_seq, jnp.float32(0.0), end_ks, carries,
                             prev0, banks),
                       donate=(3, 4, 5),
                       max_dispatch={"predict_heads": 0, "predict": 0})


@ops.register_entrypoint("serve_slot_join")
def _entry_slot_join(ctx: AuditContext) -> TracedEntry:
    """Masked slot (re)initialization at a chunk boundary (Lane.admit)."""
    eng = _network_engine(ctx)
    _, carries, prev0, _ = _network_state(eng, ctx)
    mask = jnp.zeros((ctx.b,), bool)
    return TracedEntry(fn=eng._build_slot_join(ctx.b),
                       args=(carries, prev0, mask, jnp.float32(0.0)),
                       donate=(0, 1),
                       max_dispatch={"predict": 0, "predict_heads": 0})


# --- auditing one entrypoint --------------------------------------------------

def audit_entry(name: str, entry: TracedEntry):
    """-> (ProgramMetrics, [Finding]) for one traced entrypoint."""
    findings = []
    with ops.dispatch_scope() as log:
        closed = jax.make_jaxpr(entry.fn)(*entry.args)
    metrics = ProgramMetrics(
        dispatches=dict(collections.Counter(log)))
    walk_jaxpr(closed.jaxpr, metrics)

    for counter, ceiling in sorted(entry.max_dispatch.items()):
        got = metrics.dispatches.get(counter, 0)
        if got > ceiling:
            findings.append(Finding(
                "dispatch-budget", name,
                f"{got} {counter} dispatches per tick traced; the "
                f"architectural ceiling is {ceiling} (a frozen-budget "
                "regen cannot lift this — the program structure "
                "regressed)"))

    for prim, in_scan in metrics.callbacks:
        where = "inside a scan body" if in_scan else "in the traced body"
        findings.append(Finding(
            "host-callback", name,
            f"host-sync primitive '{prim}' {where}: every dispatch would "
            "stall on a host round-trip"))

    for aval, in_scan in metrics.wide_dtypes:
        where = " inside a scan body" if in_scan else ""
        findings.append(Finding(
            "fp64-promotion", name,
            f"wide dtype {aval}{where}: the hot path is fp32-only "
            "(an fp64 leak doubles bandwidth and silently changes "
            "records)"))

    if entry.donate:
        expected = len(jax.tree.leaves(
            tuple(entry.args[i] for i in entry.donate)))
        lower = getattr(entry.fn, "lower", None)
        if lower is None:
            findings.append(Finding(
                "donation", name,
                f"declares donate_argnums={entry.donate} but the built "
                "program is not a jitted function — nothing is donated"))
        else:
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                lowered = lower(*entry.args)
            for w in caught:
                if DONATION_WARNING in str(w.message):
                    findings.append(Finding(
                        "donation", name,
                        f"dropped donation: {w.message}"))
            metrics.donated = lowered.as_text().count(DONATION_MARKER)
            if metrics.donated != expected:
                findings.append(Finding(
                    "donation", name,
                    f"{metrics.donated} of {expected} declared donated "
                    f"leaves (donate_argnums={entry.donate}) are aliased "
                    "in the lowered program — the rest silently copy "
                    "every chunk"))
    return metrics, findings


# --- frozen budgets -----------------------------------------------------------

BUDGETS_PATH = REPO_ROOT / "tests" / "data" / "program_budgets.json"


@contextlib.contextmanager
def pinned_env():
    """Pin the knobs that select traced bodies, so budgets are
    reproducible regardless of the caller's environment (the megakernel
    entrypoint opts in explicitly via ``fused_kernel=True``)."""
    pins = {"REPRO_FUSED_KERNEL": "0", "REPRO_TICK_PALLAS": "0",
            # fault injection must never perturb traced programs or
            # their budgets ("" reads as no plan via fault_plan_path)
            "REPRO_FAULT_PLAN": ""}
    saved = {k: os.environ.get(k) for k in pins}
    os.environ.update(pins)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def collect_budgets() -> dict:
    """Trace every registered entrypoint -> {name: budget row}."""
    with pinned_env():
        ctx = build_context()
        rows = {}
        for name, builder in sorted(ops.registered_entrypoints().items()):
            metrics, _ = audit_entry(name, builder(ctx))
            rows[name] = metrics.budget_row()
    return rows


def load_budgets(path=BUDGETS_PATH) -> dict:
    with open(path) as f:
        return json.load(f)["entries"]


def save_budgets(rows: dict, path=BUDGETS_PATH) -> None:
    payload = {
        "_comment": [
            "Frozen per-entrypoint program budgets (dispatches per tick,",
            "dot_general/scan/pallas_call counts, donated leaf count).",
            "Checked by tools/check_programs.py; regenerate an",
            "intentional change with:",
            "  PYTHONPATH=src python tools/check_programs.py --regen",
            "Architectural ceilings (fused <= 3 dispatches, megakernel",
            "== 1) are hard-coded in repro/analysis/jaxpr_audit.py and",
            "cannot be regenerated away.",
        ],
        "entries": {k: rows[k] for k in sorted(rows)},
    }
    path = pathlib.Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


def compare_budgets(rows: dict, frozen: dict) -> list:
    findings = []
    for name in sorted(set(rows) | set(frozen)):
        if name not in frozen:
            findings.append(Finding(
                "program-budget", name,
                "entrypoint has no frozen budget — run tools/"
                "check_programs.py --regen and review the new row"))
        elif name not in rows:
            findings.append(Finding(
                "program-budget", name,
                "frozen budget exists but the entrypoint is no longer "
                "registered — regen to drop it"))
        elif rows[name] != frozen[name]:
            findings.append(Finding(
                "program-budget", name,
                f"traced program drifted from the frozen budget: "
                f"now {rows[name]}, frozen {frozen[name]} (intentional? "
                "regen with tools/check_programs.py --regen)"))
    return findings


# --- cache-key completeness ---------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CacheKeySpec:
    """One registered cache: where its key is built and what the key must
    discriminate on."""

    name: str
    module: str
    qualname: str
    required: tuple


CACHE_KEY_REGISTRY = (
    CacheKeySpec(
        "engine-cache", "repro.lasana", "engine",
        required=("backend", "mode", "mesh", "record_hidden", "fused",
                  "fused_kernel")),
    CacheKeySpec(
        "network-program-cache", "repro.core.network",
        "NetworkEngine._program_key",
        required=("kind", "fused", "fused_kernel_enabled",
                  "tick_pallas_enabled", "b", "t_steps", "structure_key")),
    CacheKeySpec(
        "dse-program-cache", "repro.core.explore",
        "DSEEngine._compiled_tile_eval",
        required=("c", "n_samples", "structure_key")),
    CacheKeySpec(
        "serve-lane-table", "repro.serve.server", "SimServer._lane_for",
        required=("bucket", "sur_token", "mode", "degraded")),
)


def check_cache_key_source(src: str, required, name: str) -> list:
    """AST-check one cache-key function's source: every declared
    discriminator must appear, and ``id(...)`` must never be called —
    object identity is not value equality, and a recycled address aliases
    the cache onto the wrong entry (the PR 6 mesh bug)."""
    findings = []
    tree = ast.parse(textwrap.dedent(src))
    seen = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            seen.add(node.id)
        elif isinstance(node, ast.Attribute):
            seen.add(node.attr)
        elif isinstance(node, ast.arg):
            seen.add(node.arg)
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "id"):
            findings.append(Finding(
                "cache-key", name,
                f"id(...) used in a cache-key expression (line "
                f"{node.lineno}): identity keys alias recycled objects — "
                "key by value/structure instead"))
    for field in required:
        if field not in seen:
            findings.append(Finding(
                "cache-key", name,
                f"declared key field '{field}' does not appear in the "
                "key-building function — the cache cannot discriminate "
                "on it (stale-program aliasing)"))
    return findings


def check_cache_keys() -> list:
    import importlib
    findings = []
    for spec in CACHE_KEY_REGISTRY:
        obj = importlib.import_module(spec.module)
        for part in spec.qualname.split("."):
            obj = getattr(obj, part)
        src = inspect.getsource(obj)
        findings.extend(check_cache_key_source(src, spec.required,
                                               f"{spec.module}."
                                               f"{spec.qualname}"))
    return findings


def check_program_key_sensitivity(ctx: AuditContext) -> list:
    """Dynamic completeness check on the network program cache: flip each
    knob that selects a different traced body and assert the key moves.
    This is the static registry's runtime shadow — an AST check can see a
    name, only this proves the key actually discriminates."""
    from repro.core.network import NetworkEngine
    findings = []
    banks = _network_engine(ctx)._runtime_banks(ctx.lif)
    small = _network_engine(ctx)._runtime_banks(
        synthetic_surrogate("lif", hidden=(6, 3)))

    def key(*, fused=True, fused_kernel=False, b=2, t_steps=3,
            kind="stream", banks=banks, env=None):
        saved = {}
        for k, v in (env or {}).items():
            saved[k] = os.environ.get(k)
            os.environ[k] = v
        try:
            eng = NetworkEngine(ctx.spec, backend="lasana", fused=fused,
                                fused_kernel=fused_kernel,
                                record_hidden=False)
            return eng._program_key(kind, b, t_steps, banks)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    base = key()
    knobs = {
        "fused": key(fused=False),
        "fused_kernel": key(fused_kernel=True),
        "tick_pallas": key(env={"REPRO_TICK_PALLAS": "1"}),
        "batch": key(b=4),
        "t_steps": key(t_steps=5),
        "kind": key(kind="slot"),
        "surrogate-structure": key(banks=small),
    }
    for knob, other in knobs.items():
        if other == base:
            findings.append(Finding(
                "cache-key", "NetworkEngine._program_key",
                f"flipping '{knob}' does not change the program cache "
                "key — the stale compiled program would be silently "
                "reused"))
    return findings


# --- environment-read discipline ----------------------------------------------

ENV_READ_ALLOWLIST = (
    "src/repro/kernels/ops.py",
    # the auditor itself: pins/restores knobs around tracing and flips
    # them for the cache-key sensitivity check — not configuration reads
    "src/repro/analysis/jaxpr_audit.py",
)


def _env_read_violations(tree: ast.AST, rel: str) -> list:
    """Flag os.environ/os.getenv READS (writes — e.g. the dry-run
    launchers pinning XLA_FLAGS — are allowed anywhere)."""
    findings = []

    def is_environ(node):
        return (isinstance(node, ast.Attribute) and node.attr == "environ"
                and isinstance(node.value, ast.Name)
                and node.value.id == "os")

    for node in ast.walk(tree):
        hit = None
        if isinstance(node, ast.Call):
            f = node.func
            if (isinstance(f, ast.Attribute) and f.attr == "getenv"
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "os"):
                hit = "os.getenv(...)"
            elif (isinstance(f, ast.Attribute) and f.attr == "get"
                    and is_environ(f.value)):
                hit = "os.environ.get(...)"
        elif (isinstance(node, ast.Subscript) and is_environ(node.value)
                and isinstance(node.ctx, ast.Load)):
            hit = "os.environ[...]"
        if hit:
            findings.append(Finding(
                "env-discipline", rel,
                f"{hit} at line {node.lineno}: configuration reads go "
                "through a kernels/ops.py accessor (the auditor's single "
                "choke point)"))
    return findings


def check_env_discipline(root=REPO_ROOT) -> list:
    root = pathlib.Path(root)
    findings = []
    scan_dirs = [root / "src" / "repro", root / "benchmarks"]
    for base in scan_dirs:
        if not base.is_dir():
            continue
        for path in sorted(base.rglob("*.py")):
            rel = path.relative_to(root).as_posix()
            if rel in ENV_READ_ALLOWLIST:
                continue
            tree = ast.parse(path.read_text())
            findings.extend(_env_read_violations(tree, rel))
    return findings


# --- the whole audit ----------------------------------------------------------

def run_audit(budgets: dict | None = None) -> list:
    """Run every pass; returns the (possibly empty) list of findings.

    ``budgets``: frozen rows to diff traced programs against (pass
    ``load_budgets()``; None skips the frozen comparison — ceilings,
    donation, dtype/callback, cache-key, and env checks still run)."""
    findings = []
    with pinned_env():
        ctx = build_context()
        rows = {}
        for name, builder in sorted(ops.registered_entrypoints().items()):
            metrics, entry_findings = audit_entry(name, builder(ctx))
            rows[name] = metrics.budget_row()
            findings.extend(entry_findings)
        if budgets is not None:
            findings.extend(compare_budgets(rows, budgets))
        findings.extend(check_program_key_sensitivity(ctx))
    findings.extend(check_cache_keys())
    findings.extend(check_env_discipline())
    return findings
