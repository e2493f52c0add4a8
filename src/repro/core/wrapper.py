"""Algorithm 1 — the ML inference wrapper, vectorized for TPUs.

The paper's wrapper walks a *set* S of circuits whose input changed at tick
t; TPUs want fixed shapes, so S becomes a boolean mask and both the
idle-catch-up path (lines 3-9) and the active path (lines 10-22) are
evaluated for all N circuits with ``where``-selection (lines 23-29).
Semantics are identical — verified against a per-circuit reference loop in
tests/test_wrapper.py — and the two systems optimizations fall out for free:

  * batching across the system: the whole tick is ONE batched inference per
    predictor (the (N, F) feature matrices below);
  * idle-period merging: stale circuits are caught up with a single E2 event
    of length t - t' - T rather than per-tick updates (line 5).

``lasana_step`` is pure and jit/shard_map-friendly: circuits shard over the
flattened mesh with zero cross-circuit communication.

Public API
----------
:class:`LasanaState` / :func:`init_state`
    per-circuit simulator state: predicted state ``v``, last output ``o``,
    last-update time ``t_last``, fixed ``params``
:func:`lasana_step`
    one digital tick of Algorithm 1 for N circuits; ``known_out=`` switches
    annotation mode (external behavioral outputs, LASANA energy/latency).
    By default the tick takes the FUSED inference path
    (``Surrogate.predict_heads``): features are derived once per variant
    and same-family predictor heads evaluate in batched stacked passes —
    three fused dispatches per tick (idle heads -> active-variant heads
    -> transition heads, which consume M_O's resolved output) instead of
    seven ``predict`` calls, and a single dispatch in annotation mode.
    ``fused=False`` keeps the original one-``predict``-per-head
    formulation (the benchmark A/B baseline; results agree within a few
    ULPs — see docs/architecture.md, "Inference hot path").
:func:`stale_circuits`
    Algorithm 1's line 4 as a mask: the circuits a tick catches up with
    one merged idle event. The default standalone tick evaluates that
    idle stage only when the mask holds a circuit.
:class:`RowBlocks` / :func:`row_blocks_ok`
    rows that repeat blocks of columns (crossbar rows), handed to
    ``lasana_step`` in place of per-row inputs, at the blocks' own
    shapes: no (N, F) feature matrix is written
:func:`lasana_step_reference`
    literal per-circuit numpy transcription, the parity oracle for tests

The network-level composition of this wrapper (event queues between
layers, mixed circuit kinds, recurrent edges) lives in core/network.py;
see docs/architecture.md for the full dataflow.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class LasanaState(NamedTuple):
    """Per-circuit simulator state (all (N,) or (N, k))."""

    v: jax.Array          # latest predicted state v'
    o: jax.Array          # latest output
    t_last: jax.Array     # latest update time t'
    params: jax.Array     # (N, n_p) fixed circuit parameters


# the columns a transition head reads after the active variant's raw ones
_TR_COLUMNS = ("o_prev", "o_new")


class RowBlocks(NamedTuple):
    """Circuit rows as column blocks (``Surrogate.predict_blocks``): each
    array's leading axes broadcast to the rows' grid, whose flattening is
    the order of the state's N circuits."""

    x: jax.Array             # (..., n_inputs) inputs applied at t
    params: jax.Array        # (..., n_params) fixed circuit parameters


def row_blocks_ok(surrogate) -> bool:
    """Whether ``lasana_step`` can take ``surrogate``'s rows as
    :class:`RowBlocks`: every Algorithm-1 head is ``mean``, ``linear`` or
    ``mlp`` and reads its circuit's x, v, tau and params columns, a
    transition head's o_prev and o_new, then the derived columns."""
    from repro.core.circuits import get_circuit
    from repro.core.surrogate import ALG1_HEADS
    column_blocks = getattr(surrogate, "column_blocks", None)
    heads = set(ALG1_HEADS["act"] + ALG1_HEADS["tr"])
    if column_blocks is None or not heads <= set(
            surrogate.manifest.predictors):
        return False
    try:
        circ = get_circuit(surrogate.manifest.circuit)
    except KeyError:
        return False
    base = (("x", circ.n_inputs), ("v", 1), ("tau", 1),
            ("p", circ.n_params))
    derived = _derived(circ, np.zeros((1, circ.n_inputs), np.float32),
                       np.zeros((1, circ.n_params), np.float32))
    for p in heads:
        extra = _TR_COLUMNS if p in ALG1_HEADS["tr"] else ()
        want = (base + tuple((c, 1) for c in extra)
                + tuple(("derived", a.shape[-1]) for a in derived.values()))
        if column_blocks(p, extra) not in ((), want):
            return False
    return True


def _derived(circ, x, params) -> dict:
    """The circuit's derived columns as a ``derived`` block (none for a
    circuit without ``surrogate_features``), from blocks that broadcast:
    the function the augmentation applies to whole rows."""
    fn = getattr(circ, "surrogate_features", None)
    return {} if fn is None else {"derived": fn(x, params)}


def stale_circuits(state: LasanaState, changed, t, clock_ns):
    """Algorithm 1's line 4: the circuits with an event at ``t`` whose
    last event was more than one clock ago, due one merged idle catch-up
    event (lines 5-6)."""
    return changed & (state.t_last < t - clock_ns)


def _idle_when_stale(stale, idle_eval):
    """Algorithm 1's lines 3-9, ``idle_eval() -> (e_s_idle, v_hat)``,
    evaluated only on a tick where some circuit is stale. The skip branch
    returns zeros, which is exact: ``_finish_tick`` reads both results
    only where ``stale``."""
    zeros = jnp.zeros(stale.shape, jnp.float32)
    return jax.lax.cond(jnp.any(stale), idle_eval, lambda: (zeros, zeros))


def init_state(n: int, params) -> LasanaState:
    return LasanaState(
        v=jnp.zeros((n,), jnp.float32),
        o=jnp.zeros((n,), jnp.float32),
        t_last=jnp.zeros((n,), jnp.float32),
        params=params,
    )


def _features(x, v, tau, params, o_prev=None, o_new=None):
    cols = [x, v[:, None], tau[:, None], params]
    if o_prev is not None:
        cols.append(o_prev[:, None])
    if o_new is not None:
        cols.append(o_new[:, None])     # chained M_O prediction (§IV-B ext.)
    return jnp.concatenate(cols, axis=1)


def _splice_transition(aug_act, f_base: int, o_prev, o_new):
    """Augmented transition matrix as a column splice of the active one.

    The transition variant is the active variant plus ``o_prev``/``o_new``
    columns inserted BEFORE the circuit's derived features (which depend
    only on the shared x/params columns) — so the already-augmented active
    matrix is reused instead of re-deriving anything."""
    return jnp.concatenate(
        [aug_act[:, :f_base], o_prev[:, None], o_new[:, None],
         aug_act[:, f_base:]], axis=1)


def _resolve_output(o_hat, o_prev, *, out_eps, spiking, vdd):
    """Lines 23-25: classify the event and resolve the published output."""
    if spiking:
        out_changed = o_hat > 0.5 * vdd          # spike fired this tick
        return out_changed, jnp.where(out_changed, vdd, 0.0)
    return jnp.abs(o_hat - o_prev) > out_eps, o_hat


def lasana_step(surrogate, state: LasanaState, changed, x, t, clock_ns, *,
                out_eps: float = 0.02, spiking: bool = False,
                known_out=None, vdd: float = 1.5, fused: bool = True,
                fused_kernel: bool | None = None, megakernel_pack=None,
                megakernel_layout=None):
    """One digital tick for N circuits (Algorithm 1).

    surrogate  a :class:`repro.core.surrogate.Surrogate` — an immutable
             pytree of selected-predictor arrays. Because it is a pytree,
             it can (and should) be passed through ``jax.jit`` as a TRACED
             ARGUMENT alongside ``state``: the compiled step then serves
             any retrained surrogate with matching shapes without
             recompiling. A legacy ``PredictorBank`` also works (duck-typed
             ``.predict``) but only as a closed-over constant, and always
             on the per-call path.
    state    LasanaState
    changed  (N,) bool — set S as a mask
    x        (N, n_in) inputs applied at t (rows of X), or a
             :class:`RowBlocks`: the rows' inputs and parameters as column
             blocks at their own, smaller, shapes, for rows that repeat
             blocks (crossbar rows share input and weight segments). The
             fused schedule then builds each head's rows from the blocks
             inside the head's first dot and writes no (N, F) feature
             matrix (``_lasana_step_blocks``). Standalone mode, for a
             surrogate :func:`row_blocks_ok` accepts; the caller checks.
    t        scalar time (ns)
    known_out  (N,) optional — annotation mode: the output this tick is
             supplied by an external behavioral model, so M_O/M_V are
             skipped and LASANA only resolves the event class and predicts
             energy/latency. Callers substitute the behavioral state into
             ``state.v`` each tick (there is no staleness to catch up, but
             the merged-E2 *energy* of idle gaps is still accounted).
    vdd      spiking circuits only: the circuit's supply voltage. A fired
             spike is resolved to exactly ``vdd`` volts and the spike
             discriminator sits at ``vdd / 2`` — callers simulating a
             non-1.5-V_dd circuit MUST thread the circuit's own supply
             here or outputs silently diverge across backends.
    fused    take the fused inference hot path
             (``Surrogate.predict_heads``): derive features once per
             variant and evaluate same-family heads in batched stacked
             passes — three fused dispatches per tick instead of seven
             ``predict`` calls (one dispatch in annotation mode). Head
             stacking reorders float reductions, so fused and per-call
             results may differ by a few ULPs (rtol 1e-5; see
             docs/architecture.md "Inference hot path" and
             tests/test_fused.py). ``fused=False`` — or a surrogate
             without ``predict_heads`` — keeps the original
             one-``predict``-per-head formulation, the benchmark A/B
             baseline.
    fused_kernel  kernel-path override threaded to
             ``ops.fused_kernel_enabled`` (None = the
             ``REPRO_FUSED_KERNEL`` env default). When the kernel path is
             on AND the surrogate's heads are packable, the whole tick
             collapses further — from three stacked dispatches to ONE
             megakernel evaluation with all stages chained in VMEM (see
             kernels/tick_megakernel.py); otherwise the stacked
             ``predict_heads`` path routes its 3-layer MLP heads through
             the multi-head Pallas kernel as before.
    megakernel_pack / megakernel_layout  a pre-built
             ``tick_megakernel.pack_heads``/``pack_library`` pack —
             callers ticking many banks (network cascades) build one
             cross-kind pack and thread each kind's slice here; when
             None, the pack is derived from ``surrogate`` on the fly.
    returns  (new_state, e (N,), l (N,), o (N,))
    """
    if isinstance(x, RowBlocks):
        return _lasana_step_blocks(surrogate, state, changed, x, t,
                                   clock_ns, out_eps=out_eps,
                                   spiking=spiking, vdd=vdd)
    if fused and hasattr(surrogate, "predict_heads"):
        from repro.kernels import ops
        if ops.fused_kernel_enabled(fused_kernel):
            from repro.kernels import tick_megakernel as mk
            pack, layout = megakernel_pack, megakernel_layout
            if pack is None:
                pack, layout = mk.pack_heads(surrogate)
            if pack is not None:
                with jax.named_scope("heads"):
                    return mk.megakernel_step(
                        pack, surrogate.manifest.circuit, state, changed, x,
                        t, clock_ns, out_eps=out_eps, spiking=spiking,
                        known_out=known_out, vdd=vdd, layout=layout)
        return _lasana_step_fused(surrogate, state, changed, x, t, clock_ns,
                                  out_eps=out_eps, spiking=spiking,
                                  known_out=known_out, vdd=vdd,
                                  fused_kernel=fused_kernel)
    return _lasana_step_percall(surrogate, state, changed, x, t, clock_ns,
                                out_eps=out_eps, spiking=spiking,
                                known_out=known_out, vdd=vdd)


def _lasana_step_fused(surrogate, state, changed, x, t, clock_ns, *,
                       out_eps, spiking, known_out, vdd,
                       fused_kernel=None):
    """Algorithm 1 via ``Surrogate.predict_heads`` (the fused hot path).

    Head schedule (standalone mode) — the data dependencies allow at most
    three fused dispatches per tick:

      1. idle variant: M_ES + M_V stacked (the v' catch-up feeds the
         active features), on a tick where some circuit is stale only
      2. active variant: M_O + M_V + M_ES stacked (only M_O's resolved
         output is needed downstream, but M_V/M_ES don't depend on it —
         so the whole variant is one pass)
      3. transition variant: M_ED + M_L stacked (these DO consume M_O's
         resolved output through the o_new column)

    Annotation mode has no data dependencies (state and outputs are
    external), so the whole tick is ONE dispatch across all variants."""
    from repro.core.surrogate import _augment

    n = state.v.shape[0]
    annotate = known_out is not None
    circuit = surrogate.manifest.circuit

    # --- lines 3-9: catch up stale circuits with one merged idle event
    with jax.named_scope("features"):
        stale = stale_circuits(state, changed, t, clock_ns)
        tau_idle = jnp.maximum(t - state.t_last - clock_ns, 0.0)
        tau_act = jnp.full((n,), clock_ns, jnp.float32)

    def idle_features():
        with jax.named_scope("features"):
            return _augment(circuit, _features(jnp.zeros_like(x), state.v,
                                               tau_idle, state.params))

    if annotate:
        v_cur = state.v            # behavioral state: never stale
        v_new = v_cur              # caller overwrites with behavioral state
        o_hat = known_out
        aug_idle = idle_features()
        with jax.named_scope("features"):
            feats = _features(x, v_cur, tau_act, state.params)
            out_changed, o_resolved = _resolve_output(
                o_hat, state.o, out_eps=out_eps, spiking=spiking, vdd=vdd)
            aug_act = _augment(circuit, feats)
            aug_tr = _splice_transition(aug_act, feats.shape[1], state.o,
                                        o_resolved)
        with jax.named_scope("heads"):
            r = surrogate.predict_heads(
                feats_idle=aug_idle, feats_act=aug_act, feats_tr=aug_tr,
                heads={"idle": ("M_ES",), "act": ("M_ES",),
                       "tr": ("M_ED", "M_L")},
                augmented=True, fused_kernel=fused_kernel)
        e_s_idle = r["idle"]["M_ES"]
        e_s, e_d, lat = r["act"]["M_ES"], r["tr"]["M_ED"], r["tr"]["M_L"]
    else:
        def idle_eval():
            aug_idle = idle_features()
            with jax.named_scope("heads"):
                r1 = surrogate.predict_heads(
                    feats_idle=aug_idle, heads={"idle": ("M_ES", "M_V")},
                    augmented=True, fused_kernel=fused_kernel)
            return r1["idle"]["M_ES"], r1["idle"]["M_V"]

        e_s_idle, v_hat = _idle_when_stale(stale, idle_eval)

        # --- lines 10-22: one stacked pass over the whole active variant
        # (M_O's prediction chains into the transition-aware heads, but
        # M_V/M_ES don't consume it — so they ride the same dispatch)
        with jax.named_scope("features"):
            v_cur = jnp.where(stale, v_hat, state.v)
            feats = _features(x, v_cur, tau_act, state.params)
            aug_act = _augment(circuit, feats)
        with jax.named_scope("heads"):
            r2 = surrogate.predict_heads(
                feats_act=aug_act, heads={"act": ("M_O", "M_V", "M_ES")},
                augmented=True, fused_kernel=fused_kernel)
        o_hat, v_new, e_s = (r2["act"]["M_O"], r2["act"]["M_V"],
                             r2["act"]["M_ES"])
        with jax.named_scope("features"):
            out_changed, o_resolved = _resolve_output(
                o_hat, state.o, out_eps=out_eps, spiking=spiking, vdd=vdd)
            aug_tr = _splice_transition(aug_act, feats.shape[1], state.o,
                                        o_resolved)
        with jax.named_scope("heads"):
            r3 = surrogate.predict_heads(
                feats_tr=aug_tr, heads={"tr": ("M_ED", "M_L")},
                augmented=True, fused_kernel=fused_kernel)
        e_d, lat = r3["tr"]["M_ED"], r3["tr"]["M_L"]

    with jax.named_scope("update"):
        return _finish_tick(state, changed, stale, e_s_idle, e_d, e_s, lat,
                            out_changed, o_hat, v_cur, v_new, t,
                            spiking=spiking, vdd=vdd)


def _lasana_step_blocks(surrogate, state, changed, blocks, t, clock_ns, *,
                        out_eps, spiking, vdd):
    """Algorithm 1 on rows given as :class:`RowBlocks`: the fused
    path's schedule (idle M_ES + M_V when some row is stale -> active
    M_O + M_V + M_ES -> transition M_ED + M_L), each stage one
    ``predict_blocks`` dispatch.

    v, tau, o_prev and o_new are one-column blocks per row; inputs,
    parameters and derived columns keep the blocks' shapes. Every head
    computes what ``predict`` computes on the concatenated rows, so the
    records are the fused path's wherever head stacking is exact, and
    within its rtol 1e-5 elsewhere (docs/architecture.md, "Inference hot
    path")."""
    from repro.core.circuits import get_circuit
    from repro.core.surrogate import ALG1_HEADS
    circ = get_circuit(surrogate.manifest.circuit)
    grid = jnp.broadcast_shapes(blocks.x.shape[:-1],
                                blocks.params.shape[:-1])
    zero_x = jnp.zeros((1,) * len(grid) + blocks.x.shape[-1:], jnp.float32)

    def col(a):                       # one value per row, as a block
        return a.reshape(*grid, 1)

    def run(variant, rows, extra=()):
        heads = ALG1_HEADS[variant]
        r = surrogate.predict_blocks(heads, rows, extra=extra)
        return tuple(r[p].reshape(-1) for p in heads)

    # --- lines 3-9: catch up stale circuits with one merged idle event
    with jax.named_scope("features"):
        stale = stale_circuits(state, changed, t, clock_ns)
        tau_idle = jnp.maximum(t - state.t_last - clock_ns, 0.0)

    def idle_eval():
        with jax.named_scope("features"):
            idle = {"x": zero_x, "v": col(state.v), "tau": col(tau_idle),
                    "p": blocks.params,
                    **_derived(circ, zero_x, blocks.params)}
        with jax.named_scope("heads"):
            return run("idle", idle)

    e_s_idle, v_hat = _idle_when_stale(stale, idle_eval)

    # --- lines 10-22: the active rows, then the transition rows
    with jax.named_scope("features"):
        v_cur = jnp.where(stale, v_hat, state.v)
        act = {"x": blocks.x, "v": col(v_cur),
               "tau": jnp.full(zero_x.shape[:-1] + (1,), clock_ns,
                               jnp.float32),
               "p": blocks.params,
               **_derived(circ, blocks.x, blocks.params)}
    with jax.named_scope("heads"):
        o_hat, v_new, e_s = run("act", act)
    with jax.named_scope("features"):
        out_changed, o_resolved = _resolve_output(
            o_hat, state.o, out_eps=out_eps, spiking=spiking, vdd=vdd)
        tr = dict(act, o_prev=col(state.o), o_new=col(o_resolved))
    with jax.named_scope("heads"):
        e_d, lat = run("tr", tr, _TR_COLUMNS)

    with jax.named_scope("update"):
        return _finish_tick(state, changed, stale, e_s_idle, e_d, e_s, lat,
                            out_changed, o_hat, v_cur, v_new, t,
                            spiking=spiking, vdd=vdd)


def _lasana_step_percall(surrogate, state, changed, x, t, clock_ns, *,
                         out_eps, spiking, known_out, vdd):
    """Algorithm 1 with one ``predict`` dispatch per head (pre-fusion
    formulation; the fused-vs-unfused benchmark baseline)."""
    n = state.v.shape[0]
    zeros_x = jnp.zeros_like(x)
    annotate = known_out is not None

    # --- lines 3-9: catch up stale circuits with one merged idle event
    stale = changed & (state.t_last < t - clock_ns)
    tau_idle = jnp.maximum(t - state.t_last - clock_ns, 0.0)
    feats_idle = _features(zeros_x, state.v, tau_idle, state.params)
    e_s_idle = surrogate.predict("M_ES", feats_idle)
    if annotate:
        v_cur = state.v            # behavioral state: never stale
    else:
        v_hat = surrogate.predict("M_V", feats_idle)
        v_cur = jnp.where(stale, v_hat, state.v)

    # --- lines 10-22: run all predictors on the active batch.
    # M_O runs first so its prediction can chain into the transition-aware
    # energy/latency predictors (beyond-paper; see predictors.py).
    tau_act = jnp.full((n,), clock_ns, jnp.float32)
    feats = _features(x, v_cur, tau_act, state.params)
    if annotate:
        o_hat = known_out
        v_new = v_cur              # caller overwrites with behavioral state
    else:
        o_hat = surrogate.predict("M_O", feats)
        v_new = surrogate.predict("M_V", feats)

    # --- lines 23-29: select dynamic vs static by output behaviour
    out_changed, o_resolved = _resolve_output(
        o_hat, state.o, out_eps=out_eps, spiking=spiking, vdd=vdd)
    # chain the event-RESOLVED output (matches the E1 training distribution,
    # where spiking outputs are exactly V_dd) into the transition predictors
    feats_tr = _features(x, v_cur, tau_act, state.params, o_prev=state.o,
                         o_new=o_resolved)
    e_d = surrogate.predict("M_ED", feats_tr)
    e_s = surrogate.predict("M_ES", feats)
    lat = surrogate.predict("M_L", feats_tr)
    with jax.named_scope("update"):
        return _finish_tick(state, changed, stale, e_s_idle, e_d, e_s, lat,
                            out_changed, o_hat, v_cur, v_new, t,
                            spiking=spiking, vdd=vdd)


def _finish_tick(state, changed, stale, e_s_idle, e_d, e_s, lat,
                 out_changed, o_hat, v_cur, v_new, t, *, spiking, vdd):
    """Lines 23-30 tail shared by both inference paths: select dynamic vs
    static records and write back the masked state update."""
    e = jnp.where(stale, e_s_idle, 0.0)
    e_evt = jnp.where(out_changed, e_d, e_s)
    l_evt = jnp.where(out_changed, lat, 0.0)
    e = e + jnp.where(changed, e_evt, 0.0)
    l = jnp.where(changed, l_evt, 0.0)
    if spiking:
        o_out = jnp.where(changed, jnp.where(out_changed, vdd, 0.0), state.o)
    else:
        o_out = jnp.where(changed, o_hat, state.o)

    new_state = LasanaState(
        v=jnp.where(changed, v_new, v_cur),
        o=o_out,
        t_last=jnp.where(changed, t, state.t_last),   # line 30
        params=state.params,
    )
    return new_state, e, l, o_out


def lasana_step_reference(surrogate, state: LasanaState, changed, x, t,
                          clock_ns, *, out_eps: float = 0.02,
                          spiking: bool = False, vdd: float = 1.5):
    """Literal per-circuit transcription of Algorithm 1 (numpy, for tests)."""
    import numpy as np

    n = state.v.shape[0]
    v = np.asarray(state.v).copy()
    o = np.asarray(state.o).copy()
    t_last = np.asarray(state.t_last).copy()
    params = np.asarray(state.params)
    x = np.asarray(x)
    e = np.zeros(n)
    l = np.zeros(n)
    changed = np.asarray(changed)

    for i in range(n):
        if not changed[i]:
            continue
        if t_last[i] < t - clock_ns:                      # lines 4-6
            tau = t - t_last[i] - clock_ns
            fi = np.concatenate([np.zeros_like(x[i]), [v[i]], [tau], params[i]])
            v[i] = float(surrogate.predict_np("M_V", fi[None])[0])
            e[i] += float(surrogate.predict_np("M_ES", fi[None])[0])
        f = np.concatenate([x[i], [v[i]], [clock_ns], params[i]])
        o_hat = float(surrogate.predict_np("M_O", f[None])[0])
        v_new = float(surrogate.predict_np("M_V", f[None])[0])
        if spiking:
            changed_out = o_hat > 0.5 * vdd
            o_res = vdd if changed_out else 0.0
        else:
            changed_out = abs(o_hat - o[i]) > out_eps
            o_res = o_hat
        fp = np.concatenate([x[i], [v[i]], [clock_ns], params[i], [o[i]],
                             [o_res]])
        e_d = float(surrogate.predict_np("M_ED", fp[None])[0])
        e_s = float(surrogate.predict_np("M_ES", f[None])[0])
        lat = float(surrogate.predict_np("M_L", fp[None])[0])
        if changed_out:                                    # lines 24-27
            e[i] += e_d
            l[i] = lat
        else:
            e[i] += e_s
        v[i] = v_new
        if spiking:
            o[i] = vdd if changed_out else 0.0
        else:
            o[i] = o_hat
        t_last[i] = t
    new_state = LasanaState(v=jnp.asarray(v, jnp.float32),
                            o=jnp.asarray(o, jnp.float32),
                            t_last=jnp.asarray(t_last, jnp.float32),
                            params=state.params)
    return new_state, e, l, np.asarray(new_state.o)
