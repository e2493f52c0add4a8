"""Whole-tick LASANA megakernel: Algorithm 1 as ONE kernel launch.

PR 5 collapsed the per-tick hot path to three stacked ``predict_heads``
dispatches (idle -> act -> transition); each still round-trips its
intermediates through HBM and relaunches. This module chains all three
stages of ``wrapper.lasana_step`` inside a single ``pallas_call``: the
surrogate weights and per-head standardizers stay VMEM-resident while the
grid walks circuit blocks, and the idle catch-up, active-variant heads,
output resolution, transition splice, and the Algorithm-1 record tail
(`_finish_tick`) all run on scratch values that never leave the core.
The ambitious end state is ``network_tick_chunk``: a time-looped variant
in the style of ``kernels/lif_scan.py`` that carries circuit state in
VMEM across a whole streaming chunk, one launch per chunk.

Head packing
------------
:func:`pack_heads` lifts a ``Surrogate``'s five Algorithm-1 predictors
into TWO canonical stacks — the A stack (idle/act feature width) holding
``M_ES``/``M_V``/``M_O`` and the T stack (transition width) holding
``M_ED``/``M_L`` — each a uniform ``(P, F, H1)/(P, H1, H2)/(P, H2, 1)``
array layout plus standardizers, regardless of predictor family. Layout
is uniform so one set of kernel refs serves every head, but EVALUATION
stays native-cost per family (:func:`_eval_stack` dispatches statically
on :class:`PackLayout` tags: a mean head is one broadcast, a linear head
one dot, only true MLP heads pay three matmuls). :func:`pack_library`
extends the stacking *across* circuit kinds in mixed graphs: every
kind's stacks pad to a common width and concatenate, so one resident
weight block serves all banks and a kind addresses its own heads through
static stack offsets.

Numerics contract (enforced by tests/test_megakernel.py): discrete
records (outputs, event classes, spike trains, t_last) are bit-identical
to the stacked-dispatch and per-call paths; continuous heads
(energy/latency/v) agree to rtol 1e-5 — head packing reorders float
reductions exactly like PR 5's stacking did. The jnp body and the Pallas
kernel compute the same math; ``REPRO_TICK_PALLAS`` (or the
``pallas=``/``ops.tick_pallas_enabled`` override) picks the launcher, and
interpret mode lets CPU CI execute the kernel code path.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.circuits import augment_features, get_circuit
from repro.core.surrogate import F32_DOT
from repro.core.wrapper import (LasanaState, _features, _finish_tick,
                                _resolve_output, _splice_transition)
from repro.kernels import ops
from repro.kernels.lif_scan import block_ticks, time_blocks

# Stack membership, in stack order. The A stack serves BOTH the idle and
# the active variant (same feature width); the T stack serves the
# transition variant (o_prev/o_new columns spliced in).
PACK_HEADS_A = ("M_ES", "M_V", "M_O")
PACK_HEADS_T = ("M_ED", "M_L")
_PACKABLE = ("mean", "linear", "mlp")
_STACK_KEYS = ("x_mu", "x_sd", "y_mu", "y_sd",
               "w0", "b0", "w1", "b1", "w2", "b2", "scale")


@dataclasses.dataclass(frozen=True)
class PackLayout:
    """Static (hashable) metadata of one circuit kind's slice of a pack.

    ``a_fams``/``t_fams`` are the per-head family tags in stack order —
    they drive the native-cost dispatch in :func:`_eval_stack` and are
    part of every compiled program's identity. ``a_off``/``t_off`` are the
    kind's first stack indices in a :func:`pack_library` unified pack
    (0 for a single-kind pack)."""

    a_fams: tuple
    t_fams: tuple
    a_off: int = 0
    t_off: int = 0


def _canonical(arrays, fam, f, h1, h2, scale):
    """One head's params in the uniform (F, H1)/(H1, H2)/(H2, 1) layout.

    mean:   y = b2 (x ignored; standardizers neutral)
    linear: y = ((x - x_mu) / x_sd) @ w0[:, 0] + b2
    mlp:    the production 3-layer net, zero-padded into (h1, h2) —
            padded hidden units have zero weights in AND out, and
            relu(0) = 0, so padding contributes exactly nothing.
    Unused slots hold zeros (x_sd holds ONES — a zero pad would divide
    by zero and poison downstream ops with NaNs)."""
    f32 = jnp.float32
    out = {
        "x_mu": jnp.zeros((f,), f32),
        "x_sd": jnp.ones((f,), f32),
        "y_mu": jnp.zeros((1,), f32),
        "y_sd": jnp.ones((1,), f32),
        "w0": jnp.zeros((f, h1), f32),
        "b0": jnp.zeros((h1,), f32),
        "w1": jnp.zeros((h1, h2), f32),
        "b1": jnp.zeros((h2,), f32),
        "w2": jnp.zeros((h2, 1), f32),
        "b2": jnp.zeros((1,), f32),
        "scale": jnp.full((1,), scale, f32),
    }
    if fam == "mean":
        out["b2"] = jnp.asarray(arrays["mu"], f32).reshape(1)
    elif fam == "linear":
        out["x_mu"] = jnp.asarray(arrays["mu"], f32)
        out["x_sd"] = jnp.asarray(arrays["sd"], f32)
        out["w0"] = out["w0"].at[:, 0].set(jnp.asarray(arrays["w"][:-1], f32))
        out["b2"] = jnp.asarray(arrays["w"][-1:], f32)
    else:
        out["x_mu"] = jnp.asarray(arrays["x_mu"], f32)
        out["x_sd"] = jnp.asarray(arrays["x_sd"], f32)
        out["y_mu"] = jnp.asarray(arrays["y_mu"], f32).reshape(1)
        out["y_sd"] = jnp.asarray(arrays["y_sd"], f32).reshape(1)
        out["w0"] = ops._pad_to(jnp.asarray(arrays["w0"], f32), h1, 1)
        out["b0"] = ops._pad_to(jnp.asarray(arrays["b0"], f32), h1, 0)
        out["w1"] = ops._pad_to(
            ops._pad_to(jnp.asarray(arrays["w1"], f32), h1, 0), h2, 1)
        out["b1"] = ops._pad_to(jnp.asarray(arrays["b1"], f32), h2, 0)
        out["w2"] = ops._pad_to(jnp.asarray(arrays["w2"], f32), h2, 0)
        out["b2"] = jnp.asarray(arrays["b2"], f32).reshape(1)
    return out


def _mlp_layers(arrays) -> int:
    return sum(1 for k in arrays if k.startswith("w"))


def pack_heads(surrogate):
    """Build (pack, :class:`PackLayout`) for one surrogate, or (None, None).

    Eligibility is fully static (manifest families + array shapes), so the
    decision — and the fallback to the PR 5 stacked-dispatch path — never
    burns a trace-time branch: all five Algorithm-1 predictors present,
    every family packable (mean/linear/mlp with the production 3-layer
    config), the circuit registered, and trained feature widths matching
    the circuit's augmented widths. The arrays themselves may be traced
    (the pack is rebuilt from surrogate leaves inside jit, so hot-swapped
    surrogates reuse the compiled program)."""
    try:
        man = surrogate.manifest
        params = surrogate.params
    except AttributeError:
        return None, None
    try:
        circ = get_circuit(man.circuit)
    except KeyError:
        return None, None
    if circ is None or not hasattr(circ, "n_inputs"):
        return None, None
    names = PACK_HEADS_A + PACK_HEADS_T
    if not set(names) <= set(man.predictors):
        return None, None
    fams = {p: man.family_of(p) for p in names}
    if any(f not in _PACKABLE for f in fams.values()):
        return None, None
    f_raw = circ.n_inputs + 2 + circ.n_params
    probe = jnp.zeros((1, f_raw), jnp.float32)
    f_aug = int(augment_features(circ, probe).shape[1])
    probe_tr = jnp.zeros((1, f_raw + 2), jnp.float32)
    f_tr = int(augment_features(circ, probe_tr).shape[1])

    def native_width(p):
        a, fam = params[p], fams[p]
        if fam == "mlp":
            if _mlp_layers(a) != 3:
                return None
            return int(a["w0"].shape[0])
        if fam == "linear":
            return int(a["mu"].shape[0])
        return f_aug if p in PACK_HEADS_A else f_tr    # mean: width-free

    if any(native_width(p) != f_aug for p in PACK_HEADS_A):
        return None, None
    if any(native_width(p) != f_tr for p in PACK_HEADS_T):
        return None, None
    h1 = max([int(params[p]["w0"].shape[1])
              for p in names if fams[p] == "mlp"], default=1)
    h2 = max([int(params[p]["w1"].shape[1])
              for p in names if fams[p] == "mlp"], default=1)

    def stack(pnames, f):
        heads = [_canonical(params[p], fams[p], f, h1, h2, man.scale_of(p))
                 for p in pnames]
        return {k: jnp.stack([h[k] for h in heads]) for k in _STACK_KEYS}

    pack = {"a": stack(PACK_HEADS_A, f_aug), "t": stack(PACK_HEADS_T, f_tr)}
    layout = PackLayout(a_fams=tuple(fams[p] for p in PACK_HEADS_A),
                        t_fams=tuple(fams[p] for p in PACK_HEADS_T))
    return pack, layout


def _pad_stack(s, f, h1, h2):
    """Pad one canonical stack to (f, h1, h2); exact by construction
    (zero weights, ones x_sd — see _canonical)."""
    return {
        "x_mu": ops._pad_to(s["x_mu"], f, 1),
        "x_sd": ops._pad_to(s["x_sd"], f, 1, value=1.0),
        "y_mu": s["y_mu"], "y_sd": s["y_sd"], "scale": s["scale"],
        "w0": ops._pad_to(ops._pad_to(s["w0"], f, 1), h1, 2),
        "b0": ops._pad_to(s["b0"], h1, 1),
        "w1": ops._pad_to(ops._pad_to(s["w1"], h1, 1), h2, 2),
        "b1": ops._pad_to(s["b1"], h2, 1),
        "w2": ops._pad_to(s["w2"], h2, 1),
        "b2": s["b2"],
    }


def pack_library(banks):
    """Cross-kind head stacking: one unified pack for a whole library.

    Every kind's A/T stacks pad to the library-wide max feature/hidden
    widths and concatenate along the head axis, so a mixed graph keeps ONE
    resident weight block and each kind addresses its heads through the
    static ``a_off``/``t_off`` in its :class:`PackLayout`. Returns
    ``(pack, {kind: PackLayout})`` — or ``(None, {})`` if any kind is
    ineligible (callers fall back to per-kind packs / stacked dispatch)."""
    kinds = sorted(banks.kinds())
    packs, layouts = {}, {}
    for kind in kinds:
        p, lo = pack_heads(banks[kind])
        if p is None:
            return None, {}
        packs[kind] = p
        layouts[kind] = lo
    if len(kinds) == 1:
        return packs[kinds[0]], layouts
    f_a = max(p["a"]["w0"].shape[1] for p in packs.values())
    f_t = max(p["t"]["w0"].shape[1] for p in packs.values())
    h1 = max(p["a"]["w0"].shape[2] for p in packs.values())
    h2 = max(p["a"]["w1"].shape[2] for p in packs.values())
    a_parts = [_pad_stack(packs[k]["a"], f_a, h1, h2) for k in kinds]
    t_parts = [_pad_stack(packs[k]["t"], f_t, h1, h2) for k in kinds]
    pack = {
        "a": {k: jnp.concatenate([p[k] for p in a_parts]) for k in _STACK_KEYS},
        "t": {k: jnp.concatenate([p[k] for p in t_parts]) for k in _STACK_KEYS},
    }
    offs = {}
    a_off = t_off = 0
    for kind in kinds:
        offs[kind] = PackLayout(a_fams=layouts[kind].a_fams,
                                t_fams=layouts[kind].t_fams,
                                a_off=a_off, t_off=t_off)
        a_off += len(PACK_HEADS_A)
        t_off += len(PACK_HEADS_T)
    return pack, offs


def _pad_cols(x, f):
    """Zero-pad feature columns up to a stack's width (inert: padded
    columns carry x_sd=1 standardizers and zero weights)."""
    return ops._pad_to(x, f, 1)


def _eval_stack(s, x, off: int, fams):
    """Evaluate heads ``off .. off+len(fams)-1`` of canonical stack ``s``
    on augmented features ``x`` (N, F) — native cost per family.

    The uniform array layout exists for VMEM residency, NOT to force every
    head through MLP math: the family tags are static, so a mean head
    lowers to one broadcast and a linear head to one dot. All families
    share the destandardize + scale tail."""
    n = x.shape[0]
    f32 = jnp.float32
    ys = []
    for j, fam in enumerate(fams):
        i = off + j
        if fam == "mean":
            y = jnp.broadcast_to(s["b2"][i, 0], (n,))
        elif fam == "linear":
            xs = (x - s["x_mu"][i]) / s["x_sd"][i]
            # (F, 1) column, not an (F,) vector: Mosaic has no rule for a
            # matrix-vector product's 1-D accumulator
            y = jnp.dot(xs, s["w0"][i, :, :1], precision=F32_DOT,
                        preferred_element_type=f32)[:, 0] + s["b2"][i, 0]
        else:
            xs = (x - s["x_mu"][i]) / s["x_sd"][i]
            h = jax.nn.relu(jnp.dot(xs, s["w0"][i], precision=F32_DOT,
                                    preferred_element_type=f32) + s["b0"][i])
            h = jax.nn.relu(jnp.dot(h, s["w1"][i], precision=F32_DOT,
                                    preferred_element_type=f32) + s["b1"][i])
            y = jnp.dot(h, s["w2"][i], precision=F32_DOT,
                        preferred_element_type=f32)[:, 0] + s["b2"][i, 0]
        ys.append((y * s["y_sd"][i, 0] + s["y_mu"][i, 0]) / s["scale"][i, 0])
    return ys


def _tick_arrays(sA, sT, v, o, t_last, params, changed, x, t, *, circuit,
                 clock_ns, out_eps, spiking, vdd, annotate, known_out,
                 layout, skip):
    """The whole-tick dataflow on raw arrays — shared verbatim by the jnp
    body and the Pallas kernel, so the two launchers cannot drift.

    ``skip=True`` (jnp body only) wraps the idle stage in a
    ``lax.cond(any(stale))``: the skip branch returns zeros, which is
    EXACT because ``_finish_tick`` only consumes ``e_s_idle``/``v_hat``
    where ``stale`` — the main steady-state win over the 3-dispatch path,
    which always pays the idle evaluation. Kernel bodies run ``skip=False``
    (no conds inside a kernel); the results are identical either way.

    Returns ``(v', o', t_last', e, l)``."""
    circ = get_circuit(circuit)
    n = v.shape[0]
    f32 = jnp.float32
    f_a = sA["w0"].shape[1]
    f_t = sT["w0"].shape[1]
    ia, it = layout.a_off, layout.t_off

    # --- idle stage (Algorithm 1 lines 3-9): one merged catch-up event
    stale = changed & (t_last < t - clock_ns)
    tau_idle = jnp.maximum(t - t_last - clock_ns, 0.0)
    n_idle_heads = 1 if annotate else 2      # annotation never catches up v

    def idle_eval(_):
        fi = _features(jnp.zeros_like(x), v, tau_idle, params)
        ai = _pad_cols(augment_features(circ, fi), f_a)
        ys = _eval_stack(sA, ai, ia, layout.a_fams[:n_idle_heads])
        if annotate:
            return ys[0], jnp.zeros((n,), f32)
        return ys[0], ys[1]                  # e_s_idle, v_hat

    if skip:
        e_s_idle, v_hat = jax.lax.cond(
            jnp.any(stale), idle_eval,
            lambda _: (jnp.zeros((n,), f32), jnp.zeros((n,), f32)), None)
    else:
        e_s_idle, v_hat = idle_eval(None)

    # --- active stage (lines 10-22) on the caught-up state
    v_cur = v if annotate else jnp.where(stale, v_hat, v)
    tau_act = jnp.full((n,), clock_ns, f32)
    feats = _features(x, v_cur, tau_act, params)
    aug_act = augment_features(circ, feats)
    aa = _pad_cols(aug_act, f_a)
    if annotate:
        (e_s,) = _eval_stack(sA, aa, ia, layout.a_fams[:1])
        o_hat = known_out
        v_new = v_cur                        # caller substitutes behavioral v
    else:
        e_s, v_new, o_hat = _eval_stack(sA, aa, ia, layout.a_fams)

    # --- transition stage (lines 23-29): splice the resolved output in
    out_changed, o_resolved = _resolve_output(
        o_hat, o, out_eps=out_eps, spiking=spiking, vdd=vdd)

    def tr_eval(_):
        aug_tr = _splice_transition(aug_act, feats.shape[1], o, o_resolved)
        at = _pad_cols(aug_tr, f_t)
        return tuple(_eval_stack(sT, at, it, layout.t_fams))

    if skip:
        # ``_finish_tick`` consumes ``e_d``/``lat`` only where
        # ``changed & out_changed``, so a tick on which no event resolves
        # skips the whole transition stack — exact, same argument as the
        # idle skip above
        e_d, lat = jax.lax.cond(
            jnp.any(changed & out_changed), tr_eval,
            lambda _: (jnp.zeros((n,), f32), jnp.zeros((n,), f32)), None)
    else:
        e_d, lat = tr_eval(None)

    state = LasanaState(v=v, o=o, t_last=t_last, params=params)
    new_state, e, l, _ = _finish_tick(
        state, changed, stale, e_s_idle, e_d, e_s, lat, out_changed,
        o_hat, v_cur, v_new, t, spiking=spiking, vdd=vdd)
    return new_state.v, new_state.o, new_state.t_last, e, l


def megakernel_step(pack, circuit, state, changed, x, t, clock_ns, *,
                    out_eps: float = 0.02, spiking: bool = False,
                    known_out=None, vdd: float = 1.5, layout: PackLayout,
                    pallas: bool | None = None):
    """One whole LASANA tick through the megakernel path.

    Drop-in for ``wrapper.lasana_step`` given a pre-built head pack;
    returns ``(new_state, e, l, o)``. ``pallas=None`` resolves the
    launcher via :func:`ops.tick_pallas_enabled`; the jnp body
    additionally wraps the whole tick in ``lax.cond(any(changed))`` —
    exact, because every record and state write-back is masked by
    ``changed`` in ``_finish_tick``."""
    ops.record_dispatch("megakernel_step")
    if pallas is None:
        pallas = ops.tick_pallas_enabled()
    annotate = known_out is not None
    if pallas:
        known = known_out if annotate else jnp.zeros_like(state.v)
        v, o, tl, e, l = network_tick(
            pack, state.v, state.o, state.t_last, state.params, changed,
            x, t, known, circuit=circuit, clock_ns=clock_ns, layout=layout,
            out_eps=out_eps, spiking=spiking, vdd=vdd, annotate=annotate)
        new_state = LasanaState(v=v, o=o, t_last=tl, params=state.params)
        return new_state, e, l, new_state.o

    def run(_):
        return _tick_arrays(
            pack["a"], pack["t"], state.v, state.o, state.t_last,
            state.params, changed, x, t, circuit=circuit,
            clock_ns=clock_ns, out_eps=out_eps, spiking=spiking, vdd=vdd,
            annotate=annotate, known_out=known_out, layout=layout,
            skip=True)

    def idle(_):
        z = jnp.zeros_like(state.v)
        return state.v, state.o, state.t_last, z, z

    v, o, tl, e, l = jax.lax.cond(jnp.any(changed), run, idle, None)
    new_state = LasanaState(v=v, o=o, t_last=tl, params=state.params)
    return new_state, e, l, new_state.o


def megakernel_chunk(pack, circuit, state, changed_seq, x_seq, t_seq,
                     clock_ns, *, out_eps: float = 0.02,
                     spiking: bool = True, vdd: float = 1.5,
                     layout: PackLayout, pallas: bool | None = None):
    """A whole chunk of ticks; the time-looped ambitious end state.

    jnp body: a ``lax.scan`` of :func:`megakernel_step` (bit-identical to
    ticking one step at a time, so streaming chunk boundaries cannot
    change results). Pallas: ONE ``network_tick_chunk`` launch whose
    in-kernel time loop carries v/o/t_last in VMEM across the chunk.
    Returns ``(new_state, o_seq, e_seq, l_seq)`` with (T, N) sequences."""
    if pallas is None:
        pallas = ops.tick_pallas_enabled()
    if pallas:
        v, o, tl, o_seq, e_seq, l_seq = network_tick_chunk(
            pack, state.v, state.o, state.t_last, state.params,
            changed_seq, x_seq, t_seq, circuit=circuit, clock_ns=clock_ns,
            layout=layout, out_eps=out_eps, spiking=spiking, vdd=vdd)
        new_state = LasanaState(v=v, o=o, t_last=tl, params=state.params)
        return new_state, o_seq, e_seq, l_seq

    def tick(st, xs):
        ch, xi, t = xs
        ns, e, l, o = megakernel_step(
            pack, circuit, st, ch, xi, t, clock_ns, out_eps=out_eps,
            spiking=spiking, vdd=vdd, layout=layout, pallas=False)
        return ns, (o, e, l)

    new_state, (o_seq, e_seq, l_seq) = jax.lax.scan(
        tick, state, (changed_seq, x_seq, t_seq))
    return new_state, o_seq, e_seq, l_seq


# ---------------------------------------------------------------------------
# Pallas launchers


def _resident(arr):
    """BlockSpec pinning a whole array into every grid step (VMEM-resident
    weights/standardizers, exactly like mlp_surrogate's head stacks)."""
    nd = arr.ndim
    return pl.BlockSpec(arr.shape, lambda *_, _nd=nd: (0,) * _nd)


def _stack_refs(refs, base):
    return {k: refs[base + j][...] for j, k in enumerate(_STACK_KEYS)}


_N_STACK = len(_STACK_KEYS)


def _make_tick_kernel(circuit, clock_ns, out_eps, spiking, vdd, annotate,
                      layout):
    """Kernel body: both head stacks resident, one N-block of circuits per
    grid step, all three stages chained in registers/VMEM scratch."""

    def kernel(*refs):
        sA = _stack_refs(refs, 0)
        sT = _stack_refs(refs, _N_STACK)
        i = 2 * _N_STACK
        v, o, t_last = refs[i][...], refs[i + 1][...], refs[i + 2][...]
        params = refs[i + 3][...]
        changed = refs[i + 4][...] > 0.5
        x = refs[i + 5][...]
        t = refs[i + 6][0]                  # SMEM scalar
        known = refs[i + 7][...] if annotate else None
        v_ref, o_ref, tl_ref, e_ref, l_ref = refs[i + 8:i + 13]
        v1, o1, tl1, e1, l1 = _tick_arrays(
            sA, sT, v, o, t_last, params, changed, x, t, circuit=circuit,
            clock_ns=clock_ns, out_eps=out_eps, spiking=spiking, vdd=vdd,
            annotate=annotate, known_out=known, layout=layout, skip=False)
        v_ref[...] = v1
        o_ref[...] = o1
        tl_ref[...] = tl1
        e_ref[...] = e1
        l_ref[...] = l1

    return kernel


def _padded_pack(pack):
    """Pad stack dims to lane multiples for the hardware path (exact —
    zero weights, ones x_sd; the N-padding counterpart lives in the
    callers)."""
    f_a = ops._ceil_to(pack["a"]["w0"].shape[1], 128)
    f_t = ops._ceil_to(pack["t"]["w0"].shape[1], 128)
    h1 = ops._ceil_to(pack["a"]["w0"].shape[2], 128)
    h2 = ops._ceil_to(pack["a"]["w1"].shape[2], 128)
    return {"a": _pad_stack(pack["a"], f_a, h1, h2),
            "t": _pad_stack(pack["t"], f_t, h1, h2)}


_TICK_STATICS = ("circuit", "clock_ns", "layout", "out_eps", "spiking",
                 "vdd", "annotate", "block_n", "interpret")


@functools.partial(jax.jit, static_argnames=_TICK_STATICS)
def network_tick(pack, v, o, t_last, params, changed, x, t, known, *,
                 circuit, clock_ns, layout: PackLayout,
                 out_eps: float = 0.02, spiking: bool = False,
                 vdd: float = 1.5, annotate: bool = False,
                 block_n: int = 1024, interpret: bool | None = None):
    """One whole LASANA tick as ONE ``pallas_call``.

    Ragged shapes are handled HERE (the raw kernel is shape-strict): N
    pads to ``block_n`` with ``changed=False`` rows (every write-back is
    masked by ``changed``, so pad rows are inert) and the pack's F/H dims
    pad to 128 — padded feature columns get ``x_sd = 1`` (the zero pad
    would divide by zero; see the named regression tests) and zero
    weights. Returns ``(v', o', t_last', e, l)``, each ``(N,)``."""
    interpret = ops._interpret_default() if interpret is None else interpret
    n = v.shape[0]
    n_pad = ops._ceil_to(n, block_n)
    pp = _padded_pack(pack)
    f32 = jnp.float32
    inputs = (
        *[pp["a"][k] for k in _STACK_KEYS],
        *[pp["t"][k] for k in _STACK_KEYS],
        ops._pad_to(v, n_pad, 0),
        ops._pad_to(o, n_pad, 0),
        ops._pad_to(t_last, n_pad, 0),
        ops._pad_to(params, n_pad, 0),
        ops._pad_to(changed.astype(f32), n_pad, 0),
        ops._pad_to(x, n_pad, 0),
        jnp.reshape(jnp.asarray(t, f32), (1,)),
        ops._pad_to(known, n_pad, 0),
    )
    n_blk = pl.BlockSpec((block_n,), lambda i: (i,))
    in_specs = [
        *[_resident(a) for a in inputs[:2 * _N_STACK]],
        n_blk, n_blk, n_blk,
        pl.BlockSpec((block_n, params.shape[1]), lambda i: (i, 0)),
        n_blk,
        pl.BlockSpec((block_n, x.shape[1]), lambda i: (i, 0)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        n_blk,
    ]
    kernel = _make_tick_kernel(circuit, clock_ns, out_eps, spiking, vdd,
                               annotate, layout)
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // block_n,),
        in_specs=in_specs,
        out_specs=[n_blk] * 5,
        out_shape=[jax.ShapeDtypeStruct((n_pad,), f32)] * 5,
        interpret=interpret,
    )(*inputs)
    return tuple(a[:n] for a in out)


def _make_chunk_kernel(circuit, clock_ns, out_eps, spiking, vdd, layout,
                       t_steps, tb, t_pad):
    """Time-looped kernel body: circuit state (v, o, t_last) lives in
    VMEM across the whole chunk; per-tick inputs are sliced and per-tick
    outputs stored inside the ``fori_loop`` (lif_scan's structure, one
    level up the stack). The grid's second axis walks time blocks; the
    state output blocks stay resident across it and carry the state from
    one time block to the next."""

    def kernel(*refs):
        sA = _stack_refs(refs, 0)
        sT = _stack_refs(refs, _N_STACK)
        i = 2 * _N_STACK
        params = refs[i + 3][...]
        ch_ref, x_ref, t_ref = refs[i + 4], refs[i + 5], refs[i + 6]
        v_ref, o_ref, tl_ref = refs[i + 7], refs[i + 8], refs[i + 9]
        os_ref, es_ref, ls_ref = refs[i + 10], refs[i + 11], refs[i + 12]
        n_in = x_ref.shape[0]

        @pl.when(pl.program_id(1) == 0)
        def _():
            v_ref[...] = refs[i][...]
            o_ref[...] = refs[i + 1][...]
            tl_ref[...] = refs[i + 2][...]

        def body(ti, carry):
            v, o, tl = carry
            row = pl.ds(ti, 1)
            ch = ch_ref[row, :][0] > 0.5
            xx = jnp.stack([x_ref[c, row, :][0] for c in range(n_in)],
                           axis=-1)
            t = t_ref[0, 0, ti]             # SMEM scalar
            v1, o1, tl1, e1, l1 = _tick_arrays(
                sA, sT, v, o, tl, params, ch, xx, t, circuit=circuit,
                clock_ns=clock_ns, out_eps=out_eps, spiking=spiking,
                vdd=vdd, annotate=False, known_out=None, layout=layout,
                skip=False)
            os_ref[row, :] = o1[None]
            es_ref[row, :] = e1[None]
            ls_ref[row, :] = l1[None]
            return v1, o1, tl1

        v, o, tl = jax.lax.fori_loop(
            0, block_ticks(t_steps, tb, t_pad), body,
            (v_ref[...], o_ref[...], tl_ref[...]))
        v_ref[...] = v
        o_ref[...] = o
        tl_ref[...] = tl

    return kernel


@functools.partial(jax.jit, static_argnames=tuple(
    s for s in _TICK_STATICS if s != "annotate"))
def network_tick_chunk(pack, v, o, t_last, params, changed_seq, x_seq,
                       t_seq, *, circuit, clock_ns, layout: PackLayout,
                       out_eps: float = 0.02, spiking: bool = True,
                       vdd: float = 1.5, block_n: int = 1024,
                       interpret: bool | None = None):
    """A whole chunk of LASANA ticks as ONE time-looped ``pallas_call``.

    ``changed_seq`` (T, N) bool, ``x_seq`` (T, N, n_in), ``t_seq`` (T,)
    tick times. Circuit state never leaves VMEM between ticks; only the
    per-tick record sequences stream out. The grid walks circuit blocks
    and, inside each, time blocks (``lif_scan.time_blocks``), so VMEM use
    is bounded for any chunk length; stimulus columns are laid out
    ``(n_in, T, N)`` so every per-tick row is lane-dense. Returns
    ``(v', o', t_last', o_seq, e_seq, l_seq)``."""
    interpret = ops._interpret_default() if interpret is None else interpret
    n = v.shape[0]
    t_steps = changed_seq.shape[0]
    n_pad = ops._ceil_to(n, block_n)
    tb, t_pad = time_blocks(t_steps)
    pp = _padded_pack(pack)
    f32 = jnp.float32
    pad_nt = lambda a: jnp.pad(                       # noqa: E731
        a, [(0, 0)] * (a.ndim - 2) + [(0, t_pad - t_steps), (0, n_pad - n)])
    inputs = (
        *[pp["a"][k] for k in _STACK_KEYS],
        *[pp["t"][k] for k in _STACK_KEYS],
        ops._pad_to(v, n_pad, 0),
        ops._pad_to(o, n_pad, 0),
        ops._pad_to(t_last, n_pad, 0),
        ops._pad_to(params, n_pad, 0),
        pad_nt(changed_seq.astype(f32)),
        pad_nt(jnp.moveaxis(x_seq, 2, 0)),
        ops._pad_to(jnp.asarray(t_seq, f32).reshape(t_steps), t_pad, 0
                    ).reshape(t_pad // tb, 1, tb),
    )
    n_blk = pl.BlockSpec((block_n,), lambda i, j: (i,))
    seq_blk = pl.BlockSpec((tb, block_n), lambda i, j: (j, i))
    in_specs = [
        *[_resident(a) for a in inputs[:2 * _N_STACK]],
        n_blk, n_blk, n_blk,
        pl.BlockSpec((block_n, params.shape[1]), lambda i, j: (i, 0)),
        seq_blk,
        pl.BlockSpec((x_seq.shape[2], tb, block_n), lambda i, j: (0, j, i)),
        pl.BlockSpec((1, 1, tb), lambda i, j: (j, 0, 0),
                     memory_space=pltpu.SMEM),
    ]
    kernel = _make_chunk_kernel(circuit, clock_ns, out_eps, spiking, vdd,
                                layout, t_steps, tb, t_pad)
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // block_n, t_pad // tb),
        in_specs=in_specs,
        out_specs=[n_blk] * 3 + [seq_blk] * 3,
        out_shape=[jax.ShapeDtypeStruct((n_pad,), f32)] * 3
        + [jax.ShapeDtypeStruct((t_pad, n_pad), f32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*inputs)
    v1, o1, tl1, o_seq, e_seq, l_seq = out
    return (v1[:n], o1[:n], tl1[:n], o_seq[:t_steps, :n],
            e_seq[:t_steps, :n], l_seq[:t_steps, :n])
