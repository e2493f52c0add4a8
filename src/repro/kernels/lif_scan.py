"""Golden LIF transient-integrator kernel — the "SPICE farm" hot loop.

Grid over circuit blocks; each invocation integrates one full digital clock
period (n_substeps exponential-Euler steps) for a block of neurons with the
(bn, 3) state, (bn, 3) stimulus and (bn, 4) knob tensors VMEM-resident.
The sub-step loop is a ``fori_loop`` over registers — zero HBM traffic
between sub-steps, vs. the pure-XLA scan that round-trips the carry.

Dataset generation maps this kernel over (runs x timesteps); it must match
``repro.core.circuits.LIFNeuron.step`` bit-for-bit in fp32 (tests sweep
shapes against that oracle).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.circuits import LIFNeuron


def _period_math(circ: LIFNeuron, st, xx, pp):
    """Integrate ONE clock period for a block — the shared in-register
    body of both the single-period kernel and the time-looped chunk
    kernel (so the two can never drift numerically). ``st``, ``xx`` and
    ``pp`` are the state, stimulus and knob columns, each a tuple of
    ``(bn,)`` vectors. Returns
    ``((v, adap, ref), out, energy, latency, spiked)``."""
    dt = circ.clock_ns / circ.n_substeps
    v0, adap0, ref0 = st
    w, x, n_spk = xx
    v_leak, v_th_knob, v_adap, v_ref = pp

    i_in = circ.g_syn * w * x * n_spk / 5.0
    leak_rate = (circ.i_leak0 / circ.c_mem) * jnp.exp(
        (v_leak - 0.5) / circ.ut) * 1e-9
    tau_ref_ns = 2.0 + 10.0 * (v_ref - 0.5)
    thresh = 0.8 + 1.0 * (v_th_knob - 0.5)
    adap_gain = 0.15 * (1.0 + 2.0 * (v_adap - 0.5))
    dv = (i_in / circ.c_mem) * 1e-9 * dt
    decay = jnp.exp(-leak_rate * dt)
    p_static_base = circ.g_static

    def sub(i, carry):
        v, adap, ref, out, energy, t_spk = carry
        in_ref = ref > 0.0
        v_new = jnp.where(in_ref, 0.0, (v + dv) * decay)
        v_new = jnp.clip(v_new, 0.0, circ.vdd)
        eff_th = thresh + adap * 1.0
        fire = (v_new >= eff_th) & (~in_ref)
        v_new = jnp.where(fire, 0.0, v_new)
        ref_new = jnp.where(fire, tau_ref_ns, jnp.maximum(ref - dt, 0.0))
        adap_new = adap * jnp.exp(-dt / 8.0) + jnp.where(fire, adap_gain, 0.0)
        out_new = jnp.where(fire, circ.vdd, out)
        t_now = (i + 1).astype(jnp.float32) * dt
        t_spk = jnp.where(fire & (t_spk < 0), t_now, t_spk)
        p_static = p_static_base * jnp.square(v_leak + v_new * 0.3)
        e_sub = p_static * dt * 1e-9
        e_sub = e_sub + jnp.abs(i_in) * jnp.abs(v_new) * dt * 1e-9 * 0.5
        e_spk = jnp.where(fire, circ.c_spike * circ.vdd ** 2, 0.0)
        return (v_new, adap_new, ref_new, out_new, energy + e_sub + e_spk,
                t_spk)

    zeros = jnp.zeros_like(v0)
    init = (v0, adap0, ref0, zeros, zeros, -jnp.ones_like(v0))
    v_end, adap_end, ref_end, out, energy, t_spk = jax.lax.fori_loop(
        0, circ.n_substeps, sub, init)
    spiked = t_spk > 0
    latency = jnp.where(spiked, t_spk, circ.clock_ns)
    return (v_end, adap_end, ref_end), out, energy, latency, spiked


def _cols(a):
    """(bn, k) block -> tuple of its k f32 columns."""
    a = a.astype(jnp.float32)
    return tuple(a[:, c] for c in range(a.shape[1]))


def _make_kernel(circ: LIFNeuron):
    def kernel(state_ref, x_ref, p_ref, new_state_ref, out_ref, energy_ref,
               latency_ref, spiked_ref):
        new_state, out, energy, latency, spiked = _period_math(
            circ, _cols(state_ref[...]), _cols(x_ref[...]),
            _cols(p_ref[...]))
        new_state_ref[...] = jnp.stack(new_state, axis=-1)
        out_ref[...] = out
        energy_ref[...] = energy
        latency_ref[...] = latency
        spiked_ref[...] = spiked

    return kernel


# Ticks per time block of the time-looped kernels: per-tick sequences
# stream through VMEM in (T_BLOCK, block_n) tiles, so a chunk of any
# length fits the chip's scoped VMEM (a whole (T, block_n) sequence does
# not once T reaches the thousands).
T_BLOCK = 128


def time_blocks(t_steps: int):
    """``(tb, t_pad)``: the time-block length and the padded tick count.

    A chunk no longer than :data:`T_BLOCK` is one block of its own length
    (a block equal to the full dimension needs no 8-row alignment)."""
    tb = t_steps if t_steps <= T_BLOCK else T_BLOCK
    return tb, -(-t_steps // tb) * tb


def block_ticks(t_steps: int, tb: int, t_pad: int):
    """Ticks to run in the current time block: ``tb``, or fewer in the
    last block of a padded sequence (pad ticks are never integrated)."""
    if t_pad == t_steps:
        return tb
    return jnp.minimum(tb, t_steps - pl.program_id(1) * tb)


def _make_chunk_kernel(circ: LIFNeuron, t_steps: int, tb: int, t_pad: int):
    def kernel(state_ref, xseq_ref, p_ref, new_state_ref, out_ref,
               energy_ref, latency_ref, spiked_ref):
        # the state output block stays resident across the time axis of
        # the grid: it is the carry from one time block to the next
        @pl.when(pl.program_id(1) == 0)
        def _():
            new_state_ref[...] = state_ref[...].astype(jnp.float32)

        pp = tuple(p_ref[c].astype(jnp.float32)
                   for c in range(p_ref.shape[0]))

        def tick(t, st):
            row = pl.ds(t, 1)
            xx = tuple(xseq_ref[c, row, :][0].astype(jnp.float32)
                       for c in range(xseq_ref.shape[0]))
            new_state, out, energy, latency, spiked = _period_math(
                circ, st, xx, pp)
            out_ref[row, :] = out[None]
            energy_ref[row, :] = energy[None]
            latency_ref[row, :] = latency[None]
            spiked_ref[row, :] = spiked.astype(jnp.float32)[None]
            return new_state

        st = jax.lax.fori_loop(
            0, block_ticks(t_steps, tb, t_pad), tick,
            tuple(new_state_ref[c] for c in range(new_state_ref.shape[0])))
        for c, col in enumerate(st):
            new_state_ref[c] = col

    return kernel


def lif_chunk(state, x_seq, params, *, circ: LIFNeuron | None = None,
              block_n: int = 1024, interpret: bool = True):
    """T clock periods in ONE launch: the time-looped lif_scan variant.

    state (N, 3), x_seq (T, N, 3), params (N, 4). The grid walks circuit
    blocks and, inside each, time blocks of :func:`time_blocks`; state
    stays in VMEM across the whole chunk — the outer tick loop nests
    around the substep loop, so nothing round-trips HBM between periods.
    Inside the kernel every per-circuit quantity is lane-dense: state,
    knobs and stimulus are laid out column-major (``(3, N)``, ``(4, N)``,
    ``(3, T, N)``). Bit-for-bit identical to chaining ``lif_step`` T
    times (both loops call ``_period_math``).
    """
    circ = circ or LIFNeuron()
    t_steps, n = x_seq.shape[0], state.shape[0]
    assert n % block_n == 0, (n, block_n)
    tb, t_pad = time_blocks(t_steps)
    xs = jnp.pad(jnp.moveaxis(x_seq, 2, 0),
                 ((0, 0), (0, t_pad - t_steps), (0, 0)))
    kernel = _make_chunk_kernel(circ, t_steps, tb, t_pad)
    col_blk = lambda k: pl.BlockSpec((k, block_n),             # noqa: E731
                                     lambda i, j: (0, i))
    seq_blk = pl.BlockSpec((tb, block_n), lambda i, j: (j, i))
    seq = jax.ShapeDtypeStruct((t_pad, n), jnp.float32)
    new_state, out, energy, latency, spiked = pl.pallas_call(
        kernel,
        grid=(n // block_n, t_pad // tb),
        in_specs=[
            col_blk(state.shape[1]),
            pl.BlockSpec((xs.shape[0], tb, block_n), lambda i, j: (0, j, i)),
            col_blk(params.shape[1]),
        ],
        out_specs=[col_blk(state.shape[1]),
                   seq_blk, seq_blk, seq_blk, seq_blk],
        out_shape=[jax.ShapeDtypeStruct((state.shape[1], n), jnp.float32),
                   seq, seq, seq, seq],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(state.T, xs, params.T)
    obs = {"output": out[:t_steps], "energy": energy[:t_steps],
           "latency": latency[:t_steps], "spiked": spiked[:t_steps] > 0.5}
    return new_state.T, obs


def lif_step(state, x, params, *, circ: LIFNeuron | None = None,
             block_n: int = 1024, interpret: bool = True):
    """One clock period for N neurons. state (N,3), x (N,3), params (N,4)."""
    circ = circ or LIFNeuron()
    n = state.shape[0]
    assert n % block_n == 0, (n, block_n)
    kernel = _make_kernel(circ)
    new_state, out, energy, latency, spiked = pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, 3), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 3), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 4), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 3), lambda i: (i, 0)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 3), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.bool_),
        ],
        interpret=interpret,
    )(state, x, params)
    obs = {"output": out, "energy": energy, "latency": latency,
           "spiked": spiked}
    return new_state, obs
