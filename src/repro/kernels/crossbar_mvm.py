"""Analog crossbar MVM kernel: differential-pair conductances + TIA
saturation, fused (the golden model's per-step target computation and the
MVM macro of the exploration feature).

Grid over circuit blocks; each block computes
    v_tgt = V_sat * tanh(-R_f * G_unit * (W v + b V_bias) / V_sat)
    tau   = tau0 * (1 + 0.5 * mean|W|)
with the (block, 32) x (block, 33) operands VMEM-resident. Rows are
independent (each circuit has its own weights) so the product is an
elementwise-multiply + row reduction — VPU work, MXU-free, which is the
right mapping for per-row distinct weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _make_kernel(n_inputs, g_unit, r_f, v_sat, v_bias, tau_base):
    def kernel(v_ref, w_ref, tgt_ref, tau_ref):
        v = v_ref[...].astype(jnp.float32)            # (bn, n_in)
        wfull = w_ref[...].astype(jnp.float32)        # (bn, n_in + 1)
        w = wfull[:, :n_inputs]
        bias = wfull[:, n_inputs]
        i_sig = g_unit * (jnp.sum(w * v, axis=-1) + bias * v_bias)
        v_lin = -r_f * i_sig
        tgt_ref[...] = v_sat * jnp.tanh(v_lin / v_sat)
        load = jnp.mean(jnp.abs(w), axis=-1)
        tau_ref[...] = tau_base * (1.0 + 0.5 * load)
    return kernel


def crossbar_target(v, w, *, g_unit=12e-6, r_f=40e3, v_sat=2.0, v_bias=0.8,
                    tau_base=0.15, block_n: int = 1024, interpret: bool = True):
    """v: (N, n_in), w: (N, n_in+1) -> (v_tgt (N,), tau (N,))."""
    n, n_in = v.shape
    assert n % block_n == 0, (n, block_n)
    kernel = _make_kernel(n_in, g_unit, r_f, v_sat, v_bias, tau_base)
    return pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, n_in), lambda i: (i, 0)),
            pl.BlockSpec((block_n, n_in + 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
        ],
        interpret=interpret,
    )(v, w)
