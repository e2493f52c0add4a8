"""Fused 3-layer MLP surrogate inference kernel (LASANA's inference hot spot).

One ``pallas_call`` evaluates an entire predictor over a block of circuits:
the (F,H1),(H1,H2),(H2,1) weight matrices live in VMEM for the whole grid
(they are a few hundred KB), activations never round-trip HBM, and both
ReLU layers fuse into the matmul epilogues. Block sizes are MXU-aligned
(inputs padded to multiples of 128 by ops.py).

This replaces the paper's five scikit-learn ``predict`` calls + Python
batching: on TPU, one kernel launch per predictor per tick, grid over
N/block circuits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.surrogate import F32_DOT


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=F32_DOT)


def _mlp_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, w3_ref, b3_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    h1 = jnp.maximum(_dot(x, w1_ref[...]) + b1_ref[...], 0.0)
    h2 = jnp.maximum(_dot(h1, w2_ref[...]) + b2_ref[...], 0.0)
    o_ref[...] = _dot(h2, w3_ref[...]) + b3_ref[...]


def mlp_surrogate(x, w1, b1, w2, b2, w3, b3, *, block_n: int = 256,
                  interpret: bool = True):
    """x: (N, F) -> (N, 1). All dims should be 128-aligned on real TPUs."""
    n, f = x.shape
    h1 = w1.shape[1]
    h2 = w2.shape[1]
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    return pl.pallas_call(
        _mlp_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, f), lambda i: (i, 0)),
            pl.BlockSpec((f, h1), lambda i: (0, 0)),
            pl.BlockSpec((h1,), lambda i: (0,)),
            pl.BlockSpec((h1, h2), lambda i: (0, 0)),
            pl.BlockSpec((h2,), lambda i: (0,)),
            pl.BlockSpec((h2, 1), lambda i: (0, 0)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
    )(x, w1, b1, w2, b2, w3, b3)


# --- multi-head variant (the fused inference hot path) --------------------------

def _mlp_heads_kernel(x_ref, xmu_ref, xsd_ref, ymu_ref, ysd_ref,
                      w1_ref, b1_ref, w2_ref, b2_ref, w3_ref, b3_ref,
                      o_ref):
    """All P heads evaluated on one (block_n, F) input block.

    Head count P is static, so the head loop unrolls at trace time; every
    head's weights (and per-head input/output standardizers) sit in VMEM
    for the whole grid — one feature-block load serves all P predictors,
    and both ReLU layers fuse into the matmul epilogues exactly as in the
    single-head kernel."""
    x = x_ref[...].astype(jnp.float32)
    p = w1_ref.shape[0]
    for i in range(p):
        xs = (x - xmu_ref[i]) / xsd_ref[i]
        h1 = jnp.maximum(_dot(xs, w1_ref[i]) + b1_ref[i], 0.0)
        h2 = jnp.maximum(_dot(h1, w2_ref[i]) + b2_ref[i], 0.0)
        out = _dot(h2, w3_ref[i]) + b3_ref[i]
        o_ref[i] = out * ysd_ref[i] + ymu_ref[i]


def mlp_surrogate_heads(x, x_mu, x_sd, y_mu, y_sd, w1, b1, w2, b2, w3, b3,
                        *, block_n: int = 256, interpret: bool = True):
    """x: (N, F) + P stacked heads -> (P, N, 1) in physical target units.

    One ``pallas_call`` evaluates every predictor head over every circuit
    block: weights are (P, ...) stacks whose BlockSpecs load the FULL
    stack (index map pinned to 0) so they stay VMEM-resident across the
    grid, which iterates over N-blocks only. Per-head feature
    standardization ((x - x_mu) / x_sd) and target de-standardization
    (y * y_sd + y_mu) happen inside the kernel, so callers hand over raw
    augmented features once for all heads.

    ``n % block_n == 0`` is required here (the raw kernel is
    shape-strict); ``ops.mlp_surrogate_heads`` pads ragged N (and the
    F/H1/H2 dims to 128) before calling in.
    """
    n, f = x.shape
    p, _, h1 = w1.shape
    h2 = w2.shape[2]
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    resident = lambda *shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    return pl.pallas_call(
        _mlp_heads_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, f), lambda i: (i, 0)),
            resident(p, f),             # x_mu
            resident(p, f),             # x_sd
            resident(p, 1),             # y_mu
            resident(p, 1),             # y_sd
            resident(p, f, h1),         # w1
            resident(p, h1),            # b1
            resident(p, h1, h2),        # w2
            resident(p, h2),            # b2
            resident(p, h2, 1),         # w3
            resident(p, 1),             # b3
        ],
        out_specs=pl.BlockSpec((p, block_n, 1), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((p, n, 1), jnp.float32),
        interpret=interpret,
    )(x, x_mu, x_sd, y_mu, y_sd, w1, b1, w2, b2, w3, b3)
