"""Multi-pod distributed LASANA simulation.

Circuits are embarrassingly parallel: Algorithm 1 has no cross-circuit
communication, so the (N, ...) state/stimulus arrays shard over EVERY mesh
axis flattened (pod x data x model = 512 ways). ``shard_map`` makes the
locality explicit — the per-shard body is exactly ``lasana_step`` on N/512
circuits — and diagnostics (total energy, spike counts) are the only psums.

The trained :class:`Surrogate` enters the sharded program as a *traced
pytree argument* with replicated (``P()``) specs: one compiled step serves
every retrained surrogate whose manifest and array shapes match, and the
predictor weights participate in the mesh like any other arrays.

This module also provides the LASANA dry-run used in EXPERIMENTS §Dry-run:
lowering one simulation tick for 2^20..2^27 circuits on the production mesh.
"""

from __future__ import annotations

import functools
import warnings
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.surrogate import Surrogate, as_surrogate
from repro.core.wrapper import LasanaState, lasana_step


def circuit_spec(mesh: Mesh) -> P:
    return P(tuple(mesh.axis_names))      # shard circuits over all axes


def batch_spec(mesh: Mesh, ndim: int = 1, axis: int = 0) -> P:
    """PartitionSpec sharding dim ``axis`` of an ndim array over ALL mesh
    axes flattened (the network engine's batch-parallel layout: batch-major
    flattened circuit arrays shard contiguously)."""
    spec: list = [None] * ndim
    spec[axis] = tuple(mesh.axis_names)
    return P(*spec)


def shard_over_batch(fn, mesh: Mesh, in_specs, out_specs,
                     donate_argnums=()):
    """jit(shard_map(fn)) — the network engine's batch-parallel wrapper.

    ``fn`` must be batch-local except for explicit psum/pmax collectives
    (Algorithm 1 has zero cross-circuit communication, so a whole network
    tick is batch-local; only diagnostics reduce). Pytree arguments whose
    in_spec leaves are ``P()`` — e.g. a :class:`Surrogate` — replicate
    across the mesh while remaining traced (swap-without-recompile).

    ``donate_argnums`` is forwarded to ``jax.jit``: the network engine's
    streaming path donates its chunk-to-chunk carries (and the surrogate
    leaves) so XLA aliases them in place instead of copying per chunk.

    ``check_vma=False``: the varying-manual-axes checker has no rule for
    ``pallas_call``, so it rejects any body that launches a kernel (the
    tick megakernel compiled on an accelerator, or forced on the CPU with
    ``REPRO_TICK_PALLAS=1``); disabling the check changes no numerics —
    the per-shard body and its collectives run identically."""
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False),
                   donate_argnums=donate_argnums)


def _sharded_step(mesh: Mesh, surrogate_template, *, clock_ns: float,
                  spiking: bool = False, vdd: float = 1.5,
                  fused: bool = True, fused_kernel: bool = False):
    """jit(shard_map) of one Algorithm-1 tick; surrogate is argument 0.

    ``surrogate_template`` supplies only the pytree *structure* for the
    replicated in_specs. ``fused_kernel`` is the RESOLVED fused-kernel
    switch — the per-shard body is exactly ``lasana_step``, so the
    megakernel runs shard-local on N/devices circuits with the head pack
    replicated like every other surrogate leaf."""
    cspec = circuit_spec(mesh)
    state_spec = LasanaState(v=cspec, o=cspec, t_last=cspec, params=cspec)
    sur_spec = jax.tree.map(lambda _: P(), surrogate_template)

    def body(surrogate, state, changed, x, t):
        new_state, e, l, o = lasana_step(surrogate, state, changed, x, t[0],
                                         clock_ns, spiking=spiking, vdd=vdd,
                                         fused=fused,
                                         fused_kernel=fused_kernel)
        e_tot = jax.lax.psum(jnp.sum(e), tuple(mesh.axis_names))
        # spike counts are integers: fp32 accumulation silently loses
        # whole events past 2^24 per tick at dry-run scales (2^27 circuits)
        n_out = jax.lax.psum(jnp.sum(o > 0.5 * vdd, dtype=jnp.int32),
                             tuple(mesh.axis_names))
        return new_state, e_tot, n_out

    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(sur_spec, state_spec, cspec, cspec, P()),
                       out_specs=(state_spec, P(), P()),
                       check_vma=False)  # pallas_call has no vma rule
    return jax.jit(sm)


def make_distributed_step(mesh, _legacy_mesh=None, *, clock_ns: float,
                          spiking: bool = False, vdd: float = 1.5,
                          fused: bool = True,
                          fused_kernel: bool | None = None):
    """(surrogate, state, changed, x, t) -> (state, e_total, spikes_total).

    Returns a callable that shard_maps one tick over ``mesh``. The
    surrogate rides along as a traced, replicated pytree: calls with
    retrained surrogates of identical structure reuse one compiled program
    (the program cache is keyed on the surrogate's treedef).
    ``spikes_total`` is an exact int32 count; ``vdd`` is the spiking
    circuit's supply voltage (spike resolution + discriminator level);
    ``fused`` selects the fused ``predict_heads`` tick body (default) vs
    the per-``predict``-call baseline; ``fused_kernel`` is the tri-state
    fused-kernel override (None defers to ``REPRO_FUSED_KERNEL``,
    re-resolved per call so env flips recompile cleanly).

    Legacy call style ``make_distributed_step(bank, mesh, ...)`` (surrogate
    closed over, returned callable takes ``(state, changed, x, t)``) is
    still accepted, with a DeprecationWarning.
    """
    if _legacy_mesh is None and not isinstance(mesh, Mesh):
        raise TypeError(
            "make_distributed_step expects a jax.sharding.Mesh as its "
            f"first argument, got {type(mesh).__name__}; the surrogate is "
            "passed to the returned step, not here")
    if _legacy_mesh is not None:
        if not isinstance(_legacy_mesh, Mesh):
            raise TypeError("legacy make_distributed_step(bank, mesh, ...) "
                            "call: second argument must be a "
                            f"jax.sharding.Mesh, got "
                            f"{type(_legacy_mesh).__name__}")
        warnings.warn(
            "make_distributed_step(bank, mesh, ...) is deprecated; call "
            "make_distributed_step(mesh, ...) and pass the Surrogate as "
            "the step's first argument", DeprecationWarning, stacklevel=2)
        surrogate = as_surrogate(mesh)
        from repro.kernels import ops
        fn = _sharded_step(_legacy_mesh, surrogate, clock_ns=clock_ns,
                           spiking=spiking, vdd=vdd, fused=fused,
                           fused_kernel=ops.fused_kernel_enabled(
                               fused_kernel))
        return lambda state, changed, x, t: fn(surrogate, state, changed,
                                               x, t)

    cache: dict = {}

    def step(surrogate, state, changed, x, t):
        from repro.kernels import ops
        surrogate = as_surrogate(surrogate)
        # the fused-kernel switch and the megakernel launcher each select
        # a different traced body, so they join the treedef in the
        # program cache key — flipping either mid-process recompiles
        # cleanly instead of silently reusing the old program
        fk = ops.fused_kernel_enabled(fused_kernel)
        key = (jax.tree.structure(surrogate), fk,
               ops.tick_pallas_enabled())
        fn = cache.get(key)
        if fn is None:
            fn = _sharded_step(mesh, surrogate, clock_ns=clock_ns,
                               spiking=spiking, vdd=vdd, fused=fused,
                               fused_kernel=fk)
            cache[key] = fn
        return fn(surrogate, state, changed, x, t)

    return step


def abstract_sim_inputs(n_circuits: int, n_in: int, n_params: int):
    f32 = jnp.float32
    state = LasanaState(
        v=jax.ShapeDtypeStruct((n_circuits,), f32),
        o=jax.ShapeDtypeStruct((n_circuits,), f32),
        t_last=jax.ShapeDtypeStruct((n_circuits,), f32),
        params=jax.ShapeDtypeStruct((n_circuits, n_params), f32),
    )
    changed = jax.ShapeDtypeStruct((n_circuits,), jnp.bool_)
    x = jax.ShapeDtypeStruct((n_circuits, n_in), f32)
    t = jax.ShapeDtypeStruct((1,), f32)
    return state, changed, x, t


def lower_distributed_step(surrogate, mesh: Mesh, n_circuits: int, n_in: int,
                           n_params: int, *, clock_ns: float,
                           spiking: bool = False, vdd: float = 1.5,
                           fused: bool = True,
                           fused_kernel: bool | None = None):
    """Lower one sharded simulation tick from ShapeDtypeStructs (dry-run).

    ``surrogate`` may be a Surrogate or a legacy PredictorBank; its arrays
    stay concrete (they are the weights), the simulation inputs are
    abstract."""
    from repro.kernels import ops
    surrogate = as_surrogate(surrogate)
    step = _sharded_step(mesh, surrogate, clock_ns=clock_ns, spiking=spiking,
                         vdd=vdd, fused=fused,
                         fused_kernel=ops.fused_kernel_enabled(fused_kernel))
    args = abstract_sim_inputs(n_circuits, n_in, n_params)
    with mesh:
        return step.lower(surrogate, *args)
