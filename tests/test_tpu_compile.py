"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Each test lowers one kernel with ``interpret=False`` for one chip of a
described (not attached) ``v5e:2x2`` topology, at the widths
``chip_smoke.py`` gives it, and asserts that the compiled HLO holds the
Mosaic kernel (``tpu_custom_call``). What the chip's compiler refuses —
block shapes that do not match XLA's tiling, more VMEM than a kernel may
use — fails here, with no chip. Nothing runs, so these tests say nothing
about results (the interpret-mode parity tests in test_megakernel.py and
test_kernels.py do).

The head pack is built as shapes from a :class:`PackLayout` holding every
head family the megakernel packs (mean, linear and the production
MLP(100, 50)), so each family's branch of the kernel body is compiled; no
surrogate is trained. The topology is
described inside a fixture: describing it loads the TPU compiler's
library, which only one process may hold, so it must happen in the one
worker that runs this file and never at import.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# chip_smoke.py: batch 256 through the 784-256-128-10 net, 100 ticks
BATCH = 256
LAYER_WIDTHS = (256, 128, 10)
N_WIDE = BATCH * LAYER_WIDTHS[0]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache altogether
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shape(one_chip):
    return lambda dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        tuple(dims), dtype, sharding=one_chip)


@pytest.fixture(scope="module")
def pack(shape):
    """(pack of ShapeDtypeStructs, PackLayout): a LIF pack with a head of
    every packable family (a trained surrogate mixes them per head)."""
    from repro.core.circuits import augment_features, get_circuit
    from repro.core.models import MLPModel
    from repro.kernels import tick_megakernel as mk
    circ = get_circuit("lif")
    f_raw = circ.n_inputs + 2 + circ.n_params
    f_a = int(augment_features(circ, jnp.zeros((1, f_raw))).shape[1])
    f_t = int(augment_features(circ, jnp.zeros((1, f_raw + 2))).shape[1])
    h1, h2 = MLPModel().hidden
    layout = mk.PackLayout(a_fams=("mean", "linear", "mlp"),
                           t_fams=("linear", "mlp"))

    def stack(p, f):
        dims = {"x_mu": (p, f), "x_sd": (p, f), "y_mu": (p, 1),
                "y_sd": (p, 1), "w0": (p, f, h1), "b0": (p, h1),
                "w1": (p, h1, h2), "b1": (p, h2), "w2": (p, h2, 1),
                "b2": (p, 1), "scale": (p, 1)}
        return {k: shape(dims[k]) for k in mk._STACK_KEYS}

    return {"a": stack(len(layout.a_fams), f_a),
            "t": stack(len(layout.t_fams), f_t)}, layout


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("width", LAYER_WIDTHS)
def test_network_tick_compiles_for_v5e(pack, shape, width):
    """The per-tick megakernel at each layer width of the smoke's net
    (``BATCH * width`` circuits; 2560 is ragged to the 1024 block)."""
    from repro.kernels import tick_megakernel as mk
    p, layout = pack
    n = BATCH * width

    def tick(p, v, o, tl, params, changed, x, t, known):
        return mk.network_tick(
            p, v, o, tl, params, changed, x, t, known, circuit="lif",
            clock_ns=5.0, layout=layout, spiking=True, vdd=1.5,
            interpret=False)

    _assert_kernel(tick, p, shape((n,)), shape((n,)), shape((n,)),
                   shape((n, 4)), shape((n,), jnp.bool_), shape((n, 3)),
                   shape(()), shape((n,)))


@pytest.mark.parametrize("t_steps", [25, 100, 300])
def test_network_tick_chunk_compiles_for_v5e(pack, shape, t_steps):
    """The time-looped chunk kernel at the smoke's stream chunk (25), its
    whole monolithic run (100), and a run long enough to take several
    VMEM time blocks (300)."""
    from repro.kernels import tick_megakernel as mk
    p, layout = pack
    n = N_WIDE

    def chunk(p, v, o, tl, params, changed, x, t):
        return mk.network_tick_chunk(
            p, v, o, tl, params, changed, x, t, circuit="lif",
            clock_ns=5.0, layout=layout, spiking=True, vdd=1.5,
            interpret=False)

    _assert_kernel(chunk, p, shape((n,)), shape((n,)), shape((n,)),
                   shape((n, 4)), shape((t_steps, n), jnp.bool_),
                   shape((t_steps, n, 3)), shape((t_steps,)))


@pytest.mark.parametrize("stack", ["a", "t"])
def test_mlp_surrogate_heads_compiles_for_v5e(pack, shape, stack):
    """The multi-head MLP kernel on both head stacks of the pack."""
    from repro.kernels import ops
    s = pack[0][stack]
    x = shape((N_WIDE, s["x_mu"].shape[1]))

    def heads(x, *w):
        return ops.mlp_surrogate_heads(x, *w, interpret=False)

    _assert_kernel(heads, x, s["x_mu"], s["x_sd"], s["y_mu"], s["y_sd"],
                   s["w0"], s["b0"], s["w1"], s["b1"], s["w2"], s["b2"])


@pytest.mark.parametrize("t_steps", [25, 300])
def test_lif_chunk_compiles_for_v5e(shape, t_steps):
    """The time-looped golden LIF kernel, one and several time blocks."""
    from repro.kernels import ops

    def chunk(state, x_seq, params):
        return ops.lif_chunk(state, x_seq, params, interpret=False)

    _assert_kernel(chunk, shape((N_WIDE, 3)), shape((t_steps, N_WIDE, 3)),
                   shape((N_WIDE, 4)))
