"""The control of each cell's comparison, at a size the CPU can hold (the
configurations' widths and surrogate heads, 16 lanes):
the plain reference computed at ``high`` (three bfloat16 passes, the step
below the configuration's float32 at HIGHEST) and put in the program's
place fails at least one limit, while the program itself passes all."""

import gc
import os
import time

import pytest

from lasbench import cells, check, harness, tiny, traffic

HARNESS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HARNESS))
SEED = 2 ** 33 + 5


@pytest.fixture(autouse=True)
def _thaw():
    """A run freezes its set-up heap out of the collector; let it go."""
    yield
    gc.unfreeze()
    gc.collect()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench")), ROOT,
                          widths=True)
    yield root
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


WORKLOADS = [w["name"] for w in cells.load_benchmark(ROOT)["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_fails_where_the_program_passes(root, workload):
    cell = cells.resolve(root, workload)
    cache = os.path.join(cell.harness_dir, ".cache")
    harness.use_cache(cache)
    ref_mod = cells.reference_module(cell.harness_dir, cell.config)
    net = harness.build_net(cell, SEED, cache, ref_mod)
    drv = traffic.driver(cell, net, SEED)
    drv.prepare()
    t0 = time.perf_counter()
    drv.window(0.5)
    assert time.perf_counter() - t0 < 120
    drv.close()
    pairs = drv.check_pairs()
    refs = check.run_reference(ref_mod, net.artifacts, net.layers, pairs)
    program = check.judge(check.compare(pairs, refs), cell.limits)
    assert check.passed(program), program
    crefs = check.run_reference(ref_mod, net.artifacts, net.layers, pairs,
                                precision="high")
    ctl = [(check.as_record(cr, run), x) for (run, x), cr in zip(pairs, crefs)]
    control = check.judge(check.compare(ctl, refs), cell.limits)
    assert not check.passed(control), control
