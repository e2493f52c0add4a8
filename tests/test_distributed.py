"""Distributed equivalence (subprocess, forced host devices): the sharded
train step must match the single-device step, and the shard_map'd LASANA
step must match the local wrapper."""

import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.configs import reduced_config
    from repro.models.model import Model
    from repro.optim import AdamW, AdamWConfig
    from repro.sharding import train_rules
    from repro.train import step as step_mod
    from repro.configs.shapes import ShapeConfig

    cfg = reduced_config("granite-3-8b")
    opt = AdamW(AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10))
    key = jax.random.PRNGKey(0)
    batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab),
             "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab)}

    # single device
    m1 = Model(cfg)
    s1 = step_mod.init_train_state(m1, opt, key)
    step1 = jax.jit(step_mod.make_train_step(m1, opt))
    _, met1 = step1(s1, batch)

    def make_mesh(shape, names):
        # jax.sharding.AxisType only exists on newer jax; 0.4.x meshes are
        # implicitly Auto
        if hasattr(jax.sharding, "AxisType"):
            return jax.make_mesh(
                shape, names,
                axis_types=(jax.sharding.AxisType.Auto,) * len(shape))
        return jax.make_mesh(shape, names)

    # 4x2 mesh, explicit shardings
    mesh = make_mesh((4, 2), ("data", "model"))
    rules = train_rules(mesh)
    m2 = Model(cfg, mesh=mesh, rules=rules)
    shape = ShapeConfig("t", 32, 8, "train")
    with mesh:
        s2 = step_mod.init_train_state(m2, opt, key)
        jitted = step_mod.jit_train_step(m2, opt, mesh, rules, shape,
                                         n_moe_groups=4)
        _, met2 = jitted(s2, batch)
    l1, l2 = float(met1["loss"]), float(met2["loss"])
    print("LOSS1", l1, "LOSS2", l2)
    assert abs(l1 - l2) / abs(l1) < 2e-2, (l1, l2)

    # LASANA shard_map equivalence: the surrogate is a TRACED argument of
    # the sharded step (swap-without-recompile serving contract)
    import repro.lasana as lasana
    from repro.core.wrapper import init_state, lasana_step
    from repro.core.distributed import make_distributed_step
    from repro.core.circuits import LIFNeuron
    surrogate = lasana.train("lif", lasana.TrainConfig(
        n_runs=40, n_steps=40, families=("linear",)))
    circ = LIFNeuron()
    n = 64
    params = circ.sample_params(key, n)
    state = init_state(n, params)
    changed = jax.random.bernoulli(key, 0.8, (n,))
    x = circ.sample_inputs(key, (n,))
    sm_mesh = make_mesh((8,), ("data",))
    dstep = make_distributed_step(sm_mesh, clock_ns=5.0, spiking=True)
    with sm_mesh:
        st_d, e_tot, n_out = dstep(surrogate, state, changed, x,
                                   jnp.asarray([5.0]))
    st_l, e_l, _, o_l = lasana_step(surrogate, state, changed, x, 5.0, 5.0,
                                    spiking=True)
    np.testing.assert_allclose(np.asarray(st_d.v), np.asarray(st_l.v),
                               rtol=1e-5, atol=1e-6)
    assert abs(float(e_tot) - float(jnp.sum(e_l))) <= 1e-18 + 1e-5 * abs(float(e_tot))
    print("SHARDMAP-OK")
""")


@pytest.mark.slow
def test_sharded_equals_single_device(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env.pop("XLA_FLAGS", None)
    script = tmp_path / "dist_check.py"
    script.write_text(_SCRIPT)
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, env=env, cwd=_ROOT, timeout=900)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out[-3000:]
    assert "SHARDMAP-OK" in out


_XBAR_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core.circuits import augment_features, get_circuit
    from repro.core.network import (NetworkEngine, crossbar_layer,
                                    graph_spec, lif_layer, recurrent_edge)
    from repro.core.surrogate import (FORMAT_VERSION, Manifest, Surrogate,
                                      _feature_names)
    from repro.kernels import ops

    rng = np.random.default_rng(11)

    def surrogate(kind, families):
        circ = get_circuit(kind)
        f_raw = circ.n_inputs + 2 + circ.n_params
        f = int(augment_features(circ, np.zeros((1, f_raw))).shape[1])
        params = {}
        for p, fam in families.items():
            w = f + 2 if p in ("M_ED", "M_L") else f
            if fam == "linear":
                a = {"w": rng.normal(0, 0.3, w + 1), "mu": np.zeros(w),
                     "sd": np.ones(w)}
            else:
                dims = (w, 16, 8, 1)
                a = {f"w{i}": rng.normal(0, 1 / np.sqrt(dims[i]),
                                         (dims[i], dims[i + 1]))
                     for i in range(3)}
                a.update({f"b{i}": rng.normal(0, 0.1, dims[i + 1])
                          for i in range(3)})
                a.update(x_mu=np.zeros(w), x_sd=np.ones(w),
                         y_mu=np.asarray([0.1]), y_sd=np.asarray([0.8]))
            params[p] = {k: jnp.asarray(v, jnp.float32)
                         for k, v in a.items()}
        return Surrogate(Manifest(
            kind, FORMAT_VERSION, tuple(sorted(families.items())),
            tuple((p, 1.0) for p in sorted(families)),
            _feature_names(kind)), params)

    fams = {"M_O": "mlp", "M_V": "mlp", "M_ED": "mlp", "M_ES": "linear",
            "M_L": "linear"}
    lib = {"crossbar": surrogate("crossbar", fams),
           "lif": surrogate("lif", fams)}
    xw = rng.integers(-1, 2, (40, 6)).astype(np.float32)
    lw = rng.normal(0, 1.0, (6, 5)).astype(np.float32)
    inhib = -0.6 * (1 - np.eye(5, dtype=np.float32))
    spec = graph_spec([crossbar_layer(xw),
                       lif_layer(lw, np.asarray([0.58, 0.5, 0.5, 0.5],
                                                np.float32))],
                      edges=[recurrent_edge(1, 1, inhib)])
    x = (rng.integers(-1, 2, (12, 8, 40)) * 0.8).astype(np.float32)
    x[3, 2:5] = 0.0
    mesh = Mesh(np.array(jax.devices()[:4]), ("batch",))
    base = NetworkEngine(spec, surrogates=lib).run(x)
    sharded = NetworkEngine(spec, surrogates=lib, mesh=mesh)
    with ops.dispatch_scope() as log:
        shard = sharded.run(x)
    assert log.count("predict_blocks") == 3, log
    stream = sharded.run_stream(x, chunk_ticks=5)
    for run in (shard, stream):
        np.testing.assert_array_equal(base.events, run.events)
        np.testing.assert_array_equal(base.outputs, run.outputs)
        for a, b in zip(base.layer_spikes, run.layer_spikes):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(base.energy, run.energy, rtol=1e-6)
        np.testing.assert_allclose(base.latency, run.latency, rtol=1e-6)
    assert base.events[:, 0].sum() > 0 and base.events[:, 1].sum() > 0
    print("XBAR-SHARDMAP-OK")
""")


def test_crossbar_network_sharded_equals_single_device(tmp_path):
    """A crossbar -> LIF graph (recurrent edge) over a 4-device batch
    mesh, monolithic and streamed, against one device: the crossbar
    layer's block tick runs shard-local."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env.pop("XLA_FLAGS", None)
    script = tmp_path / "xbar_shard_check.py"
    script.write_text(_XBAR_SCRIPT)
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, env=env, cwd=_ROOT, timeout=600)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out[-3000:]
    assert "XBAR-SHARDMAP-OK" in out
