"""The crossbar tick by column blocks: ``lasana_step`` on a ``RowBlocks``
and ``Surrogate.predict_blocks`` against the per-row paths.

A crossbar row (lane b, output o, segment s) reads lane b's input segment
s and output o's weight segment s, so the engine hands the tick its rows
as blocks at their own shapes and never writes the (N, F) feature
matrices. Each head builds its standardized rows from the blocks inside
its first dot, with the arithmetic ``predict`` writes on the concatenated
rows: against the per-row fused path (which stacks heads) records agree
within its contract (rtol 1e-5), which rows receive an event and the
event counts are exact, and MLP heads equal the per-call path bit for
bit. Surrogates with a ``table`` or ``gbdt`` head, annotation mode, the
megakernel and the LIF tick keep the per-row path, seen through the
dispatch record.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.network as network
from repro.core.circuits import CrossbarRow
from repro.core.network import (NetworkEngine, _row_segments,
                                crossbar_layer, crossbar_mlp_spec,
                                graph_spec, lif_layer)
from repro.core.surrogate import (FAMILY_PREDICT, FORMAT_VERSION,
                                  Manifest, Surrogate, _feature_names)
from repro.core.wrapper import (LasanaState, RowBlocks, lasana_step,
                                row_blocks_ok, stale_circuits)
from repro.kernels import ops

RTOL = 1e-5


def assert_close(got, want, err_msg=""):
    """The fused contract, rtol 1e-5, with an absolute floor at rtol of
    the array's largest magnitude: a value near zero is a sum whose
    terms cancel, and its reassociation error scales with the terms."""
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=RTOL,
                               atol=RTOL * float(np.max(np.abs(want))),
                               err_msg=err_msg)

XB = CrossbarRow()
K, N_P = XB.n_inputs, XB.n_params
F_ACT = K + 2 + N_P + 1              # x, v, tau, p, row current
F_TR = F_ACT + 2                     # + o_prev, o_new


def _head(rng, family, f):
    if family == "mean":
        return {"mu": np.float32(rng.normal())}
    if family == "linear":
        return {"w": (rng.normal(size=(f + 1,)) * 0.3).astype(np.float32),
                "mu": (rng.normal(size=(f,)) * 0.2).astype(np.float32),
                "sd": (0.5 + rng.random(f)).astype(np.float32)}
    if family == "table":
        return {"tx": rng.normal(size=(16, f)).astype(np.float32),
                "ty": rng.normal(size=(16,)).astype(np.float32),
                "mu": np.zeros((f,), np.float32),
                "sd": np.ones((f,), np.float32)}
    if family == "gbdt":
        return {"feat": rng.integers(0, f, (2, 3)).astype(np.int32),
                "thr": rng.normal(size=(2, 3)).astype(np.float32),
                "leaf": rng.normal(size=(2, 4)).astype(np.float32),
                "base": np.float32(0.0)}
    dims = (f, 24, 12, 1)
    a = {}
    for i in range(3):
        a[f"w{i}"] = (rng.normal(size=(dims[i], dims[i + 1]))
                      / np.sqrt(dims[i])).astype(np.float32)
        a[f"b{i}"] = (rng.normal(size=(dims[i + 1],)) * 0.1
                      ).astype(np.float32)
    a.update(x_mu=(rng.normal(size=(f,)) * 0.2).astype(np.float32),
             x_sd=(0.5 + rng.random(f)).astype(np.float32),
             y_mu=np.float32([0.2]), y_sd=np.float32([0.6]))
    return a


def xbar_surrogate(families: dict, seed: int = 0) -> Surrogate:
    """A crossbar Surrogate with random heads of the given families."""
    rng = np.random.default_rng(seed)
    params = {p: {k: jnp.asarray(v) for k, v in _head(
        rng, fam, F_TR if p in ("M_ED", "M_L") else F_ACT).items()}
        for p, fam in families.items()}
    return Surrogate(Manifest(
        circuit="crossbar", format_version=FORMAT_VERSION,
        families=tuple(sorted(families.items())),
        scales=tuple(sorted((p, 1e15 if p.startswith("M_E") else 1.0)
                            for p in families)),
        features=_feature_names("crossbar")), params)


def _all(fam):
    return {p: fam for p in ("M_O", "M_V", "M_ES", "M_ED", "M_L")}


HEAD_SETS = {
    "stacked-mlp": _all("mlp"),
    "single-mlp": {"M_O": "mlp", "M_V": "linear", "M_ES": "linear",
                   "M_ED": "mlp", "M_L": "linear"},
    "linear": _all("linear"),
    "mean": _all("mean"),
    "mixed": {"M_O": "mlp", "M_V": "mlp", "M_ES": "linear", "M_ED": "mlp",
              "M_L": "mean"},
}


def _layer(seed, b=3, fan_in=72, n_out=5):
    """One crossbar layer's rows (n_out, n_seg, K + 1) with live bias
    rows, and a state of its rows over ``b`` lanes with stale and fresh
    rows."""
    rng = np.random.default_rng(seed)
    w = rng.integers(-1, 2, (fan_in, n_out)).astype(np.float32)
    n_seg = -(-fan_in // K)
    rows = _row_segments(w, K).reshape(n_out, n_seg, K + 1)
    rows[..., K] = rng.integers(-1, 2, (n_out, n_seg))     # live bias rows
    pall = np.broadcast_to(rows.reshape(1, -1, K + 1),
                           (b, n_out * n_seg, K + 1)).reshape(-1, K + 1)
    n = len(pall)
    state = LasanaState(
        v=jnp.asarray(rng.uniform(-2, 2, n), jnp.float32),
        o=jnp.asarray(rng.uniform(-2, 2, n), jnp.float32),
        t_last=jnp.asarray(rng.choice([0.0, 8.0, 16.0], n), jnp.float32),
        params=jnp.asarray(pall))
    return rows, state


def _stimulus(kind, seed, ticks=4, b=3, fan_in=72):
    """(ticks, b, n_seg * K) input volts: every segment of every lane live
    on every tick ("every_tick"), or dead segments and lanes on chosen
    ticks ("sparse"): lane 0's second segment and lane 1 on tick 0, lane 2
    on tick 2."""
    rng = np.random.default_rng(seed)
    n_seg = -(-fan_in // K)
    x = rng.uniform(-0.8, 0.8, (ticks, b, n_seg * K)).astype(np.float32)
    x[..., fan_in:] = 0.0
    if kind == "sparse":
        x[0, 0, K:2 * K] = 0.0
        x[0, 1] = 0.0
        x[2, 2] = 0.0
    return x


def _tick(rows, x):
    """One tick's inputs ``x (b, n_seg * K)`` to the rows: the event mask,
    the inputs broadcast per row, and the rows as blocks."""
    n_out, n_seg = rows.shape[:2]
    x_seg = x.reshape(len(x), n_seg, K)
    xin = np.broadcast_to(x_seg[:, None], (len(x), n_out, n_seg, K)
                          ).reshape(-1, K)
    changed = jnp.asarray(np.any(np.abs(xin) > 1e-6, axis=-1))
    blocks = RowBlocks(x=jnp.asarray(x_seg[:, None]),
                       params=jnp.asarray(rows[None]))
    return changed, jnp.asarray(xin), blocks


# ticks on which a row is stale, from a state with stale rows: the block
# step runs its idle stage on these only
IDLE_TICKS = {"every_tick": [True, False, False, False],
              "sparse": [True, True, False, True]}


@pytest.mark.parametrize("stimulus", list(IDLE_TICKS))
@pytest.mark.parametrize("heads", list(HEAD_SETS), ids=list(HEAD_SETS))
def test_block_step_matches_row_step(heads, stimulus):
    """Ticks on one layer: the block step reproduces ``lasana_step`` on
    the broadcast rows (fused path) within the fused contract, dead
    segments included, on ticks that skip the idle catch-up (no row
    stale) and on ticks that run it."""
    sur = xbar_surrogate(HEAD_SETS[heads], seed=1)
    assert row_blocks_ok(sur)
    rows, state = _layer(2)
    step = jax.jit(lambda s, st, c, x, t: lasana_step(
        s, st, c, x, t, XB.clock_ns, fused=True, fused_kernel=False))
    ran = []
    for k, x in enumerate(_stimulus(stimulus, 3)):
        t = 24.0 + XB.clock_ns * k
        changed, xin, blocks = _tick(rows, x)
        ran.append(bool(jnp.any(stale_circuits(state, changed, t,
                                               XB.clock_ns))))
        ref = step(sur, state, changed, xin, t)
        got = step(sur, state, changed, blocks, t)
        for name, r, g in (("e", ref[1], got[1]), ("l", ref[2], got[2]),
                           ("o", ref[3], got[3]), ("v", ref[0].v, got[0].v),
                           ("o_state", ref[0].o, got[0].o)):
            assert_close(g, r, err_msg=f"{name}, tick {k}")
        np.testing.assert_array_equal(np.asarray(got[0].t_last),
                                      np.asarray(ref[0].t_last))
        # rows without an event are untouched and charge nothing
        idle = ~np.asarray(changed)
        np.testing.assert_array_equal(np.asarray(got[1])[idle], 0.0)
        np.testing.assert_array_equal(np.asarray(got[0].v)[idle],
                                      np.asarray(state.v)[idle])
        state = ref[0]
    assert ran == IDLE_TICKS[stimulus]


def test_block_idle_skip_is_exact():
    """A block tick on which no row is stale skips the idle heads; the
    same tick with a fourth lane of stale rows runs them. The first three
    lanes' records and state are bit-identical between the two. The
    fourth lane is there in both, fresh in the first, so that both runs
    compile the same shapes (XLA:CPU's dots round apart at another
    row count)."""
    sur = xbar_surrogate(HEAD_SETS["stacked-mlp"], seed=1)
    rows, state = _layer(2, b=4)
    changed, _, blocks = _tick(rows, _stimulus("every_tick", 3, ticks=1,
                                               b=4)[0])
    n = 3 * rows.shape[0] * rows.shape[1]            # rows of lanes 0-2
    fresh = state._replace(t_last=jnp.full_like(state.t_last,
                                                24.0 - XB.clock_ns))
    stale = fresh._replace(t_last=fresh.t_last.at[n:].set(0.0))
    assert not bool(jnp.any(stale_circuits(fresh, changed, 24.0,
                                           XB.clock_ns)))
    assert bool(jnp.all(stale_circuits(stale, changed, 24.0,
                                       XB.clock_ns)[n:]))
    step = jax.jit(lambda st: lasana_step(sur, st, changed, blocks, 24.0,
                                          XB.clock_ns))
    for name, a, b in zip(("v", "o", "t_last", "params", "e", "l", "o_out"),
                          jax.tree.leaves(step(fresh)),
                          jax.tree.leaves(step(stale))):
        np.testing.assert_array_equal(np.asarray(a)[:n], np.asarray(b)[:n],
                                      err_msg=name)


def test_predict_blocks_matches_predict_on_rows():
    """Every head on blocks equals its family's prediction on the rows the
    blocks broadcast to, concatenated (the derived column included)."""
    sur = xbar_surrogate(HEAD_SETS["mixed"], seed=3)
    rng = np.random.default_rng(4)
    grid = (2, 4, 3)
    x = rng.normal(size=(2, 1, 3, K)).astype(np.float32)
    p = rng.normal(size=(1, 4, 3, N_P)).astype(np.float32)
    per_row = {c: rng.normal(size=grid + (1,)).astype(np.float32)
               for c in ("v", "tau", "o_prev", "o_new", "derived")}
    blocks = dict(per_row, x=x, p=p)
    for heads, extra in ((("M_O", "M_V", "M_ES"), ()),
                         (("M_ED", "M_L"), ("o_prev", "o_new"))):
        out = sur.predict_blocks(heads, blocks, extra=extra)
        cols = ([np.broadcast_to(x, grid + (K,)), per_row["v"],
                 per_row["tau"], np.broadcast_to(p, grid + (N_P,))]
                + [per_row[c] for c in extra] + [per_row["derived"]])
        feats = jnp.asarray(np.concatenate(cols, -1).reshape(
            -1, F_TR if extra else F_ACT))
        for name in heads:
            fam = sur.manifest.family_of(name)
            want = (FAMILY_PREDICT[fam](sur.params[name], feats)
                    / sur.manifest.scale_of(name))
            assert out[name].shape == grid
            assert_close(np.asarray(out[name]).reshape(-1), want,
                         err_msg=name)


def test_column_blocks_split():
    """The manifest's columns in blocks, per head; none for the families
    whose first layer reads whole rows."""
    sur = xbar_surrogate({"M_O": "mlp", "M_V": "linear", "M_ES": "mean",
                          "M_ED": "table", "M_L": "gbdt"})
    base = (("x", K), ("v", 1), ("tau", 1), ("p", N_P))
    assert sur.column_blocks("M_O") == base + (("derived", 1),)
    assert sur.column_blocks("M_V") == base + (("derived", 1),)
    assert sur.column_blocks("M_ES") == ()
    assert sur.column_blocks("M_ED", ("o_prev", "o_new")) is None
    assert sur.column_blocks("M_L", ("o_prev", "o_new")) is None
    assert not row_blocks_ok(sur)


# --- the engine ---------------------------------------------------------------

def _net(seed=5):
    rng = np.random.default_rng(seed)
    ws = [rng.integers(-1, 2, (40, 8)).astype(np.float32),
          rng.integers(-1, 2, (8, 4)).astype(np.float32)]
    x = rng.uniform(-0.8, 0.8, (7, 3, 40)).astype(np.float32)
    x[2] = 0.0                      # a silent tick: every row idles
    x[4, :, :20] = 0.0              # a dead first segment
    x[5, 1] = 0.0                   # a silent lane
    return crossbar_mlp_spec(ws), x


def _run(spec, x, sur, **kw):
    with ops.dispatch_scope() as log:
        run = NetworkEngine(spec, surrogates=sur, **kw).run(x)
    return run, set(log)


@pytest.mark.parametrize("heads", ["stacked-mlp", "mixed", "linear"])
def test_engine_blocks_match_rows(heads, monkeypatch):
    """A crossbar network by blocks against the same network with the
    per-row tick: events exact, the rest within the fused contract."""
    spec, x = _net()
    sur = xbar_surrogate(HEAD_SETS[heads], seed=6)
    blk, log_b = _run(spec, x, sur)
    monkeypatch.setattr(network, "row_blocks_ok", lambda *a: False)
    row, log_r = _run(spec, x, sur)
    assert log_b == {"predict_blocks"} and log_r == {"predict_heads"}
    np.testing.assert_array_equal(blk.events, row.events)
    assert blk.events[2, 0] == 0 and blk.events.sum() > 0   # layer 0 idles
    assert_close(blk.energy, row.energy)
    assert_close(blk.latency, row.latency)
    step = 4.0 / 255 / (XB.r_f * XB.g_unit)       # one ADC step in codes
    for a, b in zip(blk.layer_spikes, row.layer_spikes):
        assert np.max(np.abs(a - b)) < 0.5 * step


def test_engine_blocks_equal_per_call_mlp_heads():
    """With MLP heads the block rows compile to the dots the per-call
    path runs on the concatenated rows: every record is bit-identical."""
    spec, x = _net()
    sur = xbar_surrogate(HEAD_SETS["stacked-mlp"], seed=9)
    blk, log_b = _run(spec, x, sur)
    call, log_c = _run(spec, x, sur, fused=False)
    assert log_b == {"predict_blocks"} and log_c == {"predict"}
    for f in ("outputs", "energy", "latency", "events"):
        np.testing.assert_array_equal(getattr(blk, f), getattr(call, f),
                                      err_msg=f)
    for a, b in zip(blk.layer_spikes, call.layer_spikes):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("fam", ["table", "gbdt"])
def test_whole_row_families_keep_the_row_path(fam):
    """A surrogate carrying a ``table`` or ``gbdt`` head runs the per-row
    fused path: the dispatch record shows no ``predict_blocks``."""
    spec, x = _net()
    sur = xbar_surrogate(dict(HEAD_SETS["mixed"], M_ES=fam), seed=7)
    _, log = _run(spec, x, sur)
    assert log == {"predict_heads"}


@pytest.mark.parametrize("kw,want", [
    ({"mode": "annotation"}, {"predict_heads"}),
    ({"fused": False}, {"predict"}),
    ({"fused_kernel": True}, {"megakernel_step"}),
], ids=["annotation", "per-call", "megakernel"])
def test_other_paths_keep_the_row_step(kw, want):
    spec, x = _net()
    _, log = _run(spec, x, xbar_surrogate(HEAD_SETS["stacked-mlp"]), **kw)
    assert log == want


def test_lif_tick_records_no_block_dispatch(lif_bank):
    """A crossbar -> LIF graph: the crossbar layer by blocks, the LIF layer
    on the per-row fused path."""
    rng = np.random.default_rng(8)
    spec = graph_spec([crossbar_layer(rng.integers(-1, 2, (40, 6))
                                      .astype(np.float32)),
                       lif_layer(rng.normal(0, 1.0, (6, 4))
                                 .astype(np.float32),
                                 np.asarray([0.58, 0.5, 0.5, 0.5],
                                            np.float32))])
    x = rng.uniform(-0.8, 0.8, (5, 2, 40)).astype(np.float32)
    lib = {"crossbar": xbar_surrogate(HEAD_SETS["mixed"]), "lif": lif_bank}
    with ops.dispatch_scope() as log:
        NetworkEngine(spec, surrogates=lib).run(x)
    # three stages a tick each: blocks for the crossbar, heads for the LIF
    assert sorted(log).count("predict_blocks") == 3
    assert sorted(log).count("predict_heads") == 3
    with ops.dispatch_scope() as log:
        NetworkEngine(graph_spec([spec.layers[1]]),
                      surrogates=lif_bank).run(x[:, :, :6] * 1.875)
    assert "predict_blocks" not in log
