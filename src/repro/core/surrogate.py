"""The deployable LASANA artifact: an immutable pytree of predictor arrays.

A :class:`Surrogate` is what the facade (``repro.lasana``) trains, persists,
and serves. It replaces the mutable :class:`~repro.core.predictors.
PredictorBank` at inference time: the five selected predictors are frozen
into flat arrays (one dict per predictor) plus a *static* :class:`Manifest`
(circuit kind, feature schema, per-predictor model family, unit scales,
format version). Because the arrays are pytree leaves and the manifest is
pytree aux data, a surrogate passes straight through ``jax.jit`` /
``shard_map`` **as a traced argument**:

  * one compiled simulation program serves any retrained surrogate whose
    manifest and array shapes match — swapping banks is a weight swap, not
    a recompile (see tests/test_facade.py);
  * predictor weights shard/donate like any other pytree of arrays.

Pytree layout (what ``jax.tree.leaves`` sees)::

    Surrogate
    ├─ aux:    Manifest(circuit, format_version, families, scales, features)
    └─ leaves: params["M_O"]["w0"], params["M_O"]["b0"], ...   # per family
               params["M_V"][...], params["M_ED"][...], ...

Per-family array schemas (mirrors ``models.SurrogateModel`` inference):

    mean    mu ()                       constant
    linear  w (F+1,), mu (F,), sd (F,)  standardized affine
    table   tx (R,F), ty (R,), mu, sd   1-nearest-neighbor
    gbdt    feat (T,N), thr (T,N), leaf (T,L), base ()   complete trees
    mlp     w0,b0,...  x_mu,x_sd (F,), y_mu,y_sd (1,)    MLP(100, 50)

Persistence is one ``.npz`` per surrogate: arrays keyed ``{pname}/{key}``
plus a JSON ``__manifest__`` carrying :data:`FORMAT_VERSION`; loading a
file with a different version raises (no silent misinterpretation of
arrays). :class:`SurrogateLibrary` maps circuit kinds to surrogates for
heterogeneous graphs and is itself a pytree.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.circuits import get_circuit

FORMAT_VERSION = 1

# Surrogate inference contracts in f32. On a TPU an f32 matmul at the
# default precision rounds its operands to bf16, so the chip's records
# would drift from the CPU's and the inference paths (per-call, stacked,
# megakernel) from each other. Every inference dot asks for HIGHEST; on
# the CPU that changes nothing.
F32_DOT = jax.lax.Precision.HIGHEST


# --- static manifest ------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Manifest:
    """Static (hashable) description of a :class:`Surrogate`.

    This is the pytree *aux data*: two surrogates with equal manifests and
    equal leaf shapes share one compiled program. Fields:

    circuit         registered circuit kind the predictors were trained for
    format_version  on-disk format tag (see :data:`FORMAT_VERSION`)
    families        ((predictor, model family), ...) sorted by predictor
    scales          ((predictor, training-unit scale), ...); predictions are
                    divided by the scale back into physical units (energies
                    are trained in femtojoules for conditioning)
    features        names of the raw feature columns every predictor sees
                    ("x0..", "v", "tau", "p0.."); transition-aware heads
                    append o_prev/o_new, and the circuit's derived
                    ``surrogate_features`` columns are appended at predict
                    time (identically to fit time)
    """

    circuit: str
    format_version: int
    families: tuple
    scales: tuple
    features: tuple

    def family_of(self, pname: str) -> str:
        """Model family serving predictor ``pname``."""
        return dict(self.families)[pname]

    def scale_of(self, pname: str) -> float:
        """Training-unit scale of predictor ``pname`` (1.0 = physical)."""
        return dict(self.scales)[pname]

    @property
    def predictors(self) -> tuple:
        """Predictor names carried by this surrogate, sorted."""
        return tuple(p for p, _ in self.families)


def _npz_path(path: str) -> str:
    """Normalize a surrogate artifact path to its on-disk ``.npz`` name.

    ``np.savez_compressed`` silently appends ``.npz`` to extension-less
    paths, so ``save("foo")`` used to write ``foo.npz`` while
    ``load("foo")`` looked for (and failed on) ``foo``. Both directions
    now resolve to the same file whether or not the caller spells the
    extension."""
    return path if path.endswith(".npz") else path + ".npz"


def _feature_names(circuit_name: str) -> tuple:
    try:
        circ = get_circuit(circuit_name)
    except KeyError:
        return ()
    return (tuple(f"x{i}" for i in range(circ.n_inputs)) + ("v", "tau")
            + tuple(f"p{i}" for i in range(circ.n_params)))


# --- per-family inference (pure functions of (arrays, features)) ---------------

def _predict_mean(a, x):
    return jnp.broadcast_to(jnp.asarray(a["mu"], jnp.float32).reshape(()),
                            (x.shape[0],))


def _predict_linear(a, x):
    xs = (x - a["mu"]) / a["sd"]
    return jnp.matmul(xs, a["w"][:-1], precision=F32_DOT) + a["w"][-1]


def _predict_table(a, x):
    xs = (x - a["mu"]) / a["sd"]
    tx = a["tx"]
    d = jnp.sum(jnp.square(tx), -1)[None, :] \
        - 2.0 * jnp.matmul(xs, tx.T, precision=F32_DOT)
    return a["ty"][jnp.argmin(d, axis=1)]


def _predict_gbdt(a, x):
    feat, thr, leaf = a["feat"], a["thr"], a["leaf"]
    max_depth = int(np.log2(feat.shape[1] + 1))        # nodes = 2^d - 1
    n_t = feat.shape[0]
    tree_ix = jnp.arange(n_t)[None, :]
    node = jnp.zeros((x.shape[0], n_t), jnp.int32)
    for _ in range(max_depth):
        nf = feat[tree_ix, node]
        th = thr[tree_ix, node]
        xv = jnp.take_along_axis(x, nf, axis=1)
        node = 2 * node + 1 + (xv > th).astype(jnp.int32)
    leaf_idx = node - (2 ** max_depth - 1)
    return a["base"] + jnp.sum(leaf[tree_ix, leaf_idx], axis=-1)


def _predict_mlp(a, x):
    h = (x - a["x_mu"]) / a["x_sd"]
    n_layers = sum(1 for k in a if k.startswith("w"))
    for i in range(n_layers):
        h = jnp.matmul(h, a[f"w{i}"], precision=F32_DOT) + a[f"b{i}"]
        if i < n_layers - 1:
            h = jax.nn.relu(h)
    return h[..., 0] * a["y_sd"][0] + a["y_mu"][0]


FAMILY_PREDICT = {
    "mean": _predict_mean,
    "linear": _predict_linear,
    "table": _predict_table,
    "gbdt": _predict_gbdt,
    "mlp": _predict_mlp,
}


# --- stacked (multi-head) family inference --------------------------------------
#
# The fused hot path (Surrogate.predict_heads) evaluates every same-family
# head that shares one feature matrix in ONE batched pass: per-head arrays
# stack along a new leading P axis AT TRACE TIME (pytree leaves are
# untouched, so the artifact format and the compiled-program cache keys
# stay exactly as before — XLA hoists the loop-invariant stacks out of the
# tick scan). Batched dots reassociate reductions, so stacked results may
# differ from the per-head functions by a few ULPs (documented tolerance:
# rtol 1e-5); single-head groups bypass stacking and stay bit-identical.

def _stack_arrays(heads) -> dict:
    """[{k: (..)}] x P -> {k: (P, ..)} — trace-time leaf stacking."""
    return {k: jnp.stack([a[k] for a in heads]) for k in heads[0]}


def _predict_mean_stacked(heads, x):
    mus = jnp.stack([jnp.asarray(a["mu"], jnp.float32).reshape(())
                     for a in heads])
    return jnp.broadcast_to(mus[:, None], (len(heads), x.shape[0]))


def _predict_linear_stacked(heads, x):
    s = _stack_arrays(heads)
    xs = (x[None] - s["mu"][:, None]) / s["sd"][:, None]
    return jnp.einsum("pnf,pf->pn", xs, s["w"][:, :-1],
                      precision=F32_DOT) + s["w"][:, -1:]


def _predict_table_stacked(heads, x):
    s = _stack_arrays(heads)
    xs = (x[None] - s["mu"][:, None]) / s["sd"][:, None]
    d = jnp.sum(jnp.square(s["tx"]), -1)[:, None, :] \
        - 2.0 * jnp.einsum("pnf,prf->pnr", xs, s["tx"], precision=F32_DOT)
    return jnp.take_along_axis(s["ty"], jnp.argmin(d, axis=2), axis=1)


def _predict_mlp_stacked(heads, x, fused_kernel=None):
    s = _stack_arrays(heads)
    n_layers = sum(1 for k in heads[0] if k.startswith("w"))
    if n_layers == 3 and _kernel_heads_enabled(fused_kernel):
        # production MLP(100, 50) config on the Pallas multi-head kernel:
        # all P heads' weights stay resident in VMEM, grid over N-blocks
        from repro.kernels import ops
        return ops.mlp_surrogate_heads(
            x, s["x_mu"], s["x_sd"], s["y_mu"], s["y_sd"],
            s["w0"], s["b0"], s["w1"], s["b1"], s["w2"], s["b2"])
    h = (x[None] - s["x_mu"][:, None]) / s["x_sd"][:, None]
    for i in range(n_layers):
        h = jnp.einsum("pnf,pfh->pnh", h, s[f"w{i}"],
                       precision=F32_DOT) + s[f"b{i}"][:, None]
        if i < n_layers - 1:
            h = jax.nn.relu(h)
    return h[..., 0] * s["y_sd"][:, :1] + s["y_mu"][:, :1]


FAMILY_PREDICT_STACKED = {
    "mean": _predict_mean_stacked,
    "linear": _predict_linear_stacked,
    "table": _predict_table_stacked,
    "mlp": _predict_mlp_stacked,
    # gbdt: per-head traversal only (tree tables rarely share shapes and
    # the gather-heavy walk gains nothing from a batch axis); it still
    # shares the once-built augmented features with every other family.
}


def _kernel_heads_enabled(override=None) -> bool:
    """Dispatch stacked MLP heads to the fused Pallas multi-head kernel.

    Off by default: the einsum path compiles to the same batched dots on
    every backend, while the kernel path (REPRO_FUSED_KERNEL=1, or an
    explicit ``fused_kernel=`` override — see
    ``ops.fused_kernel_enabled``, the single source of truth for the
    flag) keeps all heads' weights resident in VMEM and grids only over
    N-blocks — the layout built for real TPUs
    (kernels/mlp_surrogate.py)."""
    from repro.kernels import ops
    return ops.fused_kernel_enabled(override)


# the Algorithm-1 head schedule: which predictors read which of the three
# per-tick feature variants (wrapper.lasana_step builds exactly these)
ALG1_HEADS = {
    "idle": ("M_ES", "M_V"),
    "act": ("M_O", "M_V", "M_ES"),
    "tr": ("M_ED", "M_L"),
}


def _model_arrays(model) -> tuple:
    """Freeze a fitted ``models.SurrogateModel`` -> (family, arrays dict).

    Only inference state is kept (e.g. the GBDT's training-time bin edges
    are dropped); every entry is an array so the whole predictor is pytree
    leaves."""
    from repro.core.models import (GBDTModel, LinearModel, MLPModel,
                                   MeanModel, TableModel)
    if isinstance(model, MeanModel):
        return "mean", {"mu": np.float32(model.mu)}
    if isinstance(model, LinearModel):
        return "linear", {"w": model.w, "mu": model.sx.mu, "sd": model.sx.sd}
    if isinstance(model, TableModel):
        return "table", {"tx": model.tx, "ty": model.ty,
                         "mu": model.sx.mu, "sd": model.sx.sd}
    if isinstance(model, GBDTModel):
        return "gbdt", {"feat": model.feat, "thr": model.thr,
                        "leaf": model.leaf, "base": np.float32(model.base)}
    if isinstance(model, MLPModel):
        arrays = {}
        for i, lyr in enumerate(model.params):
            arrays[f"w{i}"] = np.asarray(lyr["w"])
            arrays[f"b{i}"] = np.asarray(lyr["b"])
        arrays.update({"x_mu": model.sx.mu, "x_sd": model.sx.sd,
                       "y_mu": model.sy.mu, "y_sd": model.sy.sd})
        return "mlp", arrays
    raise TypeError(f"cannot freeze {type(model).__name__} into a Surrogate")


def _augment(circuit_name: str, feats):
    """Append the circuit's derived interface features — the SAME
    ``circuits.augment_features`` call ``PredictorBank`` applies at fit
    time, so fit and serving can never drift apart."""
    from repro.core.circuits import augment_features
    try:
        circ = get_circuit(circuit_name)
    except KeyError:
        circ = None
    return augment_features(circ, feats)


# --- the artifact ---------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(eq=False, repr=False)
class Surrogate:
    """Immutable inference artifact: selected-predictor arrays + manifest.

    Treat instances as frozen — mutating ``params`` in place invalidates
    jit caches keyed on leaf identity. Build one with
    :meth:`from_bank` (or ``repro.lasana.train``), persist with
    :meth:`save` / :meth:`load`, and pass it *as an argument* through
    jitted simulation entry points (``lasana.simulate``,
    ``wrapper.lasana_step``, ``distributed.make_distributed_step``).

    ``fit_info`` carries optional training metrics (per-predictor val/test
    MSE); it is not a pytree leaf and not part of the compiled-program
    cache key, but it is persisted in the manifest JSON.
    """

    manifest: Manifest
    params: dict
    fit_info: Optional[dict] = None

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        """Leaves: the predictor arrays dict. Aux: the static manifest."""
        return (self.params,), self.manifest

    @classmethod
    def tree_unflatten(cls, manifest, children):
        """Rebuild from (manifest, (params,)); fit_info does not survive."""
        return cls(manifest=manifest, params=children[0])

    # -- construction -------------------------------------------------------
    @classmethod
    def from_bank(cls, bank) -> "Surrogate":
        """Freeze a fitted ``PredictorBank``'s selected models.

        Array shapes (and thus the compiled-program cache key) depend only
        on the selected family and its fitted dimensions, not on the
        training data."""
        families, scales, params = [], [], {}
        for pname in sorted(bank.selected):
            fam, arrays = _model_arrays(bank.selected[pname])
            families.append((pname, fam))
            scales.append((pname, float(bank.scales[pname])))
            params[pname] = {k: jnp.asarray(v) for k, v in arrays.items()}
        fit_info = None
        if bank.results:
            fit_info = {
                p: {f: {"val_mse": r.val_mse, "test_mse": r.test_mse,
                        "test_mape": r.test_mape}
                    for f, r in fams.items()}
                for p, fams in bank.results.items()}
        manifest = Manifest(
            circuit=bank.circuit_name, format_version=FORMAT_VERSION,
            families=tuple(families), scales=tuple(scales),
            features=_feature_names(bank.circuit_name))
        return cls(manifest=manifest, params=params, fit_info=fit_info)

    # -- inference ----------------------------------------------------------
    @property
    def circuit(self) -> str:
        """Registered circuit kind this surrogate was trained for."""
        return self.manifest.circuit

    def predict(self, pname: str, feats):
        """JAX prediction in physical units (energies back to joules).

        ``feats`` are raw ``(x, v, tau, params[, o_prev, o_new])`` rows;
        the circuit's derived interface features are appended here. Pure in
        the pytree leaves — traceable with ``self`` as a jit argument."""
        from repro.kernels import ops
        ops.record_dispatch("predict")
        feats = _augment(self.manifest.circuit, jnp.asarray(feats))
        y = FAMILY_PREDICT[self.manifest.family_of(pname)](
            self.params[pname], feats)
        return y / self.manifest.scale_of(pname)

    def predict_heads(self, feats_idle=None, feats_act=None, feats_tr=None,
                      *, heads=None, augmented: bool = False,
                      fused_kernel=None) -> dict:
        """Fused multi-head inference: one feature build + one batched pass
        per (variant, family) group, instead of one :meth:`predict`
        dispatch per head.

        This is Algorithm 1's hot path (see docs/architecture.md,
        "Inference hot path"): per digital tick the wrapper evaluates up
        to seven predictor heads over three feature variants —

        feats_idle  ``(N, F)`` merged-E2 catch-up rows (zero inputs,
                    stale state, idle tau)
        feats_act   ``(N, F)`` active-event rows (inputs at t, caught-up
                    state, one-clock tau)
        feats_tr    ``(N, F+2)`` transition rows (``feats_act`` plus
                    ``o_prev``/``o_new`` columns) for the
                    transition-aware M_ED/M_L heads

        Any subset may be passed. Each given matrix is augmented with the
        circuit's derived features ONCE (pass ``augmented=True`` when the
        caller already augmented them — e.g. the wrapper builds the
        transition matrix as a column splice of the augmented active one).

        ``heads`` maps variant name -> predictor tuple and defaults to the
        full Algorithm-1 schedule (:data:`ALG1_HEADS`) restricted to this
        surrogate's predictors. Same-family heads whose arrays share
        shapes are stacked along a new leading axis at trace time and
        evaluated in one batched pass (``gbdt`` always walks per head);
        stacking reorders float reductions, so batched results may differ
        from :meth:`predict` by a few ULPs (documented tolerance:
        ``rtol=1e-5``; single-head groups are bit-identical). Caveat for
        discontinuous families: a stacked ``table`` head whose query row
        sits within rounding distance of TWO table rows may resolve the
        nearest-neighbor argmin to the other, equally-near row — the
        deviation is then the gap between those two table entries, not
        ULPs (measure-zero for continuous features, but the rtol contract
        is per-distance, not per-output, at exact ties). Pure in the
        pytree leaves — traceable with ``self`` as a jit argument, and the
        stacks are built from existing leaves so compiled-program cache
        keys (manifest + leaf shapes) are unchanged.

        Returns ``{variant: {pname: (N,) predictions}}`` in physical
        units.
        """
        from repro.kernels import ops
        ops.record_dispatch("predict_heads")
        mats = {"idle": feats_idle, "act": feats_act, "tr": feats_tr}
        mats = {v: jnp.asarray(m) for v, m in mats.items() if m is not None}
        if not mats:
            raise ValueError("predict_heads needs at least one of "
                             "feats_idle / feats_act / feats_tr")
        avail = set(self.manifest.predictors)
        if heads is None:
            heads = {v: tuple(p for p in ALG1_HEADS[v] if p in avail)
                     for v in mats}
        unknown = [(v, p) for v, ps in heads.items() for p in ps
                   if p not in avail]
        if unknown:
            raise ValueError(f"predict_heads: unknown predictor(s) "
                             f"{unknown}; this surrogate carries "
                             f"{sorted(avail)}")
        missing = [v for v in heads if v not in mats]
        if missing:
            raise ValueError(f"predict_heads: heads requested for variant"
                             f"(s) {missing} but no matching feature "
                             "matrix was given")
        if not augmented:
            mats = {v: _augment(self.manifest.circuit, m)
                    for v, m in mats.items()}

        # group same-family heads per matrix; stack only when every array
        # shape matches (mismatched shapes — e.g. per-predictor table row
        # counts — fall back to the exact per-head functions)
        groups: dict = {}
        for v, pnames in heads.items():
            for p in pnames:
                fam = self.manifest.family_of(p)
                if fam in FAMILY_PREDICT_STACKED:
                    sig = tuple(sorted((k, tuple(a.shape))
                                       for k, a in self.params[p].items()))
                    key = (v, fam, sig)
                else:
                    key = (v, fam, p)
                groups.setdefault(key, []).append(p)

        out: dict = {v: {} for v in heads}
        for (v, fam, _), pnames in groups.items():
            x = mats[v]
            if len(pnames) == 1 or fam not in FAMILY_PREDICT_STACKED:
                for p in pnames:
                    out[v][p] = FAMILY_PREDICT[fam](self.params[p], x) \
                        / self.manifest.scale_of(p)
            else:
                fn = FAMILY_PREDICT_STACKED[fam]
                if fam == "mlp":
                    # only the MLP family has a Pallas kernel path; thread
                    # the explicit override so tests/callers can pick the
                    # path without env mutation (ops.fused_kernel_enabled)
                    ys = fn([self.params[p] for p in pnames], x,
                            fused_kernel=fused_kernel)
                else:
                    ys = fn([self.params[p] for p in pnames], x)
                for i, p in enumerate(pnames):
                    out[v][p] = ys[i] / self.manifest.scale_of(p)
        return out

    def predict_np(self, pname: str, feats) -> np.ndarray:
        """Host-side convenience wrapper around :meth:`predict`."""
        return np.asarray(self.predict(pname, np.asarray(feats)))

    def __repr__(self):
        fams = ", ".join(f"{p}:{f}" for p, f in self.manifest.families)
        return f"Surrogate({self.manifest.circuit!r}, {fams})"

    # -- persistence --------------------------------------------------------
    def save(self, path: str) -> None:
        """Write one versioned ``.npz``: arrays + JSON ``__manifest__``.

        ``path`` may omit the ``.npz`` extension; it is normalized so the
        :meth:`load` round trip works either way."""
        path = _npz_path(path)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        arrays = {f"{p}/{k}": np.asarray(v)
                  for p, d in self.params.items() for k, v in d.items()}
        manifest = {
            "format_version": self.manifest.format_version,
            "circuit": self.manifest.circuit,
            "families": dict(self.manifest.families),
            "scales": dict(self.manifest.scales),
            "features": list(self.manifest.features),
            "fit_info": self.fit_info,
        }
        arrays["__manifest__"] = np.frombuffer(
            json.dumps(manifest).encode(), dtype=np.uint8)
        np.savez_compressed(path, **arrays)

    @classmethod
    def load(cls, path: str) -> "Surrogate":
        """Load a surrogate saved by :meth:`save`.

        ``path`` may omit the ``.npz`` extension (mirroring :meth:`save`).
        Raises ``FileNotFoundError`` naming every path tried when neither
        spelling exists (``np.load`` used to leak a raw error naming only
        the post-normalization path). Raises ``ValueError`` if the file's
        format version differs from :data:`FORMAT_VERSION` — array
        schemas are version-specific, so a mismatched file must be
        regenerated, never reinterpreted."""
        if not os.path.isfile(path):
            alt = _npz_path(path)
            if alt == path or not os.path.isfile(alt):
                tried = sorted({path, alt})
                raise FileNotFoundError(
                    "no surrogate artifact at "
                    + " or ".join(repr(p) for p in tried)
                    + " (expected an .npz written by Surrogate.save)")
            path = alt
        with np.load(path) as z:
            if "__manifest__" not in z.files:
                raise ValueError(f"{path}: not a Surrogate artifact "
                                 "(missing __manifest__)")
            meta = json.loads(bytes(z["__manifest__"].tobytes()).decode())
            version = meta.get("format_version")
            if version != FORMAT_VERSION:
                raise ValueError(
                    f"{path}: surrogate format version {version!r} is not "
                    f"supported (this build reads version {FORMAT_VERSION}); "
                    "regenerate the artifact with Surrogate.save")
            params = {}
            for pname in meta["families"]:
                params[pname] = {
                    k.split("/", 1)[1]: jnp.asarray(z[k]) for k in z.files
                    if k.startswith(pname + "/")}
        manifest = Manifest(
            circuit=meta["circuit"], format_version=version,
            families=tuple(sorted(meta["families"].items())),
            scales=tuple(sorted(meta["scales"].items())),
            features=tuple(meta.get("features", ())))
        return cls(manifest=manifest, params=params,
                   fit_info=meta.get("fit_info"))


def structure_key(surrogates) -> tuple:
    """Hashable structure key of a surrogate pytree (or library of them).

    ``(treedef, ((leaf shape, dtype), ...))`` — two artifacts with equal
    keys are weight swaps of one another and may share a compiled
    program; anything else (different family mix, different fitted
    dimensions) must compile its own. This is THE cache-key convention
    for every compiled surrogate-serving program (``NetworkEngine``
    network programs, the DSE sweep evaluator), so the zero-recompile
    hot-swap contract cannot drift between engines."""
    leaves, treedef = jax.tree.flatten(surrogates)
    return treedef, tuple((tuple(l.shape), str(l.dtype)) for l in leaves)


def as_surrogate(obj) -> Surrogate:
    """Coerce a legacy ``PredictorBank`` (or pass through a Surrogate)."""
    if isinstance(obj, Surrogate):
        return obj
    from repro.core.predictors import PredictorBank
    if isinstance(obj, PredictorBank):
        return Surrogate.from_bank(obj)
    raise ValueError(
        f"cannot use {type(obj).__name__!r} as a surrogate; pass a "
        "repro.lasana.Surrogate (or a legacy fitted PredictorBank)")


# --- per-circuit-kind library ---------------------------------------------------

@jax.tree_util.register_pytree_node_class
class SurrogateLibrary:
    """Circuit kind -> :class:`Surrogate` mapping for heterogeneous graphs.

    Itself a pytree (kinds are aux data, surrogates are subtrees), so a
    whole library passes through jitted simulation programs as one traced
    argument — mixed crossbar/LIF graphs stop sharing a single ``bank=``.
    """

    def __init__(self, surrogates=()):
        self._by_kind = dict(surrogates)
        for kind, s in self._by_kind.items():
            if isinstance(s, Surrogate) and s.circuit != kind:
                raise ValueError(
                    f"surrogate trained for circuit {s.circuit!r} registered "
                    f"under kind {kind!r}")

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        """Leaves: the surrogates (sorted by kind). Aux: the kind names."""
        kinds = tuple(sorted(self._by_kind))
        return tuple(self._by_kind[k] for k in kinds), kinds

    @classmethod
    def tree_unflatten(cls, kinds, surrogates):
        """Rebuild the mapping from sorted kinds + surrogate subtrees."""
        lib = cls.__new__(cls)          # skip kind validation on tracers
        lib._by_kind = dict(zip(kinds, surrogates))
        return lib

    # -- mapping surface ----------------------------------------------------
    def __getitem__(self, kind: str) -> Surrogate:
        return self._by_kind[kind]

    def get(self, kind: str, default=None):
        """Surrogate registered for ``kind``, or ``default``."""
        return self._by_kind.get(kind, default)

    def __contains__(self, kind: str) -> bool:
        return kind in self._by_kind

    def __len__(self) -> int:
        return len(self._by_kind)

    def kinds(self) -> tuple:
        """Registered circuit kinds, sorted."""
        return tuple(sorted(self._by_kind))

    def items(self):
        """(kind, surrogate) pairs, sorted by kind."""
        return tuple((k, self._by_kind[k]) for k in sorted(self._by_kind))

    def __repr__(self):
        return f"SurrogateLibrary({', '.join(self.kinds()) or 'empty'})"

    # -- persistence --------------------------------------------------------
    def save(self, directory: str) -> None:
        """Write one ``{kind}.npz`` per surrogate into ``directory``."""
        os.makedirs(directory, exist_ok=True)
        for kind, s in self._by_kind.items():
            s.save(os.path.join(directory, f"{kind}.npz"))

    @classmethod
    def load(cls, directory: str) -> "SurrogateLibrary":
        """Load every ``*.npz`` in ``directory`` saved by :meth:`save`."""
        lib = {}
        for name in sorted(os.listdir(directory)):
            if name.endswith(".npz"):
                lib[name[:-4]] = Surrogate.load(os.path.join(directory, name))
        return cls(lib)
