"""Surrogate model families (paper Table I): Mean, Table (nearest-neighbor),
Linear, GBDT (CatBoost stand-in, from scratch), MLP (100, 50).

Every model exposes both a numpy ``predict`` (benchmarks) and a JAX-traceable
``jax_predict`` so selected predictors can run *inside* the jitted,
shard_map'd simulation step — the TPU adaptation of the paper's C++ wrapper.

The GBDT uses 256-bin histogram split finding and **complete binary trees**
stored as dense per-depth (feature, threshold) arrays: prediction is
max_depth gathers+compares per tree, no pointer chasing — that is the
MXU/VPU-friendly reformulation of CatBoost inference (see DESIGN.md §4/§8).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.surrogate import F32_DOT


# --- standardization -----------------------------------------------------------

@dataclasses.dataclass
class Standardizer:
    mu: np.ndarray
    sd: np.ndarray

    @staticmethod
    def fit(x: np.ndarray) -> "Standardizer":
        mu = x.mean(axis=0)
        sd = x.std(axis=0)
        sd = np.where(sd < 1e-12, 1.0, sd)
        return Standardizer(mu.astype(np.float32), sd.astype(np.float32))

    def apply(self, x):
        return (x - self.mu) / self.sd

    def apply_jax(self, x):
        return (x - jnp.asarray(self.mu)) / jnp.asarray(self.sd)


class SurrogateModel:
    name: str = "base"
    train_time: float = 0.0

    def fit(self, xtr, ytr, xva, yva):  # pragma: no cover - interface
        raise NotImplementedError

    def predict(self, x: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def jax_predict(self, x):
        raise NotImplementedError


# --- mean ------------------------------------------------------------------------

class MeanModel(SurrogateModel):
    name = "mean"

    def fit(self, xtr, ytr, xva, yva):
        t0 = time.time()
        self.mu = float(np.mean(ytr))
        self.train_time = time.time() - t0
        return self

    def predict(self, x):
        return np.full((x.shape[0],), self.mu, np.float32)

    def jax_predict(self, x):
        return jnp.full((x.shape[0],), self.mu, jnp.float32)


# --- table (1-NN) -----------------------------------------------------------------

class TableModel(SurrogateModel):
    """Nearest-neighbor estimator (table-based models in circuit simulators).

    Inference cost is dominated by the distance computation — the paper's
    Table I shows exactly this blowing up with crossbar dimensionality.
    """

    name = "table"

    def __init__(self, max_rows: int = 20000):
        self.max_rows = max_rows

    def fit(self, xtr, ytr, xva, yva):
        t0 = time.time()
        n = min(len(ytr), self.max_rows)
        idx = np.random.default_rng(0).permutation(len(ytr))[:n]
        self.sx = Standardizer.fit(xtr)
        self.tx = self.sx.apply(xtr[idx]).astype(np.float32)
        self.ty = ytr[idx].astype(np.float32)
        self.train_time = time.time() - t0
        return self

    def predict(self, x):
        xs = self.sx.apply(x).astype(np.float32)
        out = np.empty((x.shape[0],), np.float32)
        t_sq = (self.tx ** 2).sum(-1)
        step = max(1, int(4e7) // max(self.tx.shape[0], 1))  # chunk queries
        for i in range(0, x.shape[0], step):
            blk = xs[i : i + step]
            # |a-b|^2 = |a|^2 - 2ab + |b|^2 (argmin ignores |a|^2)
            d = t_sq[None, :] - 2.0 * (blk @ self.tx.T)
            out[i : i + step] = self.ty[np.argmin(d, axis=1)]
        return out

    def jax_predict(self, x):
        xs = self.sx.apply_jax(x)
        tx = jnp.asarray(self.tx)
        d = jnp.sum(jnp.square(tx), -1)[None, :] - 2.0 * (xs @ tx.T)
        return jnp.asarray(self.ty)[jnp.argmin(d, axis=1)]


# --- linear ------------------------------------------------------------------------

class LinearModel(SurrogateModel):
    name = "linear"

    def fit(self, xtr, ytr, xva, yva):
        t0 = time.time()
        self.sx = Standardizer.fit(xtr)
        a = np.concatenate([self.sx.apply(xtr),
                            np.ones((len(ytr), 1), np.float32)], axis=1)
        w, *_ = np.linalg.lstsq(a.astype(np.float64), ytr.astype(np.float64),
                                rcond=None)
        self.w = w.astype(np.float32)
        self.train_time = time.time() - t0
        return self

    def predict(self, x):
        a = np.concatenate([self.sx.apply(x),
                            np.ones((x.shape[0], 1), np.float32)], axis=1)
        return a @ self.w

    def jax_predict(self, x):
        xs = self.sx.apply_jax(x)
        w = jnp.asarray(self.w)
        return xs @ w[:-1] + w[-1]


# --- GBDT --------------------------------------------------------------------------

class GBDTModel(SurrogateModel):
    """Histogram gradient-boosted complete trees (CatBoost stand-in)."""

    name = "gbdt"

    def __init__(self, n_trees=80, max_depth=8, lr=0.12, n_bins=256,
                 subsample=0.7, min_leaf=8, l2=1.0, seed=0):
        self.n_trees = n_trees
        self.max_depth = max_depth
        self.lr = lr
        self.n_bins = n_bins
        self.subsample = subsample
        self.min_leaf = min_leaf
        self.l2 = l2
        self.seed = seed

    # binning ---------------------------------------------------------------
    def _fit_bins(self, x):
        qs = np.linspace(0, 1, self.n_bins + 1)[1:-1]
        self.edges = np.quantile(x, qs, axis=0).astype(np.float32)  # (B-1, F)

    def _binize(self, x):
        out = np.zeros(x.shape, np.int32)
        for f in range(x.shape[1]):
            out[:, f] = np.searchsorted(self.edges[:, f], x[:, f], side="right")
        return out

    def fit(self, xtr, ytr, xva, yva):
        t0 = time.time()
        rng = np.random.default_rng(self.seed)
        x = np.asarray(xtr, np.float32)
        y = np.asarray(ytr, np.float64)
        n, f = x.shape
        self._fit_bins(x)
        bins = self._binize(x)
        self.base = float(np.mean(y))
        pred = np.full(n, self.base)
        n_nodes = 2 ** self.max_depth - 1          # internal nodes
        n_leaves = 2 ** self.max_depth
        self.feat = np.zeros((self.n_trees, n_nodes), np.int32)
        self.thr = np.full((self.n_trees, n_nodes), np.inf, np.float32)
        self.leaf = np.zeros((self.n_trees, n_leaves), np.float32)

        best_va = np.inf
        va_pred = np.full(len(yva), self.base)
        xva_np = np.asarray(xva, np.float32)
        self._kept = self.n_trees

        for t in range(self.n_trees):
            g = (y - pred)                                       # residuals
            if self.subsample < 1.0:
                mask = rng.random(n) < self.subsample
            else:
                mask = np.ones(n, bool)
            node = np.zeros(n, np.int32)                         # current node per sample
            for d in range(self.max_depth):
                lo = 2 ** d - 1
                n_level = 2 ** d
                # histograms over (level-node, feature, bin) in one shot
                rel = node[mask] - lo
                flat = (rel[:, None] * f + np.arange(f)[None, :]) * self.n_bins \
                    + bins[mask]
                gs = np.zeros(n_level * f * self.n_bins)
                cs = np.zeros(n_level * f * self.n_bins)
                np.add.at(gs, flat.ravel(),
                          np.repeat(g[mask], f))
                np.add.at(cs, flat.ravel(), 1.0)
                gs = gs.reshape(n_level, f, self.n_bins)
                cs = cs.reshape(n_level, f, self.n_bins)
                gc = np.cumsum(gs, axis=2)
                cc = np.cumsum(cs, axis=2)
                g_tot = gc[:, :, -1:]
                c_tot = cc[:, :, -1:]
                gl, cl = gc, cc
                gr, cr = g_tot - gc, c_tot - cc
                gain = (gl ** 2 / (cl + self.l2) + gr ** 2 / (cr + self.l2)
                        - g_tot ** 2 / (c_tot + self.l2))
                gain[(cl < self.min_leaf) | (cr < self.min_leaf)] = -np.inf
                gain = gain[:, :, :-1]                           # last bin can't split
                best = gain.reshape(n_level, -1).argmax(axis=1)
                bf = (best // (self.n_bins - 1)).astype(np.int32)
                bb = (best % (self.n_bins - 1)).astype(np.int32)
                ok = np.take_along_axis(
                    gain.reshape(n_level, -1), best[:, None], 1)[:, 0] > 1e-12
                # thresholds from bin edges; dead nodes stay (f=0, thr=inf)
                for j in range(n_level):
                    ni = lo + j
                    if ok[j]:
                        self.feat[t, ni] = bf[j]
                        self.thr[t, ni] = self.edges[min(bb[j], self.n_bins - 2), bf[j]]
                # descend (x <= thr -> left)
                nf = self.feat[t, node]
                nt = self.thr[t, node]
                go_right = x[np.arange(n), nf] > nt
                node = 2 * node + 1 + go_right.astype(np.int32)
            leaf_idx = node - (2 ** self.max_depth - 1)
            sums = np.zeros(n_leaves)
            cnts = np.zeros(n_leaves)
            np.add.at(sums, leaf_idx[mask], g[mask])
            np.add.at(cnts, leaf_idx[mask], 1.0)
            vals = self.lr * sums / (cnts + self.l2)
            self.leaf[t] = vals.astype(np.float32)
            pred = pred + vals[leaf_idx]
            # early stopping on validation
            va_pred = va_pred + self._tree_predict(xva_np, t)
            mse = float(np.mean((va_pred - yva) ** 2))
            if mse < best_va - 1e-12:
                best_va = mse
                self._kept = t + 1
        self.feat = self.feat[: self._kept]
        self.thr = self.thr[: self._kept]
        self.leaf = self.leaf[: self._kept]
        self.train_time = time.time() - t0
        return self

    def _tree_predict(self, x, t):
        node = np.zeros(x.shape[0], np.int32)
        for _ in range(self.max_depth):
            nf = self.feat[t, node]
            nt = self.thr[t, node]
            node = 2 * node + 1 + (x[np.arange(x.shape[0]), nf] > nt)
        return self.leaf[t, node - (2 ** self.max_depth - 1)]

    def predict(self, x):
        x = np.asarray(x, np.float32)
        out = np.full(x.shape[0], self.base, np.float32)
        for t in range(self.feat.shape[0]):
            out = out + self._tree_predict(x, t)
        return out

    def jax_predict(self, x):
        """Depth-unrolled vectorized walk over ALL trees at once.

        Complete trees = dense (tree, node) tables: the walk is max_depth
        gathers + compares, fully vectorized over (samples x trees).
        """
        feat = jnp.asarray(self.feat)            # (T, nodes)
        thr = jnp.asarray(self.thr)
        leaf = jnp.asarray(self.leaf)            # (T, L)
        n_t = feat.shape[0]
        tree_ix = jnp.arange(n_t)[None, :]       # (1, T)
        node = jnp.zeros((x.shape[0], n_t), jnp.int32)
        for _ in range(self.max_depth):
            nf = feat[tree_ix, node]             # (N, T)
            th = thr[tree_ix, node]
            xv = jnp.take_along_axis(x, nf, axis=1)
            node = 2 * node + 1 + (xv > th).astype(jnp.int32)
        leaf_idx = node - (2 ** self.max_depth - 1)
        out = jnp.sum(leaf[tree_ix, leaf_idx], axis=-1)
        return self.base + out


# --- MLP ---------------------------------------------------------------------------

class MLPModel(SurrogateModel):
    """Pure-JAX MLP(100, 50), Adam, early stopping on validation loss."""

    name = "mlp"

    def __init__(self, hidden=(100, 50), lr=2e-3, batch=1024, max_epochs=120,
                 patience=12, l2=1e-6, seed=0):
        self.hidden = hidden
        self.lr = lr
        self.batch = batch
        self.max_epochs = max_epochs
        self.patience = patience
        self.l2 = l2
        self.seed = seed

    def _init(self, key, dims):
        params = []
        for i in range(len(dims) - 1):
            key, k = jax.random.split(key)
            w = jax.random.normal(k, (dims[i], dims[i + 1])) * np.sqrt(2.0 / dims[i])
            params.append({"w": w.astype(jnp.float32),
                           "b": jnp.zeros((dims[i + 1],), jnp.float32)})
        return params

    @staticmethod
    def _apply(params, x):
        h = x
        for i, lyr in enumerate(params):
            # f32 on every backend, so a surrogate trained on the chip
            # matches one trained on a CPU
            h = jnp.matmul(h, lyr["w"], precision=F32_DOT) + lyr["b"]
            if i < len(params) - 1:
                h = jax.nn.relu(h)
        return h[..., 0]

    def fit(self, xtr, ytr, xva, yva):
        t0 = time.time()
        self.sx = Standardizer.fit(xtr)
        self.sy = Standardizer.fit(ytr[:, None])
        x = jnp.asarray(self.sx.apply(xtr), jnp.float32)
        y = jnp.asarray(self.sy.apply(ytr[:, None])[:, 0], jnp.float32)
        xv = jnp.asarray(self.sx.apply(xva), jnp.float32)
        yv = jnp.asarray(self.sy.apply(yva[:, None])[:, 0], jnp.float32)
        dims = (x.shape[1], *self.hidden, 1)
        key = jax.random.PRNGKey(self.seed)
        params = self._init(key, dims)
        opt = [{"m": jax.tree.map(jnp.zeros_like, params),
                "v": jax.tree.map(jnp.zeros_like, params)}]
        l2 = self.l2
        lr = self.lr

        @jax.jit
        def step(params, m, v, t, xb, yb):
            def loss_fn(p):
                pred = self._apply(p, xb)
                return jnp.mean(jnp.square(pred - yb)) + l2 * sum(
                    jnp.sum(jnp.square(l["w"])) for l in p)
            loss, g = jax.value_and_grad(loss_fn)(params)
            m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
            v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * jnp.square(b), v, g)
            mh = jax.tree.map(lambda a: a / (1 - 0.9 ** t), m)
            vh = jax.tree.map(lambda a: a / (1 - 0.999 ** t), v)
            params = jax.tree.map(
                lambda p, a, b: p - lr * a / (jnp.sqrt(b) + 1e-8), params, mh, vh)
            return params, m, v, loss

        @jax.jit
        def val_loss(params):
            return jnp.mean(jnp.square(self._apply(params, xv) - yv))

        m, v = opt[0]["m"], opt[0]["v"]
        rng = np.random.default_rng(self.seed)
        n = x.shape[0]
        best = (np.inf, params)
        bad = 0
        t = 0
        for epoch in range(self.max_epochs):
            perm = rng.permutation(n)
            for i in range(0, n - self.batch + 1, self.batch):
                idx = perm[i : i + self.batch]
                t += 1
                params, m, v, _ = step(params, m, v, t, x[idx], y[idx])
            vl = float(val_loss(params))
            if vl < best[0] - 1e-7:
                best = (vl, jax.tree.map(lambda a: a, params))
                bad = 0
            else:
                bad += 1
                if bad >= self.patience:
                    break
        self.params = jax.tree.map(np.asarray, best[1])
        self.train_time = time.time() - t0
        return self

    def predict(self, x):
        return np.asarray(self.jax_predict(jnp.asarray(x, jnp.float32)))

    def jax_predict(self, x):
        xs = self.sx.apply_jax(x)
        p = jax.tree.map(jnp.asarray, self.params)
        yn = self._apply(p, xs)
        return yn * jnp.asarray(self.sy.sd[0]) + jnp.asarray(self.sy.mu[0])


MODEL_FAMILIES = {
    "mean": MeanModel,
    "table": TableModel,
    "linear": LinearModel,
    "gbdt": GBDTModel,
    "mlp": MLPModel,
}
