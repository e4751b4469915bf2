"""Plain reference of piecewise-linear leaves (``linear_tree`` of LightGBM's
``docs/Parameters.rst``: "fit piecewise linear gradient boosting tree";
Shi, Li and Li, *Gradient Boosting with Piece-Wise Linear Regression
Trees*, arXiv:1802.05640, Eq. 3) for a binary objective.

Leaf-wise boosting as ``gbdt.py`` states it (its binning, gradients,
histograms, split scan and partition grow each tree unchanged), with the
leaves' outputs made linear. Per boosting step, after the tree is grown:

- the first tree of the model keeps its constant leaves ("the first tree
  has constant leaf values");
- for every other tree and each leaf ``l``, ``S_l`` are the distinct
  features split on along the root -> leaf branch (this reference's own
  tree), ascending, ``k = |S_l|``, and ``a_i = [x_i,S_l, 1]`` over the
  leaf's rows with no NaN in ``S_l``;
- ``beta = -(sum h_i a_i a_i^T + lambda diag(1, ..., 1, 0))^-1 sum g_i
  a_i``, ``lambda = linear_lambda``: the constant is not regularised;
- a leaf with fewer than ``k + 1`` such rows, or whose solve is not
  finite, keeps its constant; coefficients within ``kZeroThreshold`` of
  zero drop out with their feature; the learning rate scales the
  coefficients and the constant;
- a row's output is ``const + sum beta_j x_j`` over its leaf's features,
  its leaf's constant value where one of them is NaN; every training row's
  score moves by it, and so does every held-out row's.

The sums are the raw float32 values' products summed leaf by leaf on the
device in float32 at ``highest`` precision (rows padded to a power of
two), solved by ``jnp.linalg.solve`` in float32; ``fit_dtype=np.float64``
sums and solves in numpy's float64 instead, as the upstream learner does.
It imports nothing of ``lightgbm_tpu``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import gbdt
from .gbdt import logloss

loss = logloss          # the loss of this reference's objective

kZeroThreshold = 1e-35


@dataclasses.dataclass
class Params(gbdt.Params):
    linear_tree: bool = True
    linear_lambda: float = 0.0


# what a program has to have to be judged here: without the leaves' fit on
# the device (a module of its own) a program fits them on the host in
# float64, two copies of the 3.9 GB table a tree, which runs past the time
# a run of the cell has; the cell asks for it before the program starts
DEVICE_FIT = os.path.join("ops", "linear.py")


def program_lacks() -> str:
    """What the program the harness will import lacks to run this
    configuration in a run's time, or "" where it lacks nothing. The
    program is found where Python finds it and is not imported."""
    found = importlib.util.find_spec("lightgbm_tpu")
    roots = list(found.submodule_search_locations or []) if found else []
    if any(os.path.exists(os.path.join(r, DEVICE_FIT)) for r in roots):
        return ""
    return ("the program has no device fit of linear leaves "
            "(lightgbm_tpu/%s): its host float64 fit takes two copies of "
            "the raw table a tree and cannot run this cell within a run's "
            "time" % DEVICE_FIT)


def init_score(y: np.ndarray) -> float:
    """``gbdt.init_score``; the harness asks for it before the program
    starts, so a program this configuration cannot be run on is refused
    here, at once (``program_lacks``)."""
    lacks = program_lacks()
    if lacks:
        raise RuntimeError("bosch-linear: " + lacks)
    return gbdt.init_score(y)


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def branch_features(tree: gbdt.RefTree) -> list:
    """Per leaf of ``tree`` the sorted distinct features split on along its
    branch: split ``i`` of leaf ``tree.leaf[i]`` on ``tree.feature[i]``
    keeps the left rows in that leaf and sends the right ones to leaf
    ``i + 1``, both under the feature."""
    paths = [[]]
    for leaf, f in zip(tree.leaf, tree.feature):
        paths[leaf] = paths[leaf] + [int(f)]
        paths.append(list(paths[leaf]))
    return [sorted(set(p)) for p in paths]


def _sums(a, g, h, dtype):
    """``(sum h a a^T, sum g a)`` over the rows of ``a``."""
    if dtype == np.float64:
        a64 = a.astype(np.float64)
        return ((a64 * h[:, None]).T @ a64, a64.T @ g.astype(np.float64))
    n = _next_pow2(len(a))
    pad = lambda v: np.concatenate([v, np.zeros((n - len(v),) + v.shape[1:],
                                                dtype=np.float32)])
    ad, gd, hd = (jnp.asarray(pad(v.astype(np.float32))) for v in (a, g, h))
    with jax.default_matmul_precision("highest"):
        A = jnp.einsum("ni,nj->ij", ad * hd[:, None], ad)
        b = jnp.einsum("ni,n->i", ad, gd)
    return np.asarray(A), np.asarray(b)


def _solve(A, b, lam, dtype):
    reg = np.full(len(b), lam, dtype=np.float64)
    reg[-1] = 0.0
    if dtype == np.float64:
        with np.errstate(all="ignore"):
            try:
                return -np.linalg.solve(A + np.diag(reg), b)
            except np.linalg.LinAlgError:
                return np.full(len(b), np.nan)
    M = jnp.asarray(A, dtype=jnp.float32) + jnp.diag(
        jnp.asarray(reg, dtype=jnp.float32))
    return -np.asarray(jnp.linalg.solve(M, jnp.asarray(b, jnp.float32)),
                       dtype=np.float64)


class Reference(gbdt.Reference):
    """``gbdt.Reference``'s grower and held-out walk, the leaves made
    linear over the raw float32 values ``X``. The controls besides the
    interface's: ``fit_first_tree`` (the first tree linear too),
    ``constant_leaves`` (no tree linear: ``gbdt.py``'s model),
    ``fit_dtype`` (the sums and solve's precision); ``linear_lambda`` comes
    with the parameters."""

    def __init__(self, X: np.ndarray, y: np.ndarray, params: Params,
                 gh_dtype=jnp.float32, drop_odd_rows: bool = False,
                 freeze_scores: bool = False, fit_first_tree: bool = False,
                 constant_leaves: bool = False, fit_dtype=np.float32):
        super().__init__(X, y, params, gh_dtype=gh_dtype,
                         drop_odd_rows=drop_odd_rows,
                         freeze_scores=freeze_scores)
        self.X = np.asarray(X, dtype=np.float32)
        self.fit_first_tree = fit_first_tree
        self.constant_leaves = constant_leaves or not params.linear_tree
        self.fit_dtype = np.float64 if fit_dtype == np.float64 \
            else np.float32
        # per linear leaf fit: (tree, leaf, rows, k, condition number)
        self.fits = []

    def step(self) -> np.ndarray:
        """One boosting step; returns the scores after it."""
        t0 = time.perf_counter()
        p = gbdt._sigmoid(self.score.astype(np.float32))
        g = (p - self.y).astype(np.float32)
        h = (p * (1.0 - p)).astype(np.float32)
        if self.drop_odd_rows:
            g[1::2] = 0.0
            h[1::2] = 0.0
        gh = jnp.asarray(np.stack([g, h], axis=1)).astype(self.gh_dtype)
        gh = gh.astype(jnp.float32)
        gh_host = np.asarray(gh, dtype=np.float64)
        tree, leaf_rows = self._grow(gh, gh_host)
        first = not self.trees
        self.trees.append(tree)
        self._fit(tree, leaf_rows, gh_host[:, 0].astype(np.float32),
                  gh_host[:, 1].astype(np.float32),
                  linear=not self.constant_leaves
                  and (self.fit_first_tree or not first))
        if not self.freeze_scores:
            for leaf, rows in leaf_rows.items():
                self.score[rows] += self._output(
                    tree, leaf, self.X, rows).astype(np.float32)
        self.seconds["steps"].append(time.perf_counter() - t0)
        return self.score.copy()

    def _fit(self, tree, leaf_rows, g, h, linear: bool) -> None:
        """``tree.lin``: per leaf ``(features, coefficients, constant)``,
        shrunk, or None where the leaf keeps its constant value."""
        tree.lin = [None] * len(tree.value)
        if not linear:
            return
        lr = self.p.learning_rate
        for leaf, feats in enumerate(branch_features(tree)):
            k = len(feats)
            if not k:
                continue
            rows = leaf_rows[leaf]
            xs = self.X[np.ix_(rows, feats)]
            ok = ~np.isnan(xs).any(axis=1)
            if ok.sum() < k + 1:
                continue
            a = np.concatenate([xs[ok], np.ones((int(ok.sum()), 1),
                                                np.float32)], axis=1)
            A, b = _sums(a, g[rows][ok], h[rows][ok], self.fit_dtype)
            beta = _solve(A, b, self.p.linear_lambda, self.fit_dtype)
            if not np.all(np.isfinite(beta)):
                continue
            with np.errstate(all="ignore"):
                cond = float(np.linalg.cond(np.asarray(A, np.float64)))
            self.fits.append((len(self.trees) - 1, leaf, int(ok.sum()), k,
                              cond))
            beta = beta.astype(self.fit_dtype)
            keep = np.abs(beta[:k]) > kZeroThreshold
            tree.lin[leaf] = ([f for f, kept in zip(feats, keep) if kept],
                              beta[:k][keep] * self.fit_dtype(lr),
                              beta[k] * self.fit_dtype(lr))

    @staticmethod
    def _output(tree, leaf: int, X: np.ndarray, rows) -> np.ndarray:
        """Float64 outputs of leaf ``leaf`` for the raw values of its rows
        ``rows`` of ``X``."""
        if tree.lin[leaf] is None:
            return np.full(len(rows), tree.value[leaf])
        feats, coef, const = tree.lin[leaf]
        xs = X[np.ix_(rows, feats)].astype(np.float64)
        out = float(const) + xs @ np.asarray(coef, dtype=np.float64)
        return np.where(np.isnan(xs).any(axis=1), tree.value[leaf], out)

    def predict_raw(self, X: np.ndarray) -> np.ndarray:
        """Raw scores of unseen float32 rows through every tree grown."""
        X = np.asarray(X, dtype=np.float32)
        # a feature with no NaN among the training rows walks a NaN as 0
        # (missing type none); the leaf's output falls back on it
        bins_t = np.ascontiguousarray(np.asarray(self.bin_rows(
            np.where(np.isnan(X), np.float32(0.0), X))).T)
        out = np.full(X.shape[0], self.init, dtype=np.float64)
        for tree in self.trees:
            leaf = tree.leaves(lambda f: bins_t[f])
            for l in np.unique(leaf):
                rows = np.flatnonzero(leaf == l)
                out[rows] += self._output(tree, int(l), X, rows)
        return out
