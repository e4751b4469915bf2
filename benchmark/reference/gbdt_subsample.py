"""Plain reference of column and row sampling (``feature_fraction``,
``bagging_fraction``, ``bagging_freq`` of LightGBM's ``docs/Parameters.rst``,
as its ``examples/binary_classification/train.conf`` sets them: "will random
select 80% feature to train on each iteration", "will perform bagging every
5 iterations") for a binary objective.

Leaf-wise boosting as ``gbdt.py`` states it, with the two differences the
settings make. Per boosting step, with ``g``, ``h`` the logistic loss's
float32 gradient and hessian in the form LightGBM's binary objective gives
them, computed on the device (``gbdt_quant.py`` says why), ``n`` the rows and
``F`` the columns:

- the bag: where ``bagging_fraction < 1`` and ``bagging_freq > 0``, a row is
  in the bag where ``u < bagging_fraction``, ``u = jax.random.uniform(
  fold_in(PRNGKey(bagging_seed), iteration // bagging_freq), (n,))``,
  iterations counted from 0; it is drawn anew where ``iteration %
  bagging_freq == 0`` and kept otherwise. It is an indicator: no gradient is
  rescaled;
- the columns: where ``feature_fraction < 1``, ``max(1, round(F *
  feature_fraction))`` of them, ``numpy.random.RandomState(
  feature_fraction_seed).choice(F, k, replace=False)``, one draw a tree from
  the one stream, in the order the trees are grown;
- the tree is grown over the in-bag rows and the sampled columns and nothing
  else: the row permutation holds only the bag, the binned table the grower
  is handed holds only the sampled columns (in ascending order, so that a
  tie between two columns falls to the lower one, as over the whole table),
  and so do the histograms, the candidates, the leaves' sums and the leaves'
  values;
- every row's score, in the bag or not, moves by the value of the leaf the
  row walks to.

Float32 at ``highest``. Binning, the split rule, the held-out walk, the loss
and the trees' form are ``gbdt.py``'s and ``binning.py``'s, the grower over a
given set of rows is ``gbdt_goss.py``'s; it imports nothing of
``lightgbm_tpu`` and uses jax's public ``random`` and numpy's only. A tree's
``smaller_rows`` are in-bag counts.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import gbdt, gbdt_goss
from .gbdt import init_score, logloss  # noqa: F401
from .gbdt_quant import _gradients

loss = logloss          # the loss of this reference's objective


@dataclasses.dataclass
class Params(gbdt.Params):
    feature_fraction: float = 1.0
    bagging_fraction: float = 1.0
    bagging_freq: int = 0
    bagging_seed: int = 3
    feature_fraction_seed: int = 2


@jax.jit
def _bag_draw(key, draw, fraction, like):
    u = jax.random.uniform(jax.random.fold_in(key, draw), like.shape)
    return u < fraction


class _SampledColumns:
    """What ``gbdt_goss.Reference._grow_on`` reads of a reference, with the
    binned table cut to the columns ``cols``: the grower has no other column
    to histogram or to scan, and numbers the ones it has from 0."""

    def __init__(self, ref: "Reference", cols: np.ndarray):
        self.p, self._idx = ref.p, ref._idx
        self.bins = jnp.take(ref.bins, jnp.asarray(cols), axis=1)
        self.bins_t = ref.bins_t[cols]


class Reference(gbdt.Reference):
    """``gbdt.Reference``'s binned data and held-out walk, with ``step()``
    growing each tree from the bag's rows and the tree's columns alone."""

    def __init__(self, X: np.ndarray, y: np.ndarray, params: Params,
                 gh_dtype=jnp.float32, drop_odd_rows: bool = False,
                 freeze_scores: bool = False):
        super().__init__(X, y, params, gh_dtype=gh_dtype,
                         drop_odd_rows=drop_odd_rows,
                         freeze_scores=freeze_scores)
        self.label = jnp.asarray(np.where(self.y > 0, 1.0, -1.0)
                                 .astype(np.float32))
        self.iteration = 0
        self.bagged = params.bagging_fraction < 1.0 and params.bagging_freq > 0
        self.key = jax.random.PRNGKey(int(params.bagging_seed))
        self.bag = np.arange(self.R, dtype=np.int32)
        self.column_draws = np.random.RandomState(
            int(params.feature_fraction_seed))
        self.columns_a_tree = max(1, int(round(
            self.F * params.feature_fraction)))

    def bag_rows(self) -> np.ndarray:
        """The sorted row numbers of this iteration's bag."""
        prm = self.p
        if self.bagged and self.iteration % prm.bagging_freq == 0:
            in_bag = _bag_draw(self.key, self.iteration // prm.bagging_freq,
                               jnp.float32(prm.bagging_fraction), self.label)
            self.bag = np.flatnonzero(np.asarray(in_bag)).astype(np.int32)
        return self.bag

    def tree_columns(self) -> np.ndarray:
        """The sorted columns of the next tree: one draw of the stream."""
        if not 0.0 < self.p.feature_fraction < 1.0:
            return np.arange(self.F)
        return np.sort(self.column_draws.choice(
            self.F, self.columns_a_tree, replace=False))

    def step(self) -> np.ndarray:
        """One boosting step; returns the scores after it."""
        t0 = time.perf_counter()
        g, h = _gradients(jnp.asarray(self.score), self.label)
        g, h = (v.astype(self.gh_dtype).astype(jnp.float32) for v in (g, h))
        if self.drop_odd_rows:
            keep = jnp.asarray(np.arange(self.R) % 2 == 0)
            g, h = jnp.where(keep, g, 0.0), jnp.where(keep, h, 0.0)
        gh = jnp.stack([g, h], axis=1)
        cols = self.tree_columns()
        tree = gbdt_goss.Reference._grow_on(
            _SampledColumns(self, cols), gh,
            np.asarray(gh, dtype=np.float64), self.bag_rows())
        tree.feature = [int(cols[f]) for f in tree.feature]
        self.trees.append(tree)
        self.iteration += 1
        if not self.freeze_scores:
            leaf = tree.leaves(lambda f: self.bins_t[f])
            self.score += tree.value.astype(np.float32)[leaf]
        self.seconds["steps"].append(time.perf_counter() - t0)
        return self.score.copy()
