"""Plain reference of gradient-based one-side sampling (GOSS; Ke et al.,
"LightGBM: A Highly Efficient Gradient Boosting Decision Tree", NeurIPS
2017, section 3 and Algorithm 2; ``data_sample_strategy=goss``,
``top_rate``, ``other_rate`` of LightGBM's ``docs/Parameters.rst``) for a
binary objective.

Leaf-wise boosting as ``gbdt.py`` states it, with the one difference the
mode makes. Per boosting step, with ``g``, ``h`` the logistic loss's float32
gradient and hessian in the form LightGBM's binary objective gives them,
computed on the device (``gbdt_quant.py`` says why), ``n`` the rows,
``top_k = int(n * top_rate)`` and ``other_k = int(n * other_rate)``:

- while fewer than ``int(1 / learning_rate)`` iterations are done, nothing
  is sampled: the step is ``gbdt.py``'s;
- ``w = |g * h|``; the threshold is the ``top_k``-th largest ``w``, from a
  full sort on the host; the rows with ``w`` at or above it are the top set
  (ties at the threshold all count as top rows);
- each other row is drawn with probability ``other_k / (n - top_k)`` by
  ``u < p``, ``u = jax.random.uniform(fold_in(PRNGKey(bagging_seed),
  iteration), (n,))``, iterations counted from 0; a drawn row's ``g`` and
  ``h`` are multiplied by ``(n - top_k) / other_k``;
- the tree is grown over the in-bag rows (the top set and the drawn rows)
  and no others: the row permutation holds only them, so do the histograms,
  the leaves' sums and the leaves' values;
- every row's score, in the bag or not, moves by the value of the leaf the
  row walks to.

Float32 at ``highest``. Binning, the split rule, the held-out walk, the loss
and the trees' form are ``gbdt.py``'s and ``binning.py``'s; it imports
nothing of ``lightgbm_tpu`` and uses jax's public ``random`` only. A run can
start from an earlier model's scores (``start_scores``,
``start_iteration``): the runner of kind ``train_warm`` hands it the
program's after the plain iterations, which another cell checks.

The controls, as keywords of ``Reference`` beside the three every reference
of kind ``train`` takes: ``sampling`` (``"none"``: every row, a program
that ignores the mode; ``"uniform"``: every row drawn with probability
``(top_k + other_k) / n`` and none amplified, bagging under GOSS's name),
``amplify`` (false: the factor left out). Another ``top_rate`` is another
``Params``.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import gbdt
from .gbdt import (RefTree, _root_step, _split_step,  # noqa: F401
                   init_score, logloss)
from .gbdt_quant import _gradients

loss = logloss          # the loss of this reference's objective


@dataclasses.dataclass
class Params(gbdt.Params):
    data_sample_strategy: str = "goss"
    top_rate: float = 0.2
    other_rate: float = 0.1
    bagging_seed: int = 3


# ----------------------------------------------------------------- device
@jax.jit
def _weights(g, h):
    return jnp.abs(g * h)


@functools.partial(jax.jit, static_argnames=("amplify",))
def _one_side_sample(g, h, w, threshold, key, prob, factor, amplify):
    """``(g, h, in-bag)`` of one sampled step: the rows at or above the
    threshold as they are, the drawn ones of the rest amplified."""
    top = w >= threshold
    drawn = ~top & (jax.random.uniform(key, w.shape) < prob)
    scale = jnp.where(drawn, factor, 1.0) if amplify else 1.0
    return g * scale, h * scale, top | drawn


@jax.jit
def _uniform_sample(key, w, prob):
    return jax.random.uniform(key, w.shape) < prob


# ------------------------------------------------------------------- host
class Reference(gbdt.Reference):
    """``gbdt.Reference``'s binned data and held-out walk, with ``step()``
    growing each tree from the sampled rows alone."""

    def __init__(self, X: np.ndarray, y: np.ndarray, params: Params,
                 gh_dtype=jnp.float32, drop_odd_rows: bool = False,
                 freeze_scores: bool = False, start_scores=None,
                 start_iteration: int = 0, sampling: str = "goss",
                 amplify: bool = True):
        if params.data_sample_strategy != "goss":
            raise ValueError("gbdt_goss states data_sample_strategy=goss, "
                             "not %r" % params.data_sample_strategy)
        super().__init__(X, y, params, gh_dtype=gh_dtype,
                         drop_odd_rows=drop_odd_rows,
                         freeze_scores=freeze_scores)
        self.label = jnp.asarray(np.where(self.y > 0, 1.0, -1.0)
                                 .astype(np.float32))
        if start_scores is not None:
            self.score = np.array(start_scores, dtype=np.float32)
        self.iteration = int(start_iteration)
        self.sampling, self.amplify = sampling, amplify
        self.top_k = int(self.R * params.top_rate)
        self.other_k = int(self.R * params.other_rate)
        self.warmup = int(1.0 / params.learning_rate)
        self.key = jax.random.PRNGKey(int(params.bagging_seed))

    def sample(self, g, h):
        """``(g, h, in-bag rows)`` of this iteration: amplified gradients
        and the sorted row numbers the tree is grown from."""
        everything = np.arange(self.R, dtype=np.int32)
        if self.iteration < self.warmup or self.sampling == "none":
            return g, h, everything
        key = jax.random.fold_in(self.key, self.iteration)
        w = _weights(g, h)
        if self.sampling == "uniform":
            bag = _uniform_sample(key, w,
                                  (self.top_k + self.other_k) / self.R)
        else:
            rest = self.R - self.top_k
            threshold = np.sort(np.asarray(w))[rest]
            g, h, bag = _one_side_sample(
                g, h, w, threshold, key, jnp.float32(self.other_k / rest),
                jnp.float32(rest / self.other_k), self.amplify)
        return g, h, everything[np.asarray(bag)]

    def step(self) -> np.ndarray:
        """One boosting step; returns the scores after it."""
        t0 = time.perf_counter()
        g, h = _gradients(jnp.asarray(self.score), self.label)
        g, h = (v.astype(self.gh_dtype).astype(jnp.float32) for v in (g, h))
        if self.drop_odd_rows:
            keep = jnp.asarray(np.arange(self.R) % 2 == 0)
            g, h = jnp.where(keep, g, 0.0), jnp.where(keep, h, 0.0)
        g, h, rows = self.sample(g, h)
        gh = jnp.stack([g, h], axis=1)
        tree = self._grow_on(gh, np.asarray(gh, dtype=np.float64), rows)
        self.trees.append(tree)
        self.iteration += 1
        if not self.freeze_scores:
            leaf = tree.leaves(lambda f: self.bins_t[f])
            self.score += tree.value.astype(np.float32)[leaf]
        self.seconds["steps"].append(time.perf_counter() - t0)
        return self.score.copy()

    def _grow_on(self, gh, gh_host, rows) -> RefTree:
        """``gbdt.Reference._grow`` over the rows ``rows`` and no others."""
        prm = self.p
        min_hess = jnp.float32(prm.min_sum_hessian_in_leaf)
        l2 = jnp.float32(prm.lambda_l2)
        order = np.array(rows, dtype=np.int32)
        seg = {0: (0, len(order))}
        sums = {0: gh_host[order].sum(axis=0)}
        idx, n = self._idx(order)
        hist, best = _root_step(self.bins, gh, idx, n,
                                jnp.asarray(sums[0], dtype=jnp.float32),
                                min_hess, l2)
        hists = {0: hist}
        cand = {0: np.asarray(best, dtype=np.float64)}
        tree = RefTree([], [], [], None, [len(order)])
        new_leaf = 1
        while new_leaf < prm.num_leaves:
            leaf = max(cand, key=lambda k: cand[k][0])
            gain, f, b, gl, hl = cand[leaf]
            if not gain > 0.0:
                break
            f, b = int(f), int(b)
            lo, hi = seg[leaf]
            members = order[lo:hi]
            left = self.bins_t[f, members] <= b
            rows_l, rows_r = members[left], members[~left]
            if len(rows_l) < prm.min_data_in_leaf or \
                    len(rows_r) < prm.min_data_in_leaf:
                cand[leaf][0] = -np.inf
                continue
            order[lo:hi] = np.concatenate([rows_l, rows_r])
            mid = lo + len(rows_l)
            seg[leaf], seg[new_leaf] = (lo, mid), (mid, hi)
            parent = sums[leaf]
            sums[leaf] = np.array([gl, hl])
            sums[new_leaf] = parent - sums[leaf]
            small, large = ((leaf, new_leaf) if len(rows_l) <= len(rows_r)
                            else (new_leaf, leaf))
            s_lo, s_hi = seg[small]
            idx, n = self._idx(order[s_lo:s_hi])
            h_small, h_large, b_small, b_large = _split_step(
                self.bins, gh, idx, n, hists[leaf],
                jnp.asarray(sums[small], dtype=jnp.float32),
                jnp.asarray(sums[large], dtype=jnp.float32), min_hess, l2)
            hists[small], hists[large] = h_small, h_large
            cand[small] = np.asarray(b_small, dtype=np.float64)
            cand[large] = np.asarray(b_large, dtype=np.float64)
            tree.leaf.append(leaf)
            tree.feature.append(f)
            tree.thr_bin.append(b)
            tree.smaller_rows.append(s_hi - s_lo)
            new_leaf += 1
        tree.value = np.array(
            [-prm.learning_rate * sums[k][0] / (sums[k][1] + prm.lambda_l2)
             for k in range(len(tree.leaf) + 1)], dtype=np.float64)
        return tree

    def predict_raw(self, X: np.ndarray, start=None) -> np.ndarray:
        """Raw scores of unseen float32 rows through every tree grown,
        added to ``start``, the rows' scores under the earlier model the run
        started from (to the score boosting starts from where None)."""
        bins_t = np.ascontiguousarray(np.asarray(self.bin_rows(X)).T)
        out = (np.full(X.shape[0], self.init, dtype=np.float64)
               if start is None else np.array(start, dtype=np.float64))
        for tree in self.trees:
            out += tree.value[tree.leaves(lambda f: bins_t[f])]
        return out
