"""Plain reference of LightGBM's quantized-gradient training
(``use_quantized_grad``, ``num_grad_quant_bins``; Shi et al., "Quantized
Training of Gradient Boosting Decision Trees", NeurIPS 2022) for a binary
objective.

Leaf-wise boosting as ``gbdt.py`` states it, with the one difference the
mode makes. Per boosting step, with ``g``, ``h`` the logistic loss's
float32 gradient and hessian in the form LightGBM's binary objective gives
them (``r = -l / (1 + exp(l * score))`` with the label ``l`` as -1 or +1,
``g = r``, ``h = |r| * (1 - |r|)``), computed on the device: the rounding
below turns a last-digit difference in ``g`` into a whole level of a row,
and numpy's ``exp`` and the chip's differ in the sixth digit.
``B = num_grad_quant_bins`` and ``u`` uniform on [0, 1):

- scales ``s_g = max|g| / (B/2)``, ``s_h = max|h| / B``;
- ``q_g = floor(g / s_g + u_g)`` in ``[-B/2, B/2]``, ``q_h = floor(h / s_h +
  u_h)`` in ``[0, B]``, stored int8; ``u = 0.5`` where
  ``stochastic_rounding`` is false. The draw is
  ``jax.random.uniform(fold_in(PRNGKey(seed), tree number), (rows, 2))``,
  column 0 for the gradient, trees numbered from 1;
- a leaf's histogram is the exact int32 sum of ``(q_g, q_h)`` per (feature,
  bin), a one-hot product in blocks of rows; the sibling's is the parent's
  less the smaller child's, in integers;
- the split scan dequantizes a leaf's histogram once (``s_g * sum q_g``,
  ``s_h * sum q_h`` per bin) and applies ``gbdt.py``'s split rule to it:
  gain ``GL^2/(HL+l2) + GR^2/(HR+l2) - G^2/(H+l2)`` under the minimum
  hessian sum, the leaf's totals ``s_g * sum q_g`` and ``s_h * sum q_h``
  of its rows' integers; a leaf adds ``-learning_rate * s_g * sum q_g /
  (s_h * sum q_h + l2)`` to its rows (``quant_train_renew_leaf`` false).

What is not integer is float32 at ``highest``. Binning, the held-out walk,
the loss, the score boosting starts from and the trees' form are
``gbdt.py``'s and ``binning.py``'s; it imports nothing of ``lightgbm_tpu``
and uses jax's public ``random`` only.

The controls, as keywords of ``Reference`` beside the three every reference
of kind ``train`` takes: ``halve_levels`` (``B/2`` in place of ``B``: the
nearest precision below the configuration's, which ``correct`` has to
refuse; a ``gh_dtype`` other than float32, which is how the runner asks for
its lower-precision control, means the same), ``symmetric_qmax`` (both
channels to ``+-qmax``: the program's ``quant_grad_bits`` scheme, another
model), ``nearest`` (``u = 0.5``).
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import gbdt
from .gbdt import HIST_ROWS, NUM_BINS, RefTree, init_score, logloss

TINY = 1e-30            # a channel that is zero everywhere

loss = logloss          # the loss of this reference's objective


@dataclasses.dataclass
class Params(gbdt.Params):
    num_grad_quant_bins: int = 4
    stochastic_rounding: bool = True
    seed: int = 0


# ----------------------------------------------------------------- device
@jax.jit
def _gradients(score, label):
    """The logistic loss's gradient and hessian at raw scores, float32,
    as LightGBM's binary objective states them; ``label`` is -1 or +1."""
    r = -label / (1.0 + jnp.exp(label * score))
    return r, jnp.abs(r) * (1.0 - jnp.abs(r))


@functools.partial(jax.jit, static_argnames=("levels", "stochastic"))
def _discretize(g, h, key, levels, stochastic):
    """``(int8 [rows, 2], float32 [2] scales)`` for ``levels`` = (gradient
    levels a side, hessian levels, the hessian's floor)."""
    g_levels, h_levels, h_floor = (jnp.float32(v) for v in levels)
    s_g = jnp.maximum(jnp.max(jnp.abs(g)), TINY) / g_levels
    s_h = jnp.maximum(jnp.max(jnp.abs(h)), TINY) / h_levels
    u = (jax.random.uniform(key, (g.shape[0], 2)) if stochastic
         else jnp.full((g.shape[0], 2), 0.5, jnp.float32))
    q_g = jnp.clip(jnp.floor(g / s_g + u[:, 0]), -g_levels, g_levels)
    q_h = jnp.clip(jnp.floor(h / s_h + u[:, 1]), h_floor, h_levels)
    return (jnp.stack([q_g, q_h], axis=1).astype(jnp.int8),
            jnp.stack([s_g, s_h]))


def _leaf_histogram(bins, q, idx, n):
    """``int32 [F, NUM_BINS, 2]`` sums of ``q`` over the rows ``idx[:n]``."""
    iota = jnp.arange(NUM_BINS, dtype=jnp.int32)
    lane = jnp.arange(HIST_ROWS, dtype=jnp.int32)

    def body(i, acc):
        rows = jax.lax.dynamic_slice(idx, (i * HIST_ROWS,), (HIST_ROWS,))
        live = (i * HIST_ROWS + lane) < n
        w = jnp.where(live[:, None], q[rows], 0).astype(jnp.int8)
        onehot = (bins[rows].astype(jnp.int32)[:, :, None]
                  == iota).astype(jnp.int8)
        return acc + jnp.einsum("rfb,rc->fbc", onehot, w,
                                preferred_element_type=jnp.int32)

    zero = jnp.zeros((bins.shape[1], NUM_BINS, 2), dtype=jnp.int32)
    return jax.lax.fori_loop(0, (n + HIST_ROWS - 1) // HIST_ROWS, body, zero)


def _best_split(hist, sums, scales, min_hess, l2):
    """Best (gain, feature, bin, ...) of a leaf with integer histogram
    ``hist`` and integer totals ``sums``, dequantized once: ``gbdt.py``'s
    rule, rows whose bin is at most ``bin`` go left."""
    total = sums.astype(jnp.float32) * scales
    return gbdt._best_split(hist.astype(jnp.float32) * scales, total[0],
                            total[1], min_hess, l2)


@jax.jit
def _root_step(bins, q, idx, n, sums, scales, min_hess, l2):
    hist = _leaf_histogram(bins, q, idx, n)
    return hist, _best_split(hist, sums, scales, min_hess, l2)


@jax.jit
def _split_step(bins, q, idx, n, parent, small_sums, large_sums, scales,
                min_hess, l2):
    small = _leaf_histogram(bins, q, idx, n)
    large = parent - small
    return (small, large,
            _best_split(small, small_sums, scales, min_hess, l2),
            _best_split(large, large_sums, scales, min_hess, l2))


# ------------------------------------------------------------------- host
class Reference(gbdt.Reference):
    """``gbdt.Reference``'s binned data, scores and held-out walk, with
    ``step()`` growing each tree from the discretized rows."""

    def __init__(self, X: np.ndarray, y: np.ndarray, params: Params,
                 gh_dtype=jnp.float32, drop_odd_rows: bool = False,
                 freeze_scores: bool = False, halve_levels: bool = False,
                 symmetric_qmax: int = 0, nearest: bool = False):
        super().__init__(X, y, params, drop_odd_rows=drop_odd_rows,
                         freeze_scores=freeze_scores)
        self.label = jnp.asarray(np.where(self.y > 0, 1.0, -1.0)
                                 .astype(np.float32))
        bins = int(params.num_grad_quant_bins)
        if halve_levels or jnp.dtype(gh_dtype) != jnp.float32:
            bins //= 2                          # control: precision below
        # gradient levels a side, hessian levels, the hessian's floor
        self.levels = ((symmetric_qmax, symmetric_qmax, -symmetric_qmax)
                       if symmetric_qmax else (bins // 2, bins, 0))
        self.stochastic = bool(params.stochastic_rounding) and not nearest
        self.key = jax.random.PRNGKey(int(params.seed))

    def discretize(self, g: np.ndarray, h: np.ndarray):
        """The integer rows and the two scales of tree ``len(trees) + 1``."""
        key = jax.random.fold_in(self.key, len(self.trees) + 1)
        return _discretize(jnp.asarray(g), jnp.asarray(h), key, self.levels,
                           self.stochastic)

    def step(self) -> np.ndarray:
        """One boosting step; returns the scores after it."""
        t0 = time.perf_counter()
        g, h = _gradients(jnp.asarray(self.score), self.label)
        if self.drop_odd_rows:
            keep = jnp.asarray(np.arange(self.R) % 2 == 0)
            g, h = jnp.where(keep, g, 0.0), jnp.where(keep, h, 0.0)
        q, scales = self.discretize(g, h)
        tree, leaf_rows = self._grow(q, np.asarray(q, dtype=np.int64),
                                     scales)
        self.trees.append(tree)
        if not self.freeze_scores:
            for leaf, rows in leaf_rows.items():
                self.score[rows] += np.float32(tree.value[leaf])
        self.seconds["steps"].append(time.perf_counter() - t0)
        return self.score.copy()

    def _grow(self, q, q_host, scales):
        prm = self.p
        min_hess = jnp.float32(prm.min_sum_hessian_in_leaf)
        l2 = jnp.float32(prm.lambda_l2)
        as_i32 = lambda v: jnp.asarray(v, dtype=jnp.int32)
        order = np.arange(self.R, dtype=np.int32)
        seg = {0: (0, self.R)}
        sums = {0: q_host.sum(axis=0)}              # exact integers
        idx, n = self._idx(order)
        hist, best = _root_step(self.bins, q, idx, n, as_i32(sums[0]),
                                scales, min_hess, l2)
        hists = {0: hist}
        cand = {0: np.asarray(best, dtype=np.float64)}
        tree = RefTree([], [], [], None, [self.R])
        new_leaf = 1
        while new_leaf < prm.num_leaves:
            leaf = max(cand, key=lambda k: cand[k][0])
            gain, f, b = cand[leaf][:3]
            if not gain > 0.0:
                break
            f, b = int(f), int(b)
            lo, hi = seg[leaf]
            rows = order[lo:hi]
            left = self.bins_t[f, rows] <= b
            rows_l, rows_r = rows[left], rows[~left]
            if len(rows_l) < prm.min_data_in_leaf or \
                    len(rows_r) < prm.min_data_in_leaf:
                cand[leaf][0] = -np.inf
                continue
            order[lo:hi] = np.concatenate([rows_l, rows_r])
            mid = lo + len(rows_l)
            seg[leaf], seg[new_leaf] = (lo, mid), (mid, hi)
            parent = sums[leaf]
            sums[leaf] = q_host[rows_l].sum(axis=0)
            sums[new_leaf] = parent - sums[leaf]
            small, large = ((leaf, new_leaf) if len(rows_l) <= len(rows_r)
                            else (new_leaf, leaf))
            s_lo, s_hi = seg[small]
            idx, n = self._idx(order[s_lo:s_hi])
            h_small, h_large, b_small, b_large = _split_step(
                self.bins, q, idx, n, hists[leaf], as_i32(sums[small]),
                as_i32(sums[large]), scales, min_hess, l2)
            hists[small], hists[large] = h_small, h_large
            cand[small] = np.asarray(b_small, dtype=np.float64)
            cand[large] = np.asarray(b_large, dtype=np.float64)
            tree.leaf.append(leaf)
            tree.feature.append(f)
            tree.thr_bin.append(b)
            tree.smaller_rows.append(s_hi - s_lo)
            new_leaf += 1
        n_leaves = len(tree.leaf) + 1
        s_g, s_h = (np.float32(v) for v in np.asarray(scales))
        tree.value = np.array(
            [-prm.learning_rate * float(s_g * np.float32(sums[k][0]))
             / (float(s_h * np.float32(sums[k][1])) + prm.lambda_l2)
             for k in range(n_leaves)], dtype=np.float64)
        return tree, {k: order[seg[k][0]:seg[k][1]] for k in range(n_leaves)}
