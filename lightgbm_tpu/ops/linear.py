"""Piecewise-linear leaves (``linear_tree``) on the device.

Equivalent of the reference's ``LinearTreeLearner::CalculateLinear``
(reference: src/treelearner/linear_tree_learner.cpp:173) and of its
``AddPredictionToScore``: once a tree's structure is grown, each leaf
``l`` gets a ridge fit over ``S_l``, the distinct numerical features split
on along its root path, from the rows of the leaf that have no NaN among
them,

    beta_l = -(sum h a a^T + lambda diag(1, ..., 1, 0))^-1 sum g a,
    a = [x_S, 1],

and every training row's score moves by its leaf's linear value. A leaf
with fewer than ``|S_l| + 1`` such rows, or whose solve is not finite,
keeps its constant; coefficients within ``kZeroThreshold`` of zero drop
out with their feature; shrinkage scales coefficients and constant. The
first tree of a model keeps constant leaves (``GBDT``: the reference's
``is_first_tree``).

The raw values stay on the device (``BinnedDataset.raw_device``), float32
as the reference keeps them; the sums are accumulated and solved in
float32 where the reference uses double. The host builds the leaf ->
path-feature table ``[L, D]`` from the tree it has just applied
(``D`` the power of two at or above the most features a branch has, 16 at
least); one program (``linear_fit``) then picks each row's ``D`` path
values out of its row, forms the per-leaf normal equations as
a product of the rows' leaf one-hot with their outer products, a tile of
rows at a time, solves them, and adds nothing to the host's waits: the
coefficients stay on the device with the tree (``Tree.attach_linear``)
until the model is read. The training rows' linear values and the
validation rows' (``linear_valid_output``) are ``ops/predict.py``'s
``linear_leaf_output``, the server's code.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..io.binning import kZeroThreshold
from ..models.tree import Tree, kCategoricalMask
from ..obs import compile as obs_compile
from ..utils import next_pow2
from .predict import (LinearLeaves, _linear_leaf_values, leaf_path_values,
                      linear_leaf_output)

# the fewest slots of the path-feature table: a leaf-wise tree of 255
# leaves on a wide table puts at most 16 distinct features on a branch at
# the benchmark's shape, and a table that grows only where a tree needs
# more compiles no program for the trees after the first
MIN_PATH_WIDTH = 16
# rows of one tile of the normal equations' sums: [T, L] one-hot times
# [T, (D + 1)^2 + D + 2] products, a few MB of each
SUM_TILE = 8192


def path_table(tree: Tree, leaves: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(feat, valid)``, both ``[leaves, D]``: each leaf's distinct
    numerical split features along its root path, ascending, as raw
    column numbers, and which of the ``D`` slots hold one. ``D`` is the
    power of two at or above the most a leaf has, and at least
    ``MIN_PATH_WIDTH``, so that the trees of a model share one compiled
    fit and one validation output."""
    paths = [()] * leaves
    stack = [(0, ())] if tree.num_leaves > 1 else []
    while stack:
        node, path = stack.pop()
        if node < 0:
            paths[~node] = tuple(sorted(set(path)))
            continue
        if not tree.decision_type[node] & kCategoricalMask:
            path = path + (int(tree.split_feature[node]),)
        stack.append((int(tree.left_child[node]), path))
        stack.append((int(tree.right_child[node]), path))
    width = max(MIN_PATH_WIDTH, next_pow2(max(len(p) for p in paths)))
    feat = np.zeros((leaves, width), dtype=np.int32)
    valid = np.zeros((leaves, width), dtype=bool)
    for leaf, cols in enumerate(paths):
        feat[leaf, :len(cols)] = cols
        valid[leaf, :len(cols)] = True
    return feat, valid


def _normal_equations(a, g, h, ok, leaf, leaves: int):
    """``(A [L, P, P], b [L, P], n [L])`` with ``P = a.shape[1]``: per
    leaf the sums of ``h a a^T``, ``g a`` and the count over the rows
    where ``ok``; the rows' leaf one-hot times their products, a tile of
    ``SUM_TILE`` rows at a time (a one-hot is exact in any precision,
    the products are summed in float32)."""
    n, p = a.shape
    tile = min(SUM_TILE, n)
    trips = -(-n // tile)
    pad = trips * tile - n
    w = ok.astype(jnp.float32)
    cols = jnp.concatenate([
        a, (g * w)[:, None], (h * w)[:, None], w[:, None]], axis=1)
    if pad:
        cols = jnp.pad(cols, ((0, pad), (0, 0)))
        leaf = jnp.pad(leaf, (0, pad), constant_values=-1)
    ids = jnp.arange(leaves, dtype=jnp.int32)

    def body(t, acc):
        c = jax.lax.dynamic_slice_in_dim(cols, t * tile, tile)
        lt = jax.lax.dynamic_slice_in_dim(leaf, t * tile, tile)
        at, gt, ht, wt = c[:, :p], c[:, p], c[:, p + 1], c[:, p + 2]
        outer = (at[:, :, None] * at[:, None, :]
                 * ht[:, None, None]).reshape(tile, p * p)
        rhs = jnp.concatenate([outer, at * gt[:, None], wt[:, None]],
                              axis=1)
        onehot = (lt[:, None] == ids[None, :]).astype(jnp.float32)
        return acc + jax.lax.dot_general(
            onehot, rhs, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST)

    acc = jax.lax.fori_loop(
        0, trips, body, jnp.zeros((leaves, p * p + p + 1), jnp.float32))
    return (acc[:, :p * p].reshape(leaves, p, p), acc[:, p * p:p * p + p],
            acc[:, -1])


def _solve(A, b, valid, lam):
    """``beta [L, D + 1]`` of ``(A + lam diag(valid, 0)) beta = -b``,
    the slots a leaf does not use held at 0 by an identity row."""
    leaves, width = valid.shape
    on = jnp.concatenate([valid, jnp.ones((leaves, 1), bool)], axis=1)
    eye = jnp.eye(width + 1, dtype=jnp.float32)
    reg = jnp.concatenate([jnp.where(valid, lam, jnp.float32(0.0)),
                           jnp.zeros((leaves, 1), jnp.float32)], axis=1)
    both = on[:, :, None] & on[:, None, :]
    A = jnp.where(both, A, jnp.float32(0.0)) \
        + eye[None] * jnp.where(on, reg, jnp.float32(1.0))[:, None, :]
    b = jnp.where(on, b, jnp.float32(0.0))
    return -jnp.linalg.solve(A, b[:, :, None])[:, :, 0]


def linear_fit(raw, grad, hess, bag, leaf_of_row, leaf_value, feat, valid,
               shrinkage, lam):
    """One tree's leaf fits and its training rows' linear values.

    ``raw`` [N, F] float32 on the device; ``grad``/``hess`` [N] the
    tree's gradients; ``bag`` [N] or None (rows out of it are not fit);
    ``leaf_of_row`` [N] the grower's final partition; ``leaf_value`` [L]
    the shrunk constant outputs; ``feat``/``valid`` [L, D] from
    ``path_table``. Returns ``(lin, delta, counts)``: ``lin`` =
    ``(const, coeff, keep, has)`` shrunk (``const`` the leaf value where
    ``has`` is false), ``delta`` [N] each row's output, ``counts`` int32
    ``[leaves fit, sum of their path features, rows they were fit on,
    sum of rows x features kept, sum of rows x features kept squared]``
    over the leaves fit."""
    leaves, width = valid.shape
    with jax.named_scope("obs_linear_fit"):
        xv = leaf_path_values(raw, leaf_of_row, feat)          # [N, D]
        v = valid[leaf_of_row]
        ok = ~jnp.any(jnp.isnan(xv) & v, axis=1)
        if bag is not None:
            ok = ok & (bag > 0)
        a = jnp.concatenate([jnp.where(v, xv, jnp.float32(0.0)),
                             jnp.ones((xv.shape[0], 1), jnp.float32)],
                            axis=1)
        # a NaN of a row left out must not reach the sums through a 0 weight
        a = jnp.where(ok[:, None], a, jnp.float32(0.0))
        A, b, n = _normal_equations(a, grad, hess, ok, leaf_of_row, leaves)
        beta = _solve(A, b, valid, lam)
        k = valid.sum(axis=1).astype(jnp.int32)
        has = ((n >= (k + 1).astype(jnp.float32)) & (k > 0)
               & jnp.all(jnp.isfinite(beta), axis=1))
        coef = beta[:, :width]
        keep = valid & has[:, None] & (jnp.abs(coef) > kZeroThreshold)
        coef = jnp.where(keep, coef * shrinkage, jnp.float32(0.0))
        const = jnp.where(has, beta[:, width] * shrinkage, leaf_value)
        rows = jnp.where(has, n, 0.0).astype(jnp.int32)
        kept = keep.sum(axis=1).astype(jnp.int32)
        counts = jnp.stack([
            has.sum(dtype=jnp.int32), jnp.where(has, k, 0).sum(),
            rows.sum(), (rows * kept).sum(), (rows * kept * kept).sum()])
    with jax.named_scope("obs_linear_out"):
        delta = linear_leaf_output(xv, leaf_of_row, leaf_value[leaf_of_row],
                                   const, coef, keep, has)
    return (const, coef, keep, has), delta, counts


def linear_valid_output(raw, leaf, leaf_value, const, coeff, feat, keep,
                        has):
    """[n] the linear output of one tree over rows whose leaves the
    validation walk found: ``_linear_leaf_values``, the server's code,
    over a forest of this one tree."""
    with jax.named_scope("obs_linear_out"):
        lin = LinearLeaves(const=const[None], coeff=coeff[None],
                           feat=feat[None], valid=keep[None],
                           has=has[None])
        return _linear_leaf_values(raw, leaf[None],
                                   leaf_value[leaf][None], lin)[0]


_fit = obs_compile.instrument_jit("linear.fit", linear_fit)
_valid_output = obs_compile.instrument_jit("linear.valid_output",
                                           linear_valid_output)


def fit_tree(raw, grad, hess, bag: Optional[jnp.ndarray], leaf_of_row,
             tree: Tree, feat: np.ndarray, valid: np.ndarray, leaves: int,
             shrinkage, lam):
    """``linear_fit`` of a tree whose shrunk leaf values are padded to
    ``leaves`` (one compiled program for every tree of a model):
    ``((const, coeff, feat, keep, has), delta, counts)``, the first
    what ``Tree.attach_linear`` takes."""
    lv = np.zeros(leaves, dtype=np.float32)
    lv[:tree.num_leaves] = tree.leaf_value[:tree.num_leaves]
    feat_dev = jnp.asarray(feat)
    (const, coeff, keep, has), delta, counts = _fit(
        raw, grad, hess, bag, leaf_of_row, jnp.asarray(lv), feat_dev,
        jnp.asarray(valid), shrinkage, lam)
    return (const, coeff, feat_dev, keep, has), delta, counts


def tree_output(raw, leaf, tree: Tree):
    """[n] a linear tree's output over rows whose leaf ids ``leaf`` the
    walk gave, from the coefficients it holds (on the device since its
    fit, or from the host's lists of a model that was read)."""
    const, coeff, feat, keep, has = tree.linear_device()
    lv = np.zeros(const.shape[0], dtype=np.float32)
    lv[:tree.num_leaves] = tree.leaf_value[:tree.num_leaves]
    return _valid_output(raw, leaf, jnp.asarray(lv), const, coeff, feat,
                         keep, has)
