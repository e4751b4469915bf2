"""Piecewise-linear trees: prediction on the host.

The leaves are fit on the device while the model trains
(``ops/linear.py``, the reference's ``LinearTreeLearner::CalculateLinear``,
src/treelearner/linear_tree_learner.cpp:173). A leaf's output is its
``leaf_const`` plus its coefficients times the row's values of its
``leaf_features``, or its ``leaf_value`` where one of those values is NaN
(reference: ``Tree::Predict``'s linear branch, include/LightGBM/tree.h).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from .tree import Tree


def linear_predict(tree: Tree, X: np.ndarray,
                   leaf_idx: Optional[np.ndarray] = None) -> np.ndarray:
    """Each row's output of a linear tree over its raw features, in
    float64."""
    X = np.asarray(X, dtype=np.float64)
    if leaf_idx is None:
        leaf_idx = tree.predict_leaf_index(X)
    out = tree.leaf_const[leaf_idx].astype(np.float64)
    for leaf in range(tree.num_leaves):
        feats = tree.leaf_features[leaf]
        if not feats:
            continue
        rows = leaf_idx == leaf
        if not rows.any():
            continue
        Xl = X[np.ix_(rows, feats)]
        nan = np.isnan(Xl).any(axis=1)
        vals = tree.leaf_const[leaf] + Xl @ np.asarray(tree.leaf_coeff[leaf])
        out[rows] = np.where(nan, tree.leaf_value[leaf], vals)
    return out
