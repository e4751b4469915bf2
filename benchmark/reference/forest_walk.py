"""The plain reference of scoring: a forest read from LightGBM model text
and walked row by row, tree by tree.

It parses the text itself (``Tree=`` blocks: ``split_feature``,
``threshold``, ``decision_type``, ``left_child``, ``right_child``,
``leaf_value``; a negative child ``c`` is leaf ``~c``), sends a row left
where its raw float32 value, widened to float64, is ``<=`` the node's
threshold, and sums the leaves' values in float64 in the trees' order. No
bins, no tables, no padding, no batching: numpy over the rows that have not
yet reached a leaf. Imports nothing of the program.

Covered: numerical splits of rows without NaN (``decision_type`` with the
categorical bit clear and missing type none, where a NaN would count as 0).
Not covered, and refused: categorical splits, missing types zero and NaN,
linear leaves, several trees per iteration.
"""
from __future__ import annotations

import re
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CATEGORICAL_BIT = 1
MISSING_TYPE_SHIFT = 2      # bits 2 and 3: 0 none, 1 zero, 2 NaN
THREADS = 8

_FIELD = re.compile(r"^(\w+)=(.*)$", re.M)


class Tree:
    def __init__(self, feature, threshold, left, right, leaf_value):
        self.feature = np.asarray(feature, dtype=np.int64)
        self.threshold = np.asarray(threshold, dtype=np.float64)
        self.left = np.asarray(left, dtype=np.int64)
        self.right = np.asarray(right, dtype=np.int64)
        self.leaf_value = np.asarray(leaf_value, dtype=np.float64)

    def walk(self, X: np.ndarray) -> tuple:
        """``(leaf of every row, nodes visited)``."""
        rows = X.shape[0]
        leaf = np.zeros(rows, dtype=np.int64)
        if not len(self.feature):
            return leaf, 0
        at = np.arange(rows)            # rows still at an inner node
        node = np.zeros(rows, dtype=np.int64)
        hops = 0
        while len(at):
            hops += len(at)
            value = X[at, self.feature[node]].astype(np.float64)
            value[np.isnan(value)] = 0.0        # missing type none
            nxt = np.where(value <= self.threshold[node], self.left[node],
                           self.right[node])
            done = nxt < 0
            leaf[at[done]] = ~nxt[done]
            at, node = at[~done], nxt[~done]
        return leaf, hops


class Forest:
    def __init__(self, trees: list, features: int):
        self.tree_list = trees
        self.features = features
        self.hops = 0

    @property
    def trees(self) -> int:
        return len(self.tree_list)

    @property
    def leaves(self) -> int:
        return max(len(t.leaf_value) for t in self.tree_list)

    @classmethod
    def from_model_text(cls, text: str, leaf_dtype=None,
                        drop_last_trees: int = 0) -> "Forest":
        """``leaf_dtype``: the control, every leaf value rounded to that
        type; ``drop_last_trees``: the fault, that many trees left out."""
        head, *blocks = text.split("\nTree=")
        header = dict(_FIELD.findall(head))
        if int(header.get("num_tree_per_iteration", "1")) != 1:
            raise ValueError("several trees per iteration are not covered")
        trees = []
        for block in blocks:
            kv = dict(_FIELD.findall(block.split("\n\n")[0]))
            if int(kv.get("is_linear", "0")) or int(kv.get("num_cat", "0")):
                raise ValueError("linear leaves and categorical splits are "
                                 "not covered")
            values = np.array(kv["leaf_value"].split(), dtype=np.float64)
            if leaf_dtype is not None:
                values = values.astype(leaf_dtype).astype(np.float64)
            if int(kv["num_leaves"]) < 2:
                trees.append(Tree([], [], [], [], values))
                continue
            kinds = np.array(kv["decision_type"].split(), dtype=np.int64)
            if np.any(kinds & CATEGORICAL_BIT) or np.any(
                    (kinds >> MISSING_TYPE_SHIFT) & 3):
                raise ValueError("decision types %s: only numerical splits "
                                 "with missing type none are covered"
                                 % sorted(set(kinds.tolist())))
            trees.append(Tree(kv["split_feature"].split(),
                              kv["threshold"].split(),
                              kv["left_child"].split(),
                              kv["right_child"].split(), values))
        if drop_last_trees:
            trees = trees[:-drop_last_trees]
        return cls(trees, int(header["max_feature_idx"]) + 1)

    def predict_raw(self, X: np.ndarray) -> np.ndarray:
        """Raw scores of raw rows, float64; ``hops`` is then the number of
        nodes these rows visited."""
        X = np.asarray(X)
        if X.ndim != 2 or X.shape[1] != self.features:
            raise ValueError("rows of shape %s, the model has %d features"
                             % (X.shape, self.features))
        with ThreadPoolExecutor(max_workers=THREADS) as pool:
            walked = list(pool.map(lambda t: t.walk(X), self.tree_list))
        out = np.zeros(X.shape[0], dtype=np.float64)
        for tree, (leaf, _) in zip(self.tree_list, walked):
            out += tree.leaf_value[leaf]
        self.hops = int(sum(h for _, h in walked))
        return out
