"""A forest of a trained model's shape, written as LightGBM model text.

Training 500 trees of 255 leaves costs 500 boosting iterations, so a scoring
cell synthesises its model. The law, whose numbers are the mix's ``model``:

- structure, from ``forest_seed`` alone: every tree starts as one leaf that
  holds all rows; ``leaves - 1`` times the leaf with the largest simulated
  share of the rows is split, its left child taking a share drawn uniformly
  from ``left_share``; the left child keeps the leaf's number and the right
  child gets the next one, as LightGBM numbers them. So depths are those of
  best-first growth, not of a balanced tree, and every ``--seed`` walks the
  same trees' shape: the program's lockstep walk takes the same number of
  hops (tables that differ moved a training iteration by 3%, PR 26);
- from ``--seed``: the split feature of every node (every feature the same
  number of times, to within one, in an order the seed draws), its threshold
  (per feature drawn without replacement from the standard-normal quantiles
  ``k / (threshold_grid + 1)``, so that a feature's distinct thresholds are
  as many as its nodes, whatever the seed) and the leaf values (normal times
  ``leaf_scale``).

No node is categorical and none has a missing type: ``decision_type`` 2
(numerical, missing goes left, which no NaN-free row exercises).
"""
from __future__ import annotations

import heapq
from statistics import NormalDist

import numpy as np

DECISION_TYPE = 2       # numerical, default left, missing type none


def _structure(gen, leaves: int, left_share) -> dict:
    """Child pointers and simulated row shares of one best-first tree."""
    lo, hi = left_share
    left = np.zeros(leaves - 1, dtype=np.int64)
    right = np.zeros(leaves - 1, dtype=np.int64)
    internal_share = np.zeros(leaves - 1)
    leaf_share = np.zeros(leaves)
    leaf_share[0] = 1.0
    parent = {0: None}          # leaf -> (node, is_left) that points at it
    heap = [(-1.0, 0)]
    for node in range(leaves - 1):
        share, leaf = heapq.heappop(heap)
        share = -share
        q = gen.uniform(lo, hi)
        new_leaf = node + 1
        if parent[leaf] is not None:
            up, is_left = parent[leaf]
            (left if is_left else right)[up] = node
        left[node], right[node] = ~leaf, ~new_leaf
        parent[leaf], parent[new_leaf] = (node, True), (node, False)
        internal_share[node] = share
        leaf_share[leaf], leaf_share[new_leaf] = share * q, share * (1 - q)
        heapq.heappush(heap, (-share * q, leaf))
        heapq.heappush(heap, (-share * (1 - q), new_leaf))
    return {"left": left, "right": right, "internal_share": internal_share,
            "leaf_share": leaf_share}


def make_forest(features: int, rows: int, seed: int, model: dict) -> list:
    """The trees as dicts of arrays: ``split_feature``, ``threshold``,
    ``left``, ``right``, ``leaf_value``, ``leaf_count``,
    ``internal_count``."""
    trees, leaves = int(model["trees"]), int(model["leaves"])
    grid = int(model["threshold_grid"])
    nodes = trees * (leaves - 1)
    structure_gen = np.random.Generator(np.random.Philox(
        np.random.SeedSequence([int(model["forest_seed"]), trees, leaves])))
    gen = np.random.Generator(np.random.Philox(
        np.random.SeedSequence([int(seed), trees, leaves, features])))
    quantiles = np.array([NormalDist().inv_cdf(k / (grid + 1.0))
                          for k in range(1, grid + 1)])
    # every feature equally often, to within one
    feature = np.resize(gen.permutation(features), nodes)
    threshold = np.empty(nodes)
    for f in range(features):
        at = np.flatnonzero(feature == f)
        if len(at) > grid:
            raise ValueError("%d nodes on feature %d, the grid has %d "
                             "thresholds" % (len(at), f, grid))
        threshold[at] = quantiles[gen.permutation(grid)[:len(at)]]
    order = gen.permutation(nodes)
    feature, threshold = feature[order], threshold[order]
    leaf_value = gen.standard_normal(trees * leaves) * float(
        model["leaf_scale"])
    out = []
    for t in range(trees):
        s = _structure(structure_gen, leaves, model["left_share"])
        a, b = t * (leaves - 1), (t + 1) * (leaves - 1)
        out.append({
            "split_feature": feature[a:b], "threshold": threshold[a:b],
            "left": s["left"], "right": s["right"],
            "leaf_value": leaf_value[t * leaves:(t + 1) * leaves],
            "leaf_count": np.maximum(
                np.rint(s["leaf_share"] * rows), 1).astype(np.int64),
            "internal_count": np.maximum(
                np.rint(s["internal_share"] * rows), 1).astype(np.int64)})
    return out


def _row(key: str, values, fmt=repr) -> str:
    return "%s=%s" % (key, " ".join(fmt(v) for v in values))


def _tree_text(tree: dict, shrinkage: float) -> str:
    n = len(tree["leaf_value"])
    return "\n".join([
        "num_leaves=%d" % n,
        "num_cat=0",
        _row("split_feature", tree["split_feature"].tolist()),
        _row("threshold", tree["threshold"].tolist()),
        _row("decision_type", [DECISION_TYPE] * (n - 1)),
        _row("left_child", tree["left"].tolist()),
        _row("right_child", tree["right"].tolist()),
        _row("leaf_value", tree["leaf_value"].tolist()),
        _row("leaf_count", tree["leaf_count"].tolist()),
        _row("internal_count", tree["internal_count"].tolist()),
        "is_linear=0",
        "shrinkage=%r" % shrinkage,
        "", ""])


def make_model_text(features: int, rows: int, seed: int, model: dict) -> str:
    """LightGBM model text (v3) of the forest ``make_forest`` gives, with a
    binary objective, as ``Booster.model_to_string`` would write it."""
    blocks = ["Tree=%d\n%s" % (i, _tree_text(t, float(model["shrinkage"])))
              for i, t in enumerate(make_forest(features, rows, seed, model))]
    header = "\n".join([
        "tree",
        "version=v3",
        "num_class=1",
        "num_tree_per_iteration=1",
        "label_index=0",
        "max_feature_idx=%d" % (features - 1),
        "objective=binary sigmoid:1",
        _row("feature_names", ("Column_%d" % f for f in range(features)),
             str),
        _row("feature_infos", ["[-8:8]"] * features, str),
        _row("tree_sizes", (len(b) for b in blocks)),
        "", ""])
    return header + "".join(blocks) + "end of trees\n"
