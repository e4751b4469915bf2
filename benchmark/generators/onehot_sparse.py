"""A one-hot encoded insurance table, as scipy CSR: a few dense numerical
columns beside the indicator columns of categorical columns, the input a
scikit-learn pipeline with ``OneHotEncoder`` hands LightGBM.

The mix's ``data`` gives the shape: ``dense`` numerical columns first, then
for each entry of ``categoricals`` (``[name, levels]``, in order) one
indicator column per level. Every row holds exactly one level of every
categorical column, so a row has ``dense + len(categoricals)`` non-zeros.

- The dense columns are drawn as ``harness/traffic.py`` draws its columns:
  standard normal, every ``heavy_tail_every``-th ``|x| ** heavy_tail_power``.
- A categorical column's levels are drawn with Zipf popularity, level ``k``
  (from 0) in proportion to ``1 / (k + 1) ** zipf``, so most levels are rare.
- ``nested`` lists ``[child, parent]`` pairs: the child is drawn, and the
  parent's level is the child's level modulo the parent's level count, so
  that every child level has one parent (a submodel one model, a model one
  make), and the parent's popularity is the sum of its children's.
- The label is the sign, about its median, of a linear form over the dense
  columns, an effect per level of the columns in ``effects``, one
  ``sin(a) * b`` interaction of two dense columns and normal noise, so that
  trees have something to learn and both classes are about as frequent.

At a width under the layout's (the benchmark's tiny CPU runs of every
cell) the table keeps its first ``features`` columns: the dense ones and
the indicators of the first categorical columns, the last of them cut to
its first levels (a row whose level was cut holds none of that column).

The table's values come from the mix's ``table_seed`` alone: the rows' order
decides which rows the bins and the bundles are found from, so another order
grows other trees, and every ``--seed`` feeds the same table (the seeds'
runs then spread by the machine alone). Imports nothing of the program.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def layout(data: dict) -> tuple:
    """``(dense, [(name, levels, first column)], total columns)``."""
    dense = int(data["dense"])
    cats, col = [], dense
    for name, levels in data["categoricals"]:
        cats.append((str(name), int(levels), col))
        col += int(levels)
    return dense, cats, col


def _zipf_draw(gen, levels: int, rows: int, power: float) -> np.ndarray:
    p = 1.0 / np.arange(1, levels + 1, dtype=np.float64) ** power
    cum = np.cumsum(p / p.sum())
    cum[-1] = 1.0
    return np.searchsorted(cum, gen.random(rows), side="right").astype(
        np.int32)


def make_table(rows: int, features: int, seed: int, data: dict):
    """``(X float32 CSR [rows, features], y float32 [rows], {})``: the table
    of ``data["table_seed"]``; ``seed`` moves nothing."""
    dense, cats, total = layout(data)
    if features > total:
        raise ValueError("the mix lays out %d columns, the configuration "
                         "has %d" % (total, features))
    root = np.random.SeedSequence([int(data["table_seed"]), rows, total])
    dense_seq, cat_seq, label_seq = root.spawn(3)
    gen = np.random.Generator(np.random.Philox(dense_seq))
    values = gen.standard_normal((rows, dense), dtype=np.float32)
    every = int(data["heavy_tail_every"])
    values[:, ::every] = np.abs(values[:, ::every]) ** np.float32(
        data["heavy_tail_power"])

    by_name = {name: (levels, first) for name, levels, first in cats}
    parent_of = {child: parent for child, parent in data.get("nested", [])}
    gen = np.random.Generator(np.random.Philox(cat_seq))
    level = {}
    for name, levels, _ in cats:            # children before their parents
        if name not in level and name not in parent_of.values():
            level[name] = _zipf_draw(gen, levels, rows, float(data["zipf"]))
    for child, parent in data.get("nested", []):
        level[parent] = level[child] % by_name[parent][0]
    if set(level) != set(by_name):
        raise ValueError("a nested parent is also drawn, or a column is "
                         "never drawn: %s" % sorted(set(by_name) ^ set(level)))

    nnz = dense + len(cats)
    indices = np.empty((rows, nnz), dtype=np.int32)
    vals = np.ones((rows, nnz), dtype=np.float32)
    indices[:, :dense] = np.arange(dense, dtype=np.int32)
    vals[:, :dense] = values
    for k, (name, _, first) in enumerate(cats):
        indices[:, dense + k] = first + level[name]
    X = sp.csr_matrix((vals.reshape(-1), indices.reshape(-1),
                       np.arange(0, rows * nnz + 1, nnz, dtype=np.int64)),
                      shape=(rows, total))

    gen = np.random.Generator(np.random.Philox(label_seq))
    w = gen.standard_normal(dense).astype(np.float32) * np.float32(
        data["weight_scale"])
    logit = values @ w + np.float32(data["interaction"]) * np.sin(
        values[:, 0]) * values[:, 1]
    for name in data["effects"]:
        levels = by_name[name][0]
        effect = gen.standard_normal(levels).astype(np.float32) * np.float32(
            data["effect_scale"])
        logit += effect[level[name]]
    logit += gen.standard_normal(rows, dtype=np.float32) * np.float32(
        data["noise"])
    y = (logit > np.median(logit)).astype(np.float32)
    return (X if features == total else X[:, :features]), y, {}
