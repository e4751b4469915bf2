"""The work piecewise-linear leaves need, from the trees themselves: the same
whatever implements them.

A tree's linear leaves are described by each leaf's rows ``n`` and the
features ``k`` its linear model kept (``leaf_count`` and ``leaf_features``
of a LightGBM model text; a leaf that kept its constant has ``k = 0``).
Per leaf with a fit, the fit reads each row's ``k`` raw values (4 bytes
each), its gradient, hessian and leaf (12 bytes) once, and accumulates
``h a a^T`` and ``g a`` over ``a = [x, 1]``: ``2 (k + 1)^2`` operations a
row. The linear output reads each row's ``k`` values and its leaf and
reads and writes its score (``4 k + 12`` bytes), over the training rows and
over the validation rows, which fall into the leaves as the training rows
do. The same sums come from three totals over the leaves with a fit,
``rows = sum n``, ``row_features = sum n k``, ``row_features_sq = sum n
k^2`` (``from_sums``), which is what the program's counters hold. Nothing an
implementation adds (gathers of path tables, one-hot operands, padding,
the solves, which are ``(k + 1)^3`` a leaf and not a row) counts.
"""
from __future__ import annotations

import re

from .work import least_seconds  # noqa: F401

VALUE_BYTES = 4         # a raw value, float32
ROW_BYTES = 12          # gradient, hessian and leaf of a row; or its score
                        # read and written and its leaf

_FIELD = re.compile(r"^(\w+)=(.*)$", re.M)


def leaves_from_model_text(text: str) -> list:
    """``[[(n, k) per leaf]]`` per linear tree of a model text with more
    than one leaf, ``k`` the features the leaf's linear model kept."""
    out = []
    for block in text.split("\nTree=")[1:]:
        kv = dict(_FIELD.findall(block.split("\n\n")[0]))
        if int(kv.get("num_leaves", "1")) < 2 \
                or not int(kv.get("is_linear", "0")):
            continue
        counts = [int(v) for v in kv["leaf_count"].split()]
        feats = [int(v) for v in kv["num_features"].split()]
        out.append(list(zip(counts, feats)))
    return out


def sums(leaves: list) -> dict:
    """``rows``, ``row_features``, ``row_features_sq`` over the leaves
    with a fit (``k > 0``) and ``all_rows`` over every leaf, of one tree's
    ``[(n, k)]``."""
    fit = [(n, k) for n, k in leaves if k]
    return {"rows": sum(n for n, _ in fit),
            "row_features": sum(n * k for n, k in fit),
            "row_features_sq": sum(n * k * k for n, k in fit),
            "all_rows": sum(n for n, _ in leaves)}


def fit_pass(rows: int, row_features: int, row_features_sq: int) -> dict:
    """The fit of the leaves whose rows, and rows times features kept
    (and squared), add up as given: ``sum n (4 k + 12)`` bytes,
    ``sum 2 n (k + 1)^2`` operations."""
    return {"bytes": VALUE_BYTES * row_features + ROW_BYTES * rows,
            "ops": 2 * (row_features_sq + 2 * row_features + rows)}


def output_pass(all_rows: int, row_features: int, valid_rows: int = 0
                ) -> dict:
    """The linear output of ``all_rows`` training rows, of which the rows
    with a fitted leaf read ``row_features`` values in all, and of
    ``valid_rows`` validation rows that fall into the leaves alike:
    ``(4 k + 12)`` bytes and ``k`` multiply-adds (``2 k`` operations) a
    row."""
    scale = 1.0 + valid_rows / all_rows if all_rows else 1.0
    return {"bytes": int(scale * (VALUE_BYTES * row_features
                                  + ROW_BYTES * all_rows)),
            "ops": int(scale * 2 * row_features)}


def tree_passes(leaves: list, valid_rows: int = 0) -> tuple:
    """``(fit_pass, output_pass)`` of one tree's ``[(n, k)]``."""
    s = sums(leaves)
    return (fit_pass(s["rows"], s["row_features"], s["row_features_sq"]),
            output_pass(s["all_rows"], s["row_features"], valid_rows))
