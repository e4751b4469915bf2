"""The work an algorithm needs, from shapes and from the grown trees' own
row counts: the same whatever implements it.

Bytes are the inputs a pass has to read once and the outputs it has to
write once; operations are the additions that accumulate them. Nothing an
implementation adds (compaction, one-hot operands, histogram stores,
padding, recomputation) counts.
"""
from __future__ import annotations

import re

BIN_BYTES = 1           # a feature's bin of a row, max_bin <= 256
GH_BYTES = 8            # gradient and hessian of a row, float32 each
SCORE_BYTES = 4


def histogram_pass(rows: int, features: int) -> dict:
    """Summing gradient and hessian of ``rows`` rows into their bins of
    ``features`` features: every bin and every (g, h) read once, two
    additions per row and feature."""
    return {"bytes": rows * (features * BIN_BYTES + GH_BYTES),
            "ops": 2 * rows * features}


def gradient_pass(rows: int) -> dict:
    """Score and label read, gradient and hessian written; the logistic
    function counted as 8 operations a row."""
    return {"bytes": rows * (2 * SCORE_BYTES + GH_BYTES), "ops": 8 * rows}


def score_pass(rows: int) -> dict:
    """Each row's score read, its leaf's value added, written back."""
    return {"bytes": rows * 2 * SCORE_BYTES, "ops": rows}


def histogram_rows(tree_counts: list) -> int:
    """Rows a tree's histograms have to visit: all of them at the root, then
    the smaller child of every split (its sibling comes by subtraction)."""
    root, smaller = tree_counts
    return int(root) + int(sum(smaller))


def boosting_iteration(rows: int, features: int, hist_rows: int) -> dict:
    parts = (histogram_pass(hist_rows, features), gradient_pass(rows),
             score_pass(rows))
    return {"bytes": sum(p["bytes"] for p in parts),
            "ops": sum(p["ops"] for p in parts)}


def least_seconds(work: dict, peaks: dict) -> tuple:
    """``(seconds, bound)``: the larger of bytes over the memory's peak and
    operations over the arithmetic peak, and which of the two it is."""
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_ops = work["ops"] / peaks["bf16_flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "ops")


_FIELD = re.compile(r"^(\w+)=(.*)$", re.M)


def tree_counts_from_model_text(text: str) -> list:
    """``[(root rows, [smaller child's rows per split])]`` per tree of a
    LightGBM model text, from its ``internal_count``, ``leaf_count``,
    ``left_child`` and ``right_child`` lines (a negative child ``c`` is leaf
    ``~c``)."""
    out = []
    for block in text.split("\nTree=")[1:]:
        kv = dict(_FIELD.findall(block.split("\n\n")[0]))
        if int(kv.get("num_leaves", "1")) < 2:
            continue
        ints = lambda k: [int(v) for v in kv[k].split()]
        internal, leaf = ints("internal_count"), ints("leaf_count")
        count = lambda c: internal[c] if c >= 0 else leaf[~c]
        smaller = [min(count(l), count(r)) for l, r in
                   zip(ints("left_child"), ints("right_child"))]
        out.append((internal[0], smaller))
    return out
