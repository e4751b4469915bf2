"""The work quantized-gradient training needs, from shapes and from the
grown trees' own row counts: the same whatever implements it.

As ``work.py`` counts the float32 algorithm, with what the mode changes: a
row's gradient and hessian are two int8 values, 2 bytes where float32 has
8, so a histogram pass reads ``rows * (features * 1 + 2)`` bytes and its
two additions per row and feature are integer operations, held against the
chip's int8 peak; the gradient pass reads score and label and writes those
2 bytes. Nothing an implementation adds (the float32 gradients between the
objective and the discretizer, one-hot operands, the int32 store) counts.
"""
from __future__ import annotations

from .work import BIN_BYTES, SCORE_BYTES, histogram_rows, score_pass

QGH_BYTES = 2           # gradient and hessian of a row, int8 each


def histogram_pass(rows: int, features: int) -> dict:
    """Summing ``rows`` rows' integer gradient and hessian into their bins
    of ``features`` features: every bin and every (q_g, q_h) read once, two
    integer additions per row and feature."""
    return {"bytes": rows * (features * BIN_BYTES + QGH_BYTES),
            "int8_ops": 2 * rows * features}


def gradient_pass(rows: int) -> dict:
    """Score and label read, the discretized gradient and hessian written;
    the logistic function counted as 8 operations a row and the rounding
    (divide, add, floor) as 3 a value."""
    return {"bytes": rows * (2 * SCORE_BYTES + QGH_BYTES),
            "ops": (8 + 2 * 3) * rows}


def boosting_iteration(rows: int, features: int, hist_rows: int) -> dict:
    parts = (histogram_pass(hist_rows, features), gradient_pass(rows),
             score_pass(rows))
    return {k: sum(p.get(k, 0) for p in parts)
            for k in ("bytes", "ops", "int8_ops")}


def least_seconds(work: dict, peaks: dict) -> tuple:
    """``(seconds, bound)``: the larger of bytes over the memory's peak and
    the arithmetic's time, integer operations over the int8 peak plus the
    others over the bf16 peak (one matrix unit serves both), and which of
    the two it is."""
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_ops = (work.get("int8_ops", 0) / peaks["int8_ops_per_s"]
              + work.get("ops", 0) / peaks["bf16_flops_per_s"])
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "ops")


def trees_least_seconds(tree_counts: list, features: int, peaks: dict,
                        rows: int = None) -> float:
    """Least time of the histogram passes of the trees ``tree_counts``
    describes (``work.tree_counts_from_model_text``); with ``rows``, of the
    whole boosting iterations that grew them."""
    total = 0.0
    for counts in tree_counts:
        hist_rows = histogram_rows(counts)
        work = (histogram_pass(hist_rows, features) if rows is None
                else boosting_iteration(rows, features, hist_rows))
        total += least_seconds(work, peaks)[0]
    return total
