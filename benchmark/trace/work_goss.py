"""The work training under gradient-based one-side sampling needs, from
shapes and from the grown trees' own row counts: the same whatever
implements it.

As ``work.py`` counts the unsampled algorithm, with what the mode changes:
a tree is grown from the rows in the bag alone, so its histogram passes
visit the in-bag rows of the root and of every smaller child (the counts a
model text carries under sampling are in-bag counts), and each iteration
has one more pass over every row, the sampling: gradient and hessian read,
both written back amplified, and the in-bag indicator written. The
gradient and the score pass cover every row, in the bag or not. Nothing an
implementation adds (a sort for the threshold, the draw's bits, passes
over rows that carry no weight) counts.
"""
from __future__ import annotations

from .work import (GH_BYTES, gradient_pass, histogram_pass, histogram_rows,
                   least_seconds, score_pass)

BAG_BYTES = 4           # a row's in-bag indicator, float32


def sampling_pass(rows: int) -> dict:
    """Gradient and hessian of every row read, written back amplified, and
    the indicator written: 20 bytes a row; the weight ``|g * h|`` (2
    operations), its comparison with the threshold, the draw's comparison
    and the two products counted as 6 operations a row."""
    return {"bytes": rows * (2 * GH_BYTES + BAG_BYTES), "ops": 6 * rows}


def boosting_iteration(rows: int, features: int, hist_rows: int) -> dict:
    """One sampled iteration over ``rows`` rows whose tree's histograms
    visit ``hist_rows`` in-bag rows."""
    parts = (histogram_pass(hist_rows, features), gradient_pass(rows),
             sampling_pass(rows), score_pass(rows))
    return {"bytes": sum(p["bytes"] for p in parts),
            "ops": sum(p["ops"] for p in parts)}


def trees_least_seconds(tree_counts: list, features: int, peaks: dict,
                        rows: int = None) -> float:
    """Least time of the histogram passes of the trees ``tree_counts``
    describes (``work.tree_counts_from_model_text`` of a model grown under
    sampling: in-bag counts); with ``rows``, of the whole sampled
    iterations that grew them."""
    total = 0.0
    for counts in tree_counts:
        hist_rows = histogram_rows(counts)
        work = (histogram_pass(hist_rows, features) if rows is None
                else boosting_iteration(rows, features, hist_rows))
        total += least_seconds(work, peaks)[0]
    return total
