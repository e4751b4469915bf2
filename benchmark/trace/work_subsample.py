"""The work training under column and row sampling needs, from shapes, from
the grown trees' own row counts and from the number of sampled columns: the
same whatever implements it.

As ``work.py`` counts the unsampled algorithm, with what the two settings
change: a tree is grown from the rows in the bag and the columns of its mask
alone, so its histogram passes visit the in-bag rows of the root and of
every smaller child (the counts a model text carries under bagging are
in-bag counts) and read, of each, the sampled columns' bins and the gradient
and hessian; and once a draw (every ``bagging_freq`` iterations) the in-bag
indicator of every row is written. The gradient and the score pass cover
every row, in the bag or not. Nothing an implementation adds (the draw's
bits, passes over rows or columns that carry no weight, a mask's upload)
counts.
"""
from __future__ import annotations

from .work import (gradient_pass, histogram_pass, histogram_rows,
                   least_seconds, score_pass)

BAG_BYTES = 4           # a row's in-bag indicator, float32


def sampled_columns(features: int, feature_fraction: float) -> int:
    """Columns a tree may split on: upstream's ``ColSampler::GetCnt``."""
    if not 0.0 < feature_fraction < 1.0:
        return features
    return max(1, int(round(features * feature_fraction)))


def bag_draws(first_iteration: int, iterations: int, bagging_freq: int) -> int:
    """Bags drawn over ``iterations`` iterations from ``first_iteration``
    on: one wherever the iteration number divides by ``bagging_freq``."""
    return sum(1 for i in range(first_iteration, first_iteration + iterations)
               if i % bagging_freq == 0) if bagging_freq > 0 else 0


def bag_pass(rows: int) -> dict:
    """The indicator of every row written, 4 bytes a row; the comparison
    with the fraction counted as one operation a row."""
    return {"bytes": rows * BAG_BYTES, "ops": rows}


def histograms_least_seconds(tree_counts: list, columns: int,
                             peaks: dict) -> float:
    """Least time of the histogram passes of the trees ``tree_counts``
    describes (``work.tree_counts_from_model_text`` of a model grown under
    bagging: in-bag counts), each over ``columns`` sampled columns."""
    return sum(least_seconds(histogram_pass(histogram_rows(counts), columns),
                             peaks)[0] for counts in tree_counts)


def window_least_seconds(tree_counts: list, columns: int, rows: int,
                         draws: int, peaks: dict) -> float:
    """Least time of the whole iterations that grew ``tree_counts`` over a
    table of ``rows`` rows, with ``draws`` bags drawn among them."""
    passes = [gradient_pass(rows), score_pass(rows)] * len(tree_counts) \
        + [bag_pass(rows)] * draws
    return histograms_least_seconds(tree_counts, columns, peaks) + sum(
        least_seconds(p, peaks)[0] for p in passes)
