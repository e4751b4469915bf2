"""The work a boosting iteration over EFB bundles needs (Ke et al., NeurIPS
2017, Algorithms 3 and 4): the same whatever implements it, and whatever
bins a layout pads a feature or a bundle to.

- The histogram passes read each row's bundle columns once, a byte each,
  and its gradient and hessian: the root's rows and each smaller child's
  (its sibling comes by subtraction), ``G`` columns a row
  (``work.histogram_pass``).
- A feature's histogram holds its own bins, 4 float32 channels each: a
  dense column ``max_bin``, an indicator two. It is made from its bundle's
  histogram, which holds that bundle's own bins (one zero bin shared by its
  members and every member's others), once at the root and once a split
  (``unpack_pass``: the bundle histograms read once, the features'
  histograms written once, an operation an entry and channel). The program
  counts both kinds of entries as its unpacks make them
  (``efb/unpacked_entries``, ``efb/bundle_entries``).
- The gradient and score passes are ``trace/work.py``'s.
"""
from __future__ import annotations

from . import work

CHANNEL_BYTES = 4 * 4   # grad, hess, count, total: float32 each


def unpack_pass(entries: int, bundle_entries: int) -> dict:
    """Per-feature histograms of ``entries`` (feature, bin) pairs made from
    bundle histograms of ``bundle_entries`` (bundle, bin) pairs: each read
    once, each written once, an operation an entry and channel."""
    return {"bytes": CHANNEL_BYTES * (entries + bundle_entries),
            "ops": 4 * entries}


def step(tree_counts: list, rows: int, groups: int, entries: int,
         bundle_entries: int) -> list:
    """The passes of the trees whose ``work.tree_counts_from_model_text``
    counts are ``tree_counts``, over ``rows`` training rows bundled into
    ``groups`` columns, whose unpacks made ``entries`` (feature, bin) pairs
    from ``bundle_entries`` (bundle, bin) pairs in all."""
    parts = [unpack_pass(entries, bundle_entries)]
    for counts in tree_counts:
        parts += [work.histogram_pass(work.histogram_rows(counts), groups),
                  work.gradient_pass(rows), work.score_pass(rows)]
    return parts
