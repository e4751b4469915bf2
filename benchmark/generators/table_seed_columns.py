"""``harness/traffic.make_table``'s table with its columns in the order the
mix's ``table_seed`` draws, whatever ``--seed`` is.

For a cell whose program samples columns by position (``feature_fraction``:
the mask is a draw of column numbers), another order of the same columns puts
other columns under the mask and grows other trees, so that the seeds' runs
would differ by what tables differ by and not by the machine's spread alone.
With the order fixed every seed feeds the same table and grows the same
trees, as the mixes without a generator do by construction (a histogram does
not depend on where its column stands).
"""
from __future__ import annotations

from benchmark.harness import traffic


def make_table(rows: int, features: int, seed: int, data: dict):
    """``(X float32 [rows, features], y float32 [rows], {})``: the table of
    ``data["table_seed"]`` with its columns in the order that same number
    draws; ``seed`` moves nothing."""
    X, y = traffic.make_table(rows, features, int(data["table_seed"]), data)
    return X, y, {}
