"""The work scoring a block of rows needs, from shapes and from the number
of nodes its rows visit: the same however the program walks the forest.

Bytes are what has to cross the chip's memory once: the block's raw values
in, every tree's node records and leaf values in, one score a row out.
Operations are a comparison and a choice of child per node visited and an
addition per row and tree. Nothing an implementation adds (quantised copies
of the block, lockstep hops past a row's leaf, padded node tables, one
dispatch per tree) counts.
"""
from __future__ import annotations

VALUE_BYTES = 4         # a raw feature value, float32
NODE_BYTES = 16         # split feature, threshold, left and right child
LEAF_BYTES = 4
SCORE_BYTES = 4
OPS_PER_HOP = 2         # compare with the threshold, choose the child


def block(rows: int, features: int, trees: int, leaves: int,
          hops_per_row: float) -> dict:
    """Scoring ``rows`` rows of ``features`` values with ``trees`` trees of
    ``leaves`` leaves, a row visiting ``hops_per_row`` nodes in all of
    them."""
    tables = trees * ((leaves - 1) * NODE_BYTES + leaves * LEAF_BYTES)
    return {"bytes": rows * features * VALUE_BYTES + tables
            + rows * SCORE_BYTES,
            "ops": rows * hops_per_row * OPS_PER_HOP + rows * trees}
