"""Exclusive Feature Bundling (EFB) — sparse-feature compression.

TPU-native equivalent of the reference's feature bundling
(reference: ``Dataset::FindGroups`` src/io/dataset.cpp:107-200 greedy
conflict-aware graph coloring; ``FeatureGroup`` include/LightGBM/
feature_group.h:25 bin-offset packing). Mutually-(almost-)exclusive sparse
features share one bin column: bundle bin 0 means "every member at its
zero bin"; member j's non-zero bins occupy a contiguous sub-range in
original bin order.

Where the reference's histogram works directly on group columns and scans
per-feature slices, the TPU build keeps the downstream learner unchanged:
the [N, G] bundled matrix is histogrammed on device and the bundle
histogram is *unpacked* back to per-feature [F, B] histograms, each
feature's bundle row shifted to its sub-range's start
(ops/histogram.py unpack_bundle_histogram); a member's
zero-bin row is reconstructed as leaf_total − Σ(non-zero bins) — valid
because exclusivity means "some other member is non-zero" ⇒ "this member
is zero" (the reference's FixHistogram plays the same trick,
src/io/dataset.cpp ConstructHistogramsInner).

Only numerical, non-NaN-missing features are bundled; categorical and
NaN-carrying features keep their own columns (single-member groups use
identity mappings so the learner has one uniform code path).
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Tuple

import numpy as np

# Share of the bundling sample's rows in which the members of one bundle
# may overlap: upstream's documented default (docs/Parameters.rst
# ``max_conflict_rate``), so bundles are exclusive on the sample.
MAX_CONFLICT_RATE = 0.0


class BundleLayout(NamedTuple):
    """Static description of the bundled bin matrix.

    ``needs_zero_fix[f]`` marks features living in multi-member bundles:
    their zero-bin histogram row must be reconstructed as total −
    Σ(others). Such a feature's non-zero bins, in order, are its bundle's
    bins from ``first_bin[f]`` on (``member_bin``); a feature alone in its
    column keeps its bins as they are.
    """
    groups: List[List[int]]          # used-feature indices per bundle
    group_of: np.ndarray             # [F] i32 bundle column per feature
    needs_zero_fix: np.ndarray       # [F] bool
    first_bin: np.ndarray            # [F] i32 (0 where alone)
    num_bins: np.ndarray             # [F] i32 each feature's own bins
    group_bins: np.ndarray           # [G] i32 each bundle's own bins
    num_bundled_bins: int            # Bg

    @property
    def num_groups(self) -> int:
        return len(self.groups)


def member_bin(raw, first_bin, num_bins, zero_bin, zero_fix,
               where=np.where):
    """A feature's original bin where its bundle column holds ``raw``:
    a member of a bundle (``zero_fix``) reads its non-zero bins, in
    order, from the bundle bins ``first_bin .. first_bin + num_bins - 2``
    and its zero bin everywhere else; a feature alone in its column reads
    ``raw``. Comparisons alone, on numpy or (``where=jnp.where``) jax
    arrays."""
    slot = raw - first_bin
    mine = (slot >= 0) & (slot < num_bins - 1)
    return where(zero_fix, where(mine, slot + (slot >= zero_bin), zero_bin),
                 raw)


def find_groups(nonzero_masks: List[Optional[np.ndarray]],
                num_bins: np.ndarray,
                sample_cnt: int,
                max_bundle_bins: int) -> List[List[int]]:
    """Greedy conflict-aware bundling over sampled non-zero masks
    (reference: Dataset::FindGroups, src/io/dataset.cpp:107: features
    sorted by non-zero count, each placed into the first group whose
    accumulated conflict count stays under the budget).

    ``nonzero_masks[f]`` is a bool[sample_cnt] mask of sampled rows where
    feature f is away from its zero bin, or None if the feature must not
    be bundled (dense/categorical/NaN) — those get singleton groups.
    A group's members may overlap in at most
    ``int(MAX_CONFLICT_RATE * sample_cnt)`` sampled rows.
    """
    F = len(nonzero_masks)
    max_conflict = int(MAX_CONFLICT_RATE * sample_cnt)
    candidates = [f for f in range(F) if nonzero_masks[f] is not None]
    # densest first, like the reference's sorted-by-cnt order
    candidates.sort(key=lambda f: -int(nonzero_masks[f].sum()))

    groups: List[List[int]] = []
    group_mask: List[np.ndarray] = []     # union of member non-zero rows
    group_conflicts: List[int] = []
    group_bins: List[int] = []            # 1 (shared zero) + Σ (b_f - 1)
    for f in candidates:
        mask = nonzero_masks[f]
        extra_bins = int(num_bins[f]) - 1
        placed = False
        for gi in range(len(groups)):
            if group_bins[gi] + extra_bins > max_bundle_bins:
                continue
            conflicts = int((group_mask[gi] & mask).sum())
            if group_conflicts[gi] + conflicts <= max_conflict:
                groups[gi].append(f)
                group_mask[gi] |= mask
                group_conflicts[gi] += conflicts
                group_bins[gi] += extra_bins
                placed = True
                break
        if not placed:
            groups.append([f])
            group_mask.append(mask.copy())
            group_conflicts.append(0)
            group_bins.append(1 + extra_bins)
    # non-candidates keep their own columns
    for f in range(F):
        if nonzero_masks[f] is None:
            groups.append([f])
    return groups


def build_layout(groups: List[List[int]],
                 num_bins: np.ndarray) -> BundleLayout:
    """Assign bundle bin ranges (reference: FeatureGroup bin offsets,
    include/LightGBM/feature_group.h:25)."""
    F = len(num_bins)
    group_of = np.zeros(F, dtype=np.int32)
    needs_zero_fix = np.zeros(F, dtype=bool)
    first_bin = np.zeros(F, dtype=np.int32)
    widths = []                          # each bundle's own bins
    for g, members in enumerate(groups):
        group_of[members] = g
        if len(members) == 1:
            widths.append(int(num_bins[members[0]]))
            continue
        offset = 1                       # bin 0: every member at its zero
        for f in members:
            needs_zero_fix[f] = True
            first_bin[f] = offset
            offset += int(num_bins[f]) - 1
        widths.append(offset)
    return BundleLayout(groups=groups, group_of=group_of,
                        needs_zero_fix=needs_zero_fix, first_bin=first_bin,
                        num_bins=np.asarray(num_bins, dtype=np.int32),
                        group_bins=np.asarray(widths, dtype=np.int32),
                        num_bundled_bins=max(max(widths), 2))


def bundle_columns(per_feature_bin_cols, layout: BundleLayout,
                   zero_bins: np.ndarray, n: int,
                   dtype) -> Tuple[np.ndarray, int]:
    """Pack per-feature bin columns into the bundled [N, G] matrix:
    ``(matrix, conflict rows)``. ``per_feature_bin_cols(f)`` yields the
    full bin column of used feature f. A row in which two members of a
    bundle are away from their zero bins (a conflict: the bundling
    sample admits ``MAX_CONFLICT_RATE`` of them, the rows outside it any
    number) keeps the member with the higher feature number, as
    upstream's ``FeatureGroup::PushData`` writes members in column
    order; the other members read their zero bins there. ``conflict
    rows`` counts such (row, bundle) pairs."""
    G = layout.num_groups
    out = np.zeros((n, G), dtype=dtype)
    conflicts = 0
    for g, members in enumerate(layout.groups):
        if len(members) == 1:
            out[:, g] = per_feature_bin_cols(members[0])
            continue
        col = np.zeros(n, dtype=np.int32)
        hits = np.zeros(n, dtype=np.uint8)
        for f in sorted(members):           # the higher feature writes last
            fb = np.asarray(per_feature_bin_cols(f))
            zb = int(zero_bins[f])
            rows = np.flatnonzero(fb != zb)
            t = fb[rows].astype(np.int32)
            # original bin t (!= zero bin) at its bundle slot
            col[rows] = layout.first_bin[f] + t - (t > zb)
            hits[rows] += 1
        conflicts += int(np.count_nonzero(hits > 1))
        out[:, g] = col.astype(dtype)
    return out, conflicts
