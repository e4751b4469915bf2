"""Best-split search over (grad, hess, count) histograms — the TPU analogue of
the reference's per-feature threshold scan.

Reference semantics reproduced (src/treelearner/feature_histogram.hpp:85
``FindBestThreshold`` / ``FindBestThresholdSequentially``; closed forms at
:477+ ``CalculateSplittedLeafOutput`` / ``GetSplitGains``; CUDA analogue
src/treelearner/cuda/cuda_best_split_finder.cu:603):

- leaf output  = -ThresholdL1(sum_grad, l1) / (sum_hess + l2), clipped to
  +-max_delta_step when positive
- leaf gain    = -(2*ThresholdL1(g,l1)*out + (h+l2)*out^2)  (equals
  ThresholdL1(g)^2/(h+l2) when the output is unclipped)
- a split is valid iff both children have >= min_data_in_leaf rows and
  >= min_sum_hessian, and split gain exceeds parent gain + min_gain_to_split
- missing handling: features with MissingType.NAN hold NaN rows in their last
  bin; the scan evaluates both "NaN goes right" (natural — the NaN bin is
  never <= threshold) and "NaN goes left" placements and records
  ``default_left``. MissingType.ZERO rows sit in the zero bin and follow the
  natural bin comparison, so default_left = (zero_bin <= threshold).

Instead of the reference's sequential per-feature loop (or the CUDA warp
prefix-sum scan), everything here is one vectorized pass: cumulative sums over
the bin axis give left-side stats for every (feature, threshold) at once, a
masked argmax picks the winner. This maps to a handful of XLA reductions, no
data-dependent control flow.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..io.binning import MissingType

_NEG_INF = -jnp.inf


class SplitParams(NamedTuple):
    """Scalar hyper-parameters of the split search (all traced, so one
    compiled kernel serves any setting). Mirror of the Config fields used by
    the reference's FeatureHistogram (config.h:291-406; categorical knobs
    :452-472)."""
    lambda_l1: jnp.ndarray
    lambda_l2: jnp.ndarray
    min_data_in_leaf: jnp.ndarray
    min_sum_hessian_in_leaf: jnp.ndarray
    min_gain_to_split: jnp.ndarray
    max_delta_step: jnp.ndarray
    cat_l2: jnp.ndarray
    cat_smooth: jnp.ndarray
    min_data_per_group: jnp.ndarray
    max_cat_threshold: jnp.ndarray
    path_smooth: jnp.ndarray = 0.0
    # CEGB scalars (cost_effective_gradient_boosting.hpp:80-87)
    cegb_tradeoff: jnp.ndarray = 1.0
    cegb_penalty_split: jnp.ndarray = 0.0
    # monotone split gain penalty (config.h:503)
    monotone_penalty: jnp.ndarray = 0.0

    @classmethod
    def from_config(cls, config) -> "SplitParams":
        return cls(
            lambda_l1=jnp.float32(config.lambda_l1),
            lambda_l2=jnp.float32(config.lambda_l2),
            min_data_in_leaf=jnp.float32(config.min_data_in_leaf),
            min_sum_hessian_in_leaf=jnp.float32(config.min_sum_hessian_in_leaf),
            min_gain_to_split=jnp.float32(config.min_gain_to_split),
            max_delta_step=jnp.float32(config.max_delta_step),
            cat_l2=jnp.float32(config.cat_l2),
            cat_smooth=jnp.float32(config.cat_smooth),
            min_data_per_group=jnp.float32(config.min_data_per_group),
            max_cat_threshold=jnp.int32(config.max_cat_threshold),
            path_smooth=jnp.float32(config.path_smooth),
            cegb_tradeoff=jnp.float32(config.cegb_tradeoff),
            cegb_penalty_split=jnp.float32(config.cegb_penalty_split),
            monotone_penalty=jnp.float32(config.monotone_penalty),
        )


class FeatureMeta(NamedTuple):
    """Per-feature static metadata, device-resident (int32 [F] each).
    Derived from the BinMappers at dataset finalization."""
    num_bin: jnp.ndarray        # bins actually used by feature f
    missing_type: jnp.ndarray   # MissingType value
    zero_bin: jnp.ndarray       # bin holding value 0.0 (default_bin)
    is_categorical: jnp.ndarray  # bool[F]
    use_onehot: jnp.ndarray     # bool[F]: cat feature with few categories
    monotone: jnp.ndarray       # i8[F]: -1/0/+1 monotone constraint

    @classmethod
    def from_dataset(cls, dataset, max_cat_to_onehot: int = 4
                     ) -> "FeatureMeta":
        import numpy as np
        from ..io.binning import BinType
        is_cat = np.asarray(
            [m.bin_type == BinType.CATEGORICAL
             for m in dataset.bin_mappers], dtype=bool)
        num_bin = np.asarray(dataset.num_bin_per_feature, dtype=np.int32)
        mc = dataset.monotone_constraints
        monotone = (np.zeros(len(num_bin), dtype=np.int8) if mc is None
                    else np.asarray(mc, dtype=np.int8))
        return cls(
            num_bin=jnp.asarray(num_bin),
            missing_type=jnp.asarray(
                np.asarray([m.missing_type for m in dataset.bin_mappers],
                           dtype=np.int32)),
            zero_bin=jnp.asarray(
                np.asarray([m.default_bin for m in dataset.bin_mappers],
                           dtype=np.int32)),
            is_categorical=jnp.asarray(is_cat),
            use_onehot=jnp.asarray(
                is_cat & (num_bin <= max_cat_to_onehot)),
            monotone=jnp.asarray(monotone),
        )


def pad_feature_meta(meta: "FeatureMeta", pad: int) -> "FeatureMeta":
    """Append ``pad`` trivial features (num_bin 1 → never a valid split
    candidate). Used to pad the feature axis to a canonical width so
    compiled step variants are shared across datasets."""
    if pad <= 0:
        return meta

    def padv(a, fill):
        return jnp.concatenate(
            [a, jnp.full((pad,), fill, dtype=a.dtype)])

    return FeatureMeta(
        num_bin=padv(meta.num_bin, 1),
        missing_type=padv(meta.missing_type, 0),
        zero_bin=padv(meta.zero_bin, 0),
        is_categorical=padv(meta.is_categorical, False),
        use_onehot=padv(meta.use_onehot, False),
        monotone=padv(meta.monotone, 0),
    )


class SplitInfo(NamedTuple):
    """Best split of one leaf — all 0-d device arrays (except
    ``cat_mask``). The TPU analogue of the reference's POD ``SplitInfo``
    (src/treelearner/split_info.hpp:22).

    ``*_count`` are in-bag row counts (what min_data_in_leaf and leaf_count
    use, matching the reference under bagging); ``*_total_count`` count every
    partitioned row including out-of-bag ones — the learner sizes its row
    compaction buffers with these. For categorical winners
    (``is_categorical``), ``cat_mask`` is the bool[B] set of bins routed
    left (the device analogue of the reference's ``cat_threshold`` bin
    list)."""
    gain: jnp.ndarray            # f32; relative gain (already minus shift); <=0 => invalid
    feature: jnp.ndarray         # i32 inner feature index; -1 if invalid
    threshold_bin: jnp.ndarray   # i32
    default_left: jnp.ndarray    # bool
    is_categorical: jnp.ndarray  # bool
    cat_mask: jnp.ndarray        # bool[B] — bins going left (cat only)
    left_sum_grad: jnp.ndarray   # f32
    left_sum_hess: jnp.ndarray
    left_count: jnp.ndarray      # f32 (exact for counts < 2^24)
    left_total_count: jnp.ndarray
    left_output: jnp.ndarray
    right_sum_grad: jnp.ndarray
    right_sum_hess: jnp.ndarray
    right_count: jnp.ndarray
    right_total_count: jnp.ndarray
    right_output: jnp.ndarray
    # monotone-constraint bounds inherited by the children (reference:
    # BasicLeafConstraints, src/treelearner/monotone_constraints.hpp)
    left_min_output: jnp.ndarray
    left_max_output: jnp.ndarray
    right_min_output: jnp.ndarray
    right_max_output: jnp.ndarray


def select_frontier(gain: jnp.ndarray, k: int):
    """(leaves [k] i32, sel_gain [k] f32) of the top-``k`` pending
    split candidates, slot 0 GUARANTEED to be ``jnp.argmax(gain)``
    (ties included). The frontier-batched growers
    (treelearner/sharded.py) speculate these as the next ``k``
    leaf-wise splits in order; pinning slot 0 to the argmax is what
    guarantees every validated sweep round accepts at least one split
    — livelock-free even where ``lax.top_k``'s tie ordering disagrees
    with repeated argmax.

    ``sel_gain`` is the SELECTION value, not a gather of ``gain``:
    when fewer than ``k`` live candidates exist, ``top_k`` over the
    masked vector hands back arbitrary -inf slots whose indices may
    ALIAS a live leaf — reading that leaf's record would resurrect an
    already-consumed candidate (a stale re-split the order validation
    cannot distinguish from the real one). The -inf selection value is
    what marks such a slot dead; callers must thread it into the
    speculation record's gain."""
    best = jnp.argmax(gain).astype(jnp.int32)
    if k <= 1:
        return best[None], gain[best][None]
    masked = gain.at[best].set(-jnp.inf)
    vals, rest = jax.lax.top_k(masked, k - 1)
    return (jnp.concatenate([best[None], rest.astype(jnp.int32)]),
            jnp.concatenate([gain[best][None], vals]))


def threshold_l1(s: jnp.ndarray, l1: jnp.ndarray) -> jnp.ndarray:
    """Soft-threshold by the L1 penalty (reference:
    feature_histogram.hpp ``ThresholdL1``)."""
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def calculate_leaf_output(sum_grad, sum_hess, p: SplitParams, l2=None):
    """Closed-form leaf weight (reference: CalculateSplittedLeafOutput,
    feature_histogram.hpp:477+). ``l2`` overrides lambda_l2 (the
    categorical path adds cat_l2, :384)."""
    if l2 is None:
        l2 = p.lambda_l2
    out = -threshold_l1(sum_grad, p.lambda_l1) / (sum_hess + l2)
    return jnp.where(p.max_delta_step > 0.0,
                     jnp.clip(out, -p.max_delta_step, p.max_delta_step),
                     out)


def leaf_gain_given_output(sum_grad, sum_hess, output, p: SplitParams,
                           l2=None):
    """reference: GetLeafGainGivenOutput — exact also when the output was
    clipped by max_delta_step."""
    if l2 is None:
        l2 = p.lambda_l2
    sg = threshold_l1(sum_grad, p.lambda_l1)
    return -(2.0 * sg * output + (sum_hess + l2) * output * output)


def leaf_gain(sum_grad, sum_hess, p: SplitParams, l2=None):
    return leaf_gain_given_output(
        sum_grad, sum_hess, calculate_leaf_output(sum_grad, sum_hess, p, l2),
        p, l2)


def smooth_output(out, count, parent_output, p: SplitParams):
    """Path smoothing toward the parent's output (reference:
    CalculateSplittedLeafOutput USE_SMOOTHING branch,
    feature_histogram.hpp:743-765): out*(n/α)/(n/α+1) + parent/(n/α+1),
    applied after max_delta_step clipping, before monotone clamping."""
    alpha = jnp.maximum(p.path_smooth, jnp.float32(1e-30))
    f = count / alpha
    smoothed = out * f / (f + 1.0) + parent_output / (f + 1.0)
    return jnp.where(p.path_smooth > kSmoothEps, smoothed, out)


kSmoothEps = 1e-15


def make_rand_bins(key, meta: "FeatureMeta", params: SplitParams):
    """extra_trees (config.h:368): one random candidate threshold per
    feature per leaf (reference: meta_->rand.NextInt calls in
    feature_histogram.hpp:109,321,402). Returns (numerical threshold,
    one-hot bin, sorted-prefix position) per feature.

    Seeding contract shared by ALL learners: feature f's draw depends
    only on (key, f) — each feature folds its index into the node key
    and draws from its own stream. A whole-vector ``uniform(key, (F,))``
    draw would make the values depend on the padded feature count,
    and the serial learner pads F to a multiple of 8 while the mesh
    learners don't — their extra_trees splits would diverge."""
    F = meta.num_bin.shape[0]
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        key, jnp.arange(F, dtype=jnp.uint32))
    u = jax.vmap(lambda k: jax.random.uniform(k, (3,)))(keys)
    rand_num = jnp.floor(
        u[:, 0] * jnp.maximum(meta.num_bin - 2, 1)).astype(jnp.int32)
    rand_oh = 1 + jnp.floor(
        u[:, 1] * jnp.maximum(meta.num_bin - 1, 1)).astype(jnp.int32)
    max_thr = jnp.maximum(
        jnp.minimum(params.max_cat_threshold, (meta.num_bin + 1) // 2), 1)
    rand_sorted = jnp.floor(u[:, 2] * max_thr).astype(jnp.int32)
    return rand_num, rand_oh, rand_sorted


@jax.named_scope("obs_split_scan")
def find_best_split(hist: jnp.ndarray,
                    sum_grad: jnp.ndarray,
                    sum_hess: jnp.ndarray,
                    sum_count: jnp.ndarray,
                    sum_total_count: jnp.ndarray,
                    meta: FeatureMeta,
                    params: SplitParams,
                    feature_mask: jnp.ndarray,
                    min_output=None,
                    max_output=None,
                    parent_output=None,
                    rand_bins=None,
                    gain_penalty=None,
                    leaf_depth=None,
                    has_categorical: bool = True,
                    bound_arrays=None,
                    hist_scale=None) -> SplitInfo:
    """Scan a leaf histogram for the best (feature, threshold) pair.

    Parameters
    ----------
    hist : f32[F, B, 4] — per (feature, bin) sums of
        (grad, hess, in-bag count, total count). In quantized-gradient
        mode this arrives as int32/int64 (exact integer accumulation,
        ops/quantize.py) and is dequantized ONCE here — each bin sum
        carries a single rounding from the scale multiply, however deep
        the leaf, instead of the f32 path's one rounding per
        accumulated row; the count channels convert exactly.
    hist_scale : f32[2] (g_scale, h_scale) — required meaningful values
        only when ``hist`` is integer; the leaf totals
        (sum_grad/sum_hess/...) are passed already dequantized.
    sum_grad/sum_hess/sum_count/sum_total_count : leaf totals (f32 scalars)
    meta : FeatureMeta (i32[F] arrays)
    params : SplitParams scalars
    feature_mask : bool[F] — feature_fraction / interaction-constraint mask
      (reference: src/treelearner/col_sampler.hpp)
    has_categorical : STATIC — when the dataset has no categorical
      features the one-hot/sorted-subset scans (two argsorts plus a
      sequential 256-step lax.scan) are compiled out entirely; they are
      dead weight in every split step of an all-numerical dataset.
    bound_arrays : monotone_constraints_method=advanced only — a
      ``(min_c, max_c)`` pair of f32[F, B] per-(feature, bin) output
      constraints (reference: AdvancedFeatureConstraints' piecewise
      thresholds/constraints lists, monotone_constraints.hpp:260,
      expanded dense over the bin axis; pad bins must carry -inf/+inf).
      The per-threshold left/right child bounds are their running
      extrema (reference: CumulativeFeatureConstraint,
      monotone_constraints.hpp:144 — a left child covering bins
      ``[0, t]`` is clamped by every constraint piece overlapping it,
      the right child by pieces overlapping ``[t+1, ...)``); candidates
      whose clamp interval inverts are rejected, mirroring the
      ``best_*_constraints.min > .max → continue`` skip in
      feature_histogram.hpp:950.
    """
    F, B, _ = hist.shape
    from .quantize import dequantize_hist
    hist = dequantize_hist(hist, hist_scale)
    g, h, c, tc = hist[..., 0], hist[..., 1], hist[..., 2], hist[..., 3]
    if min_output is None:
        min_output = jnp.float32(-jnp.inf)
    if max_output is None:
        max_output = jnp.float32(jnp.inf)
    if parent_output is None:
        parent_output = jnp.float32(0.0)

    if bound_arrays is not None:
        min_c, max_c = bound_arrays                              # [F, B]
        lmin_b = jax.lax.cummax(min_c, axis=1)                   # [F, B]
        lmax_b = jax.lax.cummin(max_c, axis=1)
        neg = jnp.full((F, 1), -jnp.inf, dtype=jnp.float32)
        pos = jnp.full((F, 1), jnp.inf, dtype=jnp.float32)
        rmin_b = jnp.concatenate(
            [jax.lax.cummax(min_c, axis=1, reverse=True)[:, 1:], neg], 1)
        rmax_b = jnp.concatenate(
            [jax.lax.cummin(max_c, axis=1, reverse=True)[:, 1:], pos], 1)
        bounds_ok = (lmin_b <= lmax_b) & (rmin_b <= rmax_b)      # [F, B]
        # categorical splits see the leaf-wide (threshold-independent)
        # clamp — pad bins are ±inf-neutral so the row extremum is the
        # most restrictive piece
        flat_min = jnp.max(min_c, axis=1)[:, None]               # [F, 1]
        flat_max = jnp.min(max_c, axis=1)[:, None]
    else:
        flat_min = min_output
        flat_max = max_output

    def bounded_output(sg, sh, n, l2=None, lo=None, hi=None):
        out = calculate_leaf_output(sg, sh, params, l2)
        out = smooth_output(out, n, parent_output, params)
        lo = min_output if lo is None else lo
        hi = max_output if hi is None else hi
        return jnp.clip(out, lo, hi)

    def bounded_gain(sg, sh, n, l2=None):
        return leaf_gain_given_output(
            sg, sh, bounded_output(sg, sh, n, l2, flat_min, flat_max),
            params, l2)

    is_cat = meta.is_categorical                                 # [F]
    is_num = ~is_cat

    # ---------------- numerical scan ----------------
    # Left-side stats for threshold t = sum over bins <= t.
    left_g = jnp.cumsum(g, axis=1)
    left_h = jnp.cumsum(h, axis=1)
    left_c = jnp.cumsum(c, axis=1)
    left_tc = jnp.cumsum(tc, axis=1)

    bin_ids = jnp.arange(B, dtype=jnp.int32)[None, :]            # [1, B]
    num_bin = meta.num_bin[:, None]                              # [F, 1]
    is_nan_missing = (meta.missing_type == MissingType.NAN)      # [F]
    nan_bin = jnp.clip(meta.num_bin - 1, 0, B - 1)               # [F]

    # NaN-bin contents, zero where the feature has no NaN bin.
    take = lambda a: jnp.take_along_axis(a, nan_bin[:, None], axis=1)[:, 0]
    nan_g = jnp.where(is_nan_missing, take(g), 0.0)              # [F]
    nan_h = jnp.where(is_nan_missing, take(h), 0.0)
    nan_c = jnp.where(is_nan_missing, take(c), 0.0)
    nan_tc = jnp.where(is_nan_missing, take(tc), 0.0)

    # Valid thresholds: t <= num_bin - 2 (right side must be reachable); for
    # NaN-missing features the NaN bin itself is not a threshold either
    # (reference scans value bins only).
    t_max = jnp.where(is_nan_missing[:, None], num_bin - 2, num_bin - 1)
    valid_t = (bin_ids < t_max) & feature_mask[:, None] \
        & is_num[:, None]                                        # [F, B]
    if rand_bins is not None:
        # extra_trees: only the per-feature random threshold is a candidate
        valid_t = valid_t & (bin_ids == rand_bins[0][:, None])

    mono = meta.monotone.astype(jnp.int32)[:, None]              # [F, 1]

    def split_gain(lg, lh, lc):
        rg, rh, rc = sum_grad - lg, sum_hess - lh, sum_count - lc
        ok = ((lc >= params.min_data_in_leaf) &
              (rc >= params.min_data_in_leaf) &
              (lh >= params.min_sum_hessian_in_leaf) &
              (rh >= params.min_sum_hessian_in_leaf))
        if bound_arrays is not None:
            out_l = bounded_output(lg, lh, lc, lo=lmin_b, hi=lmax_b)
            out_r = bounded_output(rg, rh, rc, lo=rmin_b, hi=rmax_b)
            ok = ok & bounds_ok
        else:
            out_l = bounded_output(lg, lh, lc)
            out_r = bounded_output(rg, rh, rc)
        # monotone filtering (reference: BasicLeafConstraints split
        # rejection, monotone_constraints.hpp)
        mono_ok = ~(((mono > 0) & (out_l > out_r))
                    | ((mono < 0) & (out_l < out_r)))
        gain = (leaf_gain_given_output(lg, lh, out_l, params)
                + leaf_gain_given_output(rg, rh, out_r, params))
        return jnp.where(ok & valid_t & mono_ok, gain, _NEG_INF)

    # Variant 0: natural placement (NaN bin stays right).
    gain_r = split_gain(left_g, left_h, left_c)
    # Variant 1: NaN bin moved to the left side (default_left).
    gain_l = split_gain(left_g + nan_g[:, None],
                        left_h + nan_h[:, None],
                        left_c + nan_c[:, None])
    # Only distinct for NaN-missing features; suppress the duplicate
    # elsewhere so argmax tie-breaking is deterministic.
    gain_l = jnp.where(is_nan_missing[:, None], gain_l, _NEG_INF)

    kEps = 1e-15
    if has_categorical:
        # ---------------- categorical scans ----------------
        # reference: FindBestThresholdCategoricalInner
        # (src/treelearner/feature_histogram.hpp:278-520). Candidate bins are
        # 1..num_bin-1 (bin 0 = NaN/other always routes right).
        cat_bin_ok = ((bin_ids >= 1) & (bin_ids < num_bin)
                      & is_cat[:, None] & feature_mask[:, None])     # [F, B]
        sum_g_ = sum_grad
        sum_h_ = sum_hess
        sum_c_ = sum_count

        # one-hot mode (num_bin <= max_cat_to_onehot; plain lambda_l2)
        oh_ok = (cat_bin_ok & meta.use_onehot[:, None]
                 & (c >= params.min_data_in_leaf)
                 & (h >= params.min_sum_hessian_in_leaf)
                 & ((sum_c_ - c) >= params.min_data_in_leaf)
                 & ((sum_h_ - h - kEps)
                    >= params.min_sum_hessian_in_leaf))
        if rand_bins is not None:
            oh_ok = oh_ok & (bin_ids == rand_bins[1][:, None])
        gain_oh = bounded_gain(g, h + kEps, c) \
            + bounded_gain(sum_g_ - g, sum_h_ - h - kEps, sum_c_ - c)
        gain_oh = jnp.where(oh_ok, gain_oh, _NEG_INF)

        # sorted-subset mode (l2 += cat_l2; sort by g/(h+cat_smooth))
        cat_l2 = params.lambda_l2 + params.cat_l2
        sort_elig = (cat_bin_ok & ~meta.use_onehot[:, None]
                     & (c >= params.cat_smooth))                     # [F, B]
        used_bin = jnp.sum(sort_elig, axis=1).astype(jnp.int32)      # [F]
        ratio = jnp.where(sort_elig, g / (h + params.cat_smooth), jnp.inf)
        order = jnp.argsort(ratio, axis=1, stable=True)              # [F, B]
        rank = jnp.argsort(order, axis=1, stable=True) \
            .astype(jnp.int32)                                       # [F, B]
        sg_s = jnp.take_along_axis(g, order, axis=1)
        sh_s = jnp.take_along_axis(h, order, axis=1)
        sc_s = jnp.take_along_axis(c, order, axis=1)
        stc_s = jnp.take_along_axis(tc, order, axis=1)
        max_num_cat = jnp.minimum(params.max_cat_threshold,
                                  (used_bin + 1) // 2)               # [F]

        def cat_dir_scan(sgd, shd, scd, stcd):
            """Prefix scan in one direction over sorted bins; returns
            per-prefix gains [F, B] plus prefix stats."""
            lg = jnp.cumsum(sgd, axis=1)
            lh = jnp.cumsum(shd, axis=1) + kEps
            lc = jnp.cumsum(scd, axis=1)
            ltc = jnp.cumsum(stcd, axis=1)
            rg, rh, rc = sum_g_ - lg, sum_h_ - lh, sum_c_ - lc
            idx = jnp.arange(B, dtype=jnp.int32)[None, :]
            pos_ok = (idx < used_bin[:, None]) & (idx < max_num_cat[:, None])
            cont = (lc < params.min_data_in_leaf) \
                | (lh < params.min_sum_hessian_in_leaf)
            brk = (~cont) & ((rc < params.min_data_in_leaf)
                             | (rc < params.min_data_per_group)
                             | (rh < params.min_sum_hessian_in_leaf))
            # sequential min_data_per_group batching (reference
            # feature_histogram.hpp:443-447): accumulate counts, evaluate
            # only when the running group reaches min_data_per_group, then
            # reset. lax.scan over the (<=256) bin positions.
            def step(carry, xs):
                cnt_cur, broken = carry
                cnt_i, cont_i, brk_i, pos_i = xs
                cnt_cur = cnt_cur + cnt_i
                can_eval = (pos_i & ~broken & ~cont_i & ~brk_i
                            & (cnt_cur >= params.min_data_per_group))
                cnt_cur = jnp.where(can_eval, 0.0, cnt_cur)
                broken = broken | (brk_i & pos_i)
                return (cnt_cur, broken), can_eval

            (_, _), can_eval = jax.lax.scan(
                step,
                (jnp.zeros(F), jnp.zeros(F, dtype=bool)),
                (scd.T, cont.T, brk.T, pos_ok.T))
            can_eval = can_eval.T                                    # [F, B]
            gains = bounded_gain(lg, lh, lc, cat_l2) \
                + bounded_gain(rg, rh, rc, cat_l2)
            return jnp.where(can_eval, gains, _NEG_INF), (lg, lh, lc, ltc)

        gain_cs_f, stats_f = cat_dir_scan(sg_s, sh_s, sc_s, stc_s)
        # reverse direction: prefixes from the high end of the sorted order,
        # but only over the eligible (first used_bin) positions — roll the
        # reversed arrays so eligible bins come first
        def rev_eligible(a):
            ar = jnp.flip(a, axis=1)
            shift = B - used_bin                                    # [F]
            idx = (jnp.arange(B, dtype=jnp.int32)[None, :]
                   + shift[:, None]) % B
            return jnp.take_along_axis(ar, idx, axis=1)

        gain_cs_r, stats_r = cat_dir_scan(
            rev_eligible(sg_s), rev_eligible(sh_s), rev_eligible(sc_s),
            rev_eligible(stc_s))
        if rand_bins is not None:
            # extra_trees sorted-subset mode: only the random prefix length
            # (reference: rand.NextInt(0, max_threshold), fh.hpp:402)
            rs = rand_bins[2][:, None] == bin_ids
            gain_cs_f = jnp.where(rs, gain_cs_f, _NEG_INF)
            gain_cs_r = jnp.where(rs, gain_cs_r, _NEG_INF)

    # Parent-gain baseline, subtracted per variant BEFORE the argmax
    # (reference: min_gain_shift). Under path smoothing the numerical
    # baseline recomputes the smoothed own-output (BeforeNumercal,
    # fh.hpp:99-110) while the categorical baseline scores the stored
    # parent output directly (fh.hpp:294-303); without smoothing both
    # reduce to the plain closed form.
    parent_gain_plain = leaf_gain(sum_grad, sum_hess, params)
    own_out = calculate_leaf_output(sum_grad, sum_hess, params)
    own_smoothed = smooth_output(own_out, sum_count, parent_output, params)
    use_smooth = params.path_smooth > kSmoothEps
    parent_gain_num = jnp.where(
        use_smooth,
        leaf_gain_given_output(sum_grad, sum_hess, own_smoothed, params),
        parent_gain_plain)
    parent_gain_cat = jnp.where(
        use_smooth,
        leaf_gain_given_output(sum_grad, sum_hess, parent_output, params),
        parent_gain_plain)
    shift_num = parent_gain_num + params.min_gain_to_split
    shift_cat = parent_gain_cat + params.min_gain_to_split

    if has_categorical:
        gains = jnp.stack([gain_r - shift_num, gain_l - shift_num,
                           gain_oh - shift_cat, gain_cs_f - shift_cat,
                           gain_cs_r - shift_cat])
    else:
        gains = jnp.stack([gain_r - shift_num, gain_l - shift_num])
    if gain_penalty is not None:
        # CEGB per-feature gain penalty (reference:
        # CostEfficientGradientBoosting::DeltaGain,
        # cost_effective_gradient_boosting.hpp:80 — threshold-independent,
        # so it reorders features without changing per-feature thresholds)
        gains = gains - gain_penalty[None, :, None]
    if leaf_depth is not None:
        # monotone split gain penalty (reference:
        # ComputeMonotoneSplitGainPenalty, monotone_constraints.hpp:355):
        # gains of splits on monotone features shrink with depth
        kMPEps = 1e-10
        p = params.monotone_penalty
        d = leaf_depth.astype(jnp.float32)
        factor = jnp.where(
            p >= d + 1.0, kMPEps,
            jnp.where(p <= 1.0,
                      1.0 - p / jnp.exp2(d) + kMPEps,
                      1.0 - jnp.exp2(p - 1.0 - d) + kMPEps))
        is_mono = (meta.monotone != 0) & (p > 0.0)
        mult = jnp.where(is_mono, factor, 1.0)[None, :, None]
        gains = jnp.where(jnp.isfinite(gains), gains * mult, gains)

    flat = gains.reshape(-1)
    best = jnp.argmax(flat)
    best_gain_rel = flat[best]
    variant, rem = best // (F * B), best % (F * B)
    feature, tbin = (rem // B).astype(jnp.int32), (rem % B).astype(jnp.int32)

    # Reconstruct the winning split's stats per variant.
    is_l = variant == 1
    lg_n = left_g[feature, tbin] + jnp.where(is_l, nan_g[feature], 0.0)
    lh_n = left_h[feature, tbin] + jnp.where(is_l, nan_h[feature], 0.0)
    lc_n = left_c[feature, tbin] + jnp.where(is_l, nan_c[feature], 0.0)
    ltc_n = left_tc[feature, tbin] + jnp.where(is_l, nan_tc[feature], 0.0)

    if has_categorical:
        winner_is_cat = variant >= 2
        lg = jnp.select(
            [variant <= 1, variant == 2, variant == 3, variant == 4],
            [lg_n, g[feature, tbin], stats_f[0][feature, tbin],
             stats_r[0][feature, tbin]])
        lh = jnp.select(
            [variant <= 1, variant == 2, variant == 3, variant == 4],
            [lh_n, h[feature, tbin] + kEps, stats_f[1][feature, tbin],
             stats_r[1][feature, tbin]])
        lc = jnp.select(
            [variant <= 1, variant == 2, variant == 3, variant == 4],
            [lc_n, c[feature, tbin], stats_f[2][feature, tbin],
             stats_r[2][feature, tbin]])
        ltc = jnp.select(
            [variant <= 1, variant == 2, variant == 3, variant == 4],
            [ltc_n, tc[feature, tbin], stats_f[3][feature, tbin],
             stats_r[3][feature, tbin]])
    else:
        winner_is_cat = jnp.asarray(False)
        lg, lh, lc, ltc = lg_n, lh_n, lc_n, ltc_n
    rg, rh, rc = sum_grad - lg, sum_hess - lh, sum_count - lc
    rtc = sum_total_count - ltc

    gain_rel = best_gain_rel
    is_valid = jnp.isfinite(best_gain_rel) & (gain_rel > 0.0)

    default_left = jnp.where(
        winner_is_cat, False,
        jnp.where(is_nan_missing[feature], variant == 1,
                  (meta.missing_type[feature] == MissingType.ZERO)
                  & (meta.zero_bin[feature] <= tbin)))

    if has_categorical:
        # categorical left-bin mask: one-hot → {tbin}; sorted fwd →
        # sorted rank <= tbin; sorted rev → the tbin+1 highest-ratio
        # eligible bins
        rk = rank[feature]                                       # [B]
        ub = used_bin[feature]
        mask_oh = jnp.arange(B, dtype=jnp.int32) == tbin
        mask_fwd = rk <= tbin
        mask_rev = (rk >= ub - 1 - tbin) & (rk < ub)
        elig_row = sort_elig[feature]
        cat_mask = jnp.select(
            [variant == 2, variant == 3, variant == 4],
            [mask_oh, mask_fwd & elig_row, mask_rev & elig_row],
            jnp.zeros(B, dtype=bool))
        out_l2 = jnp.where(variant >= 3, cat_l2, params.lambda_l2)
    else:
        cat_mask = jnp.zeros(B, dtype=bool)
        out_l2 = params.lambda_l2
    if bound_arrays is not None:
        # the winner's outputs must carry the same per-threshold clamp
        # the gain scan used (reference: CalculateSplittedLeafOutput
        # with best_left/right_constraints, feature_histogram.hpp:1060)
        w_lmin = jnp.where(winner_is_cat, flat_min[feature, 0],
                           lmin_b[feature, tbin])
        w_lmax = jnp.where(winner_is_cat, flat_max[feature, 0],
                           lmax_b[feature, tbin])
        w_rmin = jnp.where(winner_is_cat, flat_min[feature, 0],
                           rmin_b[feature, tbin])
        w_rmax = jnp.where(winner_is_cat, flat_max[feature, 0],
                           rmax_b[feature, tbin])
        out_left = bounded_output(lg, lh, lc, out_l2, w_lmin, w_lmax)
        out_right = bounded_output(rg, rh, rc, out_l2, w_rmin, w_rmax)
    else:
        out_left = bounded_output(lg, lh, lc, out_l2)
        out_right = bounded_output(rg, rh, rc, out_l2)
    # children bounds (reference: BasicLeafConstraints::Update — the
    # mid-point between child outputs caps the monotone side)
    mc_w = jnp.where(winner_is_cat, 0,
                     meta.monotone[feature].astype(jnp.int32))
    mid = (out_left + out_right) / 2.0
    left_max = jnp.where(mc_w > 0, jnp.minimum(max_output, mid),
                         max_output)
    right_min = jnp.where(mc_w > 0, jnp.maximum(min_output, mid),
                          min_output)
    left_min = jnp.where(mc_w < 0, jnp.maximum(min_output, mid),
                         min_output)
    right_max = jnp.where(mc_w < 0, jnp.minimum(max_output, mid),
                          max_output)
    return SplitInfo(
        gain=jnp.where(is_valid, gain_rel, _NEG_INF).astype(jnp.float32),
        feature=jnp.where(is_valid, feature, -1),
        threshold_bin=tbin,
        default_left=default_left,
        is_categorical=winner_is_cat,
        cat_mask=cat_mask,
        left_sum_grad=lg, left_sum_hess=lh, left_count=lc,
        left_total_count=ltc,
        left_output=out_left,
        right_sum_grad=rg, right_sum_hess=rh, right_count=rc,
        right_total_count=rtc,
        right_output=out_right,
        left_min_output=left_min, left_max_output=left_max,
        right_min_output=right_min, right_max_output=right_max,
    )
