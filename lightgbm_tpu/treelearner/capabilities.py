"""Learner-independent capability layer: config resolution + the
host-side training loops that need per-split host state.

The reference supports every feature (CEGB, monotone constraint methods,
extra_trees, interaction constraints, per-node column sampling) under
every ``tree_learner`` — the feature logic lives in shared classes the
learners all call (reference: src/treelearner/col_sampler.hpp,
cost_effective_gradient_boosting.hpp, monotone_constraints.hpp). This
module is the TPU build's equivalent: the config-derived feature state
(:class:`CapabilityMixin`) and the three host drivers that steer
per-split device steps (CEGB penalties, intermediate-monotone bound
propagation, per-node feature masks) are written once and used by both
the single-chip :class:`~.serial.SerialTreeLearner` and the
mesh-parallel learners (parallel/data_parallel.py), which plug in their
own jitted step functions via the ``_cegb_root/_cegb_step``,
``_mono_root/_mono_step/_mono_rescan`` and ``_node_step`` adapter
methods.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.registry import registry as obs
from ..utils import log
from .grow import apply_split_record, record_is_valid


class CapabilityMixin:
    """Config-derived feature state shared by all tree learners.

    Requires the concrete learner to define, before the ``_init_*``
    calls: ``config``, ``dataset``, ``F`` (logical features), ``Fp``
    (padded feature axis of masks/penalty vectors), ``L``, ``R``,
    ``_ff_rng``, ``_extra_trees``.
    """

    # the voting learner keeps no per-leaf histogram store, so the
    # intermediate monotone method's rescans are impossible there
    _supports_intermediate = True

    # ------------------------------------------------------------------
    def _resolve_constraints(self):
        """interaction_constraints (config.h:562): groups of inner feature
        indices; a branch may only combine features co-occurring in at
        least one group (reference: ColSampler::SetUsedFeatureByNode)."""
        ic = self.config.interaction_constraints
        if not ic:
            self._constraint_groups = None
            return
        groups = []
        for grp in ic:
            inner = set()
            for real_f in grp:
                j = self.dataset.inner_feature_index(int(real_f))
                if j >= 0:
                    inner.add(j)
            if inner:
                groups.append(frozenset(inner))
        self._constraint_groups = groups or None

    def _node_mask(self, tree_mask: jnp.ndarray,
                   path_features: frozenset) -> jnp.ndarray:
        """Per-node mask: interaction constraints filtered by the
        feature-path, plus feature_fraction_bynode sampling."""
        mask = None
        if self._constraint_groups is not None:
            allowed = np.zeros(self.Fp, dtype=bool)
            for grp in self._constraint_groups:
                if path_features <= grp:
                    allowed[list(grp)] = True
            mask = allowed
        ffb = float(self.config.feature_fraction_bynode)
        if 0.0 < ffb < 1.0:
            n_real = self.dataset.num_features
            m2 = np.zeros(self.Fp, dtype=bool)
            k = max(1, int(round(n_real * ffb)))
            m2[self._ff_rng.choice(n_real, k, replace=False)] = True
            mask = m2 if mask is None else (mask & m2)
        if mask is None:
            return tree_mask
        return tree_mask & jnp.asarray(mask)

    def _needs_per_node_masks(self) -> bool:
        return (self._constraint_groups is not None
                or 0.0 < float(self.config.feature_fraction_bynode) < 1.0)

    def _draw_feature_mask(self) -> np.ndarray:
        """Host ``bool[Fp]`` column mask of one tree: per-tree column
        sampling (reference: ColSampler,
        src/treelearner/col_sampler.hpp:20) over the dataset's real
        features, the padding columns off, and the interaction
        constraints' allowed set (the root scan may only use features
        inside some group, ColSampler::SetUsedFeatureByNode). The one
        draw of every learner: the host RNG sequence is part of the
        bit-parity contract between them."""
        n_real = self.dataset.num_features
        ff = float(self.config.feature_fraction)
        mask = np.zeros(self.Fp, dtype=bool)
        mask[:n_real] = True
        if 0.0 < ff < 1.0:
            k = max(1, int(round(n_real * ff)))
            mask[:] = False
            mask[self._ff_rng.choice(n_real, k, replace=False)] = True
        if self._constraint_groups is not None:
            allowed = np.zeros(self.Fp, dtype=bool)
            for grp in self._constraint_groups:
                allowed[list(grp)] = True
            mask &= allowed
        return mask

    def _place_feature_mask(self, mask: np.ndarray) -> jnp.ndarray:
        """The drawn mask on the device, where the learner's programs
        take it (the mesh learners replicate it)."""
        return jnp.asarray(mask)

    def _sample_features(self) -> jnp.ndarray:
        return self._place_feature_mask(self._draw_feature_mask())

    def _tree_feature_mask(self) -> jnp.ndarray:
        """``_sample_features`` as every learner's ``train`` calls it:
        the draw and the upload under the host span
        ``tree::sample_features``, and while the stage timer is on the
        sampled columns counted beside all of them
        (``sample/cols_in_mask``, ``sample/cols_total``: nothing skips
        a masked column yet, the histograms visit every one)."""
        with obs.scope("tree::sample_features"):
            mask = self._draw_feature_mask()
            if obs.enabled:
                obs.inc("sample/cols_in_mask", int(mask.sum()))
                obs.inc("sample/cols_total", self.dataset.num_features)
            return self._place_feature_mask(mask)

    # ------------------------------------------------------------------
    def _init_quantization(self, qbits: int, config, max_rows: int
                           ) -> None:
        """Quantized-gradient mode state (ops/quantize.py), shared by
        the serial and mesh learners: the static per-row magnitude cap
        (overflow discipline vs the histogram accumulator), the row
        dtype, and the per-tree PRNG seed for stochastic rounding.
        ``self._qscale`` always holds the CURRENT tree's (g, h) scales —
        ones in exact mode — so every step adapter can pass it
        unconditionally."""
        self._quantized = bool(qbits)
        self._qs_ones = jnp.ones(2, dtype=jnp.float32)
        self._qscale = self._qs_ones
        if not self._quantized:
            return
        from ..ops.quantize import (effective_quant_max, published_levels,
                                    quant_dtype, quant_warn_capped)
        bins = getattr(config, "grad_quant_bins_given", lambda: 0)()
        if bins:
            # LightGBM's own levels (num_grad_quant_bins given)
            self._qmax = published_levels(
                bins, getattr(config, "stochastic_rounding", True), qbits,
                max_rows)
        else:
            self._qmax = effective_quant_max(qbits, max_rows)
            quant_warn_capped(qbits, self._qmax, max_rows)
        self._qdtype = quant_dtype(qbits)
        self._quant_seed = int(getattr(config, "seed", 0)) & 0x7FFFFFFF
        # base key staged once at setup: a per-tree PRNGKey(seed) would
        # be an implicit scalar transfer inside the training loop
        self._quant_base_key = jax.random.PRNGKey(self._quant_seed)
        # device-side tree counter: the per-tree fold-in value now
        # advances ON DEVICE (ops/quantize.tree_key), so steady-state
        # training performs zero per-tree seed transfers (each new
        # tree number used to be a fresh dev_u32 device_put). The host
        # mirror below tracks the same sequence without ever reading
        # the device value back — it exists only to ASSERT the counter
        # stays in lockstep with the callers' tree numbering.
        from ..utils.scalars import dev_u32
        self._quant_ctr = dev_u32(0)
        self._quant_ctr_host = 0

    def _quantize_stage(self, grad, hess, ind, tree_no: int,
                        ordered=None):
        """Discretize one tree's (grad, hess, in-bag) to integer rows
        (``ordered``: the rows the grower orders, ``_quantize_gh``).
        The draw runs on the UNPADDED [N] vectors with a per-tree
        fold-in key, so learners with different row/feature padding
        (serial pads rows to 4096s, meshes to the device count) produce
        BIT-IDENTICAL quantized rows — the padding-invariance contract
        make_rand_bins established for extra_trees. The key derives
        from the device-side counter (``tree_key``); the assert pins
        its sequence to the caller's ``tree_no`` (1, 2, ...) — a
        caller off the one-call-per-tree cadence would otherwise
        silently shift every later stochastic draw."""
        from ..ops.quantize import quantize_gh, tree_key
        key, self._quant_ctr = tree_key(self._quant_base_key,
                                        self._quant_ctr)
        self._quant_ctr_host += 1
        assert self._quant_ctr_host == tree_no, \
            "quantize tree counter desynced from tree numbering " \
            "(%d != %d)" % (self._quant_ctr_host, tree_no)
        self._count_discretized(1, int(grad.shape[0]))
        return quantize_gh(grad, hess, ind, key, self._qmax,
                           self._qdtype, ordered)

    @staticmethod
    def _count_discretized(trees: int, rows: int) -> None:
        """``quant/trees`` and ``quant/rows_discretized``: the trees whose
        gradients went through the discretizer and the rows it rounded."""
        obs.inc("quant/trees", trees)
        obs.inc("quant/rows_discretized", trees * rows)

    # ------------------------------------------------------------------
    def _make_cegb_fetched(self, rows: int) -> jnp.ndarray:
        """[rows, Fp] zeros for the lazy-penalty fetched matrix; mesh
        learners override to create it row-sharded."""
        return jnp.zeros((rows, self.Fp), dtype=jnp.float32)

    def _init_cegb(self, config) -> None:
        """CEGB setup (reference: CostEfficientGradientBoosting::IsEnable
        + Init, cost_effective_gradient_boosting.hpp:27-68). The
        used-features vector and (lazy mode) the per-(row, feature)
        fetched matrix persist across trees, like the reference's
        is_feature_used_in_split_ / feature_used_in_data_ members."""
        coupled = list(config.cegb_penalty_feature_coupled or [])
        lazy = list(config.cegb_penalty_feature_lazy or [])
        self._cegb_enabled = (config.cegb_tradeoff < 1.0
                              or config.cegb_penalty_split > 0.0
                              or bool(coupled) or bool(lazy))
        if not self._cegb_enabled:
            return
        if self._extra_trees:
            log.warning("extra_trees is ignored when CEGB is enabled")
        n_total = self.dataset.num_total_features
        for name, vec in (("cegb_penalty_feature_coupled", coupled),
                          ("cegb_penalty_feature_lazy", lazy)):
            if vec and len(vec) != n_total:
                log.fatal("%s should be the same size as feature number "
                          "(%d vs %d)" % (name, len(vec), n_total))

        def to_inner(vec):
            out = np.zeros(self.Fp, dtype=np.float32)
            if vec:
                for j in range(self.dataset.num_features):
                    out[j] = vec[self.dataset.real_feature_index(j)]
            return jnp.asarray(out)

        self._cegb_coupled = to_inner(coupled)
        self._cegb_lazy = to_inner(lazy)
        self._cegb_has_lazy = bool(lazy) and any(v != 0 for v in lazy)
        self._cegb_used = jnp.zeros(self.Fp, dtype=bool)
        if self._cegb_has_lazy:
            if self.R * self.Fp > 3 * 10**8:
                log.warning("cegb_penalty_feature_lazy tracks a "
                            "[rows x features] matrix (%.1f GB)"
                            % (self.R * self.Fp * 4 / 2**30))
            self._cegb_fetched = self._make_cegb_fetched(self.R)
        else:
            self._cegb_fetched = self._make_cegb_fetched(1)

    # ------------------------------------------------------------------
    def _init_monotone(self, config) -> None:
        """intermediate/advanced monotone methods route through the
        host-tracked stepwise path (reference: the LeafConstraintsBase
        hierarchy, monotone_constraints.hpp)."""
        self._mono_tracker = None
        method = str(config.monotone_constraints_method)
        mc = self.dataset.monotone_constraints
        has_mono = mc is not None and any(int(v) != 0 for v in mc)
        if not has_mono or method == "basic":
            return
        if self._cegb_enabled:
            log.warning("CEGB takes precedence over "
                        "monotone_constraints_method=%s; monotone "
                        "constraints run in basic mode" % method)
            return
        if not self._supports_intermediate:
            log.warning("monotone_constraints_method=%s degrades to "
                        "'basic' under the voting-parallel learner (no "
                        "per-leaf histogram store to rescan)" % method)
            return
        if self._extra_trees:
            log.warning("extra_trees is ignored under "
                        "monotone_constraints_method=%s" % method)
        n_real = self.dataset.num_features
        mono_inner = np.zeros(self.Fp, dtype=np.int8)
        mono_inner[:n_real] = np.asarray(mc, dtype=np.int8)[:n_real]
        if method == "advanced":
            from .monotone import AdvancedMonotoneTracker
            num_bin = np.ones(self.Fp, dtype=np.int64)
            nbpf = self.dataset.num_bin_per_feature
            num_bin[:len(nbpf)] = nbpf
            self._mono_tracker = AdvancedMonotoneTracker(
                self.L, mono_inner, num_bin, self.B)
        else:
            from .monotone import IntermediateMonotoneTracker
            self._mono_tracker = IntermediateMonotoneTracker(self.L,
                                                             mono_inner)


def _cegb_penalty(params, count, used, coupled, unfetched, lazy):
    """Per-feature CEGB gain penalty for scanning one leaf (reference:
    CostEfficientGradientBoosting::DeltaGain,
    cost_effective_gradient_boosting.hpp:80-99): split penalty scaled by
    leaf size + coupled penalty for model-new features + lazy per-row
    fetch cost for rows that have not used the feature yet."""
    pen = params.cegb_penalty_split * count + coupled * (~used)
    if lazy is not None:
        pen = pen + lazy * unfetched
    return params.cegb_tradeoff * pen


# ----------------------------------------------------------------------
# Host-side training drivers. Each steers per-split device steps through
# the learner's adapter methods; the loops are identical for the serial
# and mesh learners (the reference runs one loop too — the learners only
# differ below FindBestSplits, serial_tree_learner.cpp:159).
# ----------------------------------------------------------------------

def train_cegb(learner, tree, gh, feature_mask):
    """CEGB growth: one host round-trip per split so penalties track
    the evolving used/fetched state (reference: the DeltaGain calls
    inside FindBestSplitsFromHistograms, serial_tree_learner.cpp:375+)."""
    if getattr(learner, "_forced", None) is not None \
            or learner._constraint_groups is not None:
        log.warning("CEGB runs without forced splits / per-node "
                    "feature masks")
    state, rec = learner._cegb_root(gh, feature_mask)
    # jaxlint: disable=JLT001 -- CEGB is a host-stepped driver: the
    # per-feature penalty depends on host used/fetched state, so one
    # sync per split is the documented contract of this mode
    pending = jax.device_get(rec)
    for k in range(1, learner.L):
        if not record_is_valid(pending):
            break
        leaf = int(pending.leaf)
        apply_split_record(tree, learner.dataset, pending)
        allowed = learner._splittable(int(tree.leaf_depth[leaf]))
        state, rec = learner._cegb_step(state, leaf, k, allowed,
                                        feature_mask)
        # jaxlint: disable=JLT001 -- per-split sync (CEGB host loop)
        pending = jax.device_get(rec)
    return state


def train_monotone(learner, tree, gh, feature_mask, rand_seed):
    """monotone_constraints_method=intermediate/advanced growth:
    stepwise with host-tracked bounds + contiguous-leaf rescans
    (reference: SerialTreeLearner::Split → constraints_->Update →
    RecomputeBestSplitForLeaf, serial_tree_learner.cpp:702-710).

    The advanced method additionally recomputes both fresh children
    with their per-(feature, bin) constraint arrays (the reference's
    lazily-recomputed AdvancedLeafConstraints,
    monotone_constraints.hpp:856) — the scalar-bound candidates from
    the shared step are overwritten by an ``_adv_scan`` per child."""
    from .monotone import AdvancedMonotoneTracker

    tracker = learner._mono_tracker
    advanced = isinstance(tracker, AdvancedMonotoneTracker)
    tracker.reset()
    if getattr(learner, "_forced", None) is not None:
        log.warning("forced splits are ignored under "
                    "monotone_constraints_method=%s"
                    % learner.config.monotone_constraints_method)
    if learner._constraint_groups is not None:
        log.warning("interaction constraints are ignored under "
                    "monotone_constraints_method=%s"
                    % learner.config.monotone_constraints_method)
    state, rec = learner._mono_root(gh, feature_mask, rand_seed)
    # jaxlint: disable=JLT001 -- intermediate/advanced monotone growth
    # is host-stepped (bound propagation walks the host tree); one
    # sync per split is the mode's documented contract
    pending = jax.device_get(rec)
    gains_h = None
    leaf_sums: dict = {}
    for k in range(1, learner.L):
        if not record_is_valid(pending):
            break
        leaf = int(pending.leaf)
        f_inner = int(pending.feature)
        mono_type = int(tracker.mono[f_inner])
        if leaf == 0 and 0 not in leaf_sums:
            leaf_sums[0] = (
                float(pending.left_sum_grad)
                + float(pending.right_sum_grad),
                float(pending.left_sum_hess)
                + float(pending.right_sum_hess),
                float(pending.left_count)
                + float(pending.right_count),
                float(pending.left_total_count)
                + float(pending.right_total_count))
        tracker.before_split(tree, leaf, mono_type)
        apply_split_record(tree, learner.dataset, pending)
        lo, ro = float(pending.left_output), \
            float(pending.right_output)
        applied_numerical = not bool(pending.is_categorical)
        if advanced:
            tracker.apply_split_outputs(leaf, k, mono_type, lo, ro,
                                        applied_numerical)
            bounds = (-np.inf, np.inf, -np.inf, np.inf)
        else:
            bounds = tracker.child_bounds(leaf, mono_type, lo, ro)
            tracker.apply_split(tree, leaf, k, bounds)
        leaf_sums[leaf] = (float(pending.left_sum_grad),
                           float(pending.left_sum_hess),
                           float(pending.left_count),
                           float(pending.left_total_count))
        leaf_sums[k] = (float(pending.right_sum_grad),
                        float(pending.right_sum_hess),
                        float(pending.right_count),
                        float(pending.right_total_count))
        allowed = learner._splittable(int(tree.leaf_depth[leaf]))
        applied_tbin = int(pending.threshold_bin)
        state, rec, gains_d = learner._mono_step(
            state, leaf, k, allowed, feature_mask, bounds)
        if advanced:
            # overwrite both children's candidates with the
            # per-threshold-constrained scan
            for child in (leaf, k):
                d = int(tree.leaf_depth[child])
                arrs = tracker.leaf_bound_arrays(tree, child)
                state, rec, gains_d = learner._adv_scan(
                    state, child, leaf_sums[child], arrs, d,
                    learner._splittable(d), feature_mask)
        # jaxlint: disable=JLT001 -- per-split sync (monotone host loop)
        pending, gains_h = jax.device_get((rec, gains_d))
        # propagate to contiguous leaves + rescan them
        upd = tracker.leaves_to_update(
            tree, k, f_inner, applied_tbin, lo, ro,
            applied_numerical,
            lambda l: (l <= k and np.isfinite(gains_h[l])))
        for l in upd:
            allowed_l = learner._splittable(int(tree.leaf_depth[l]))
            if advanced:
                arrs = tracker.leaf_bound_arrays(tree, l)
                state, rec, gains_d = learner._adv_scan(
                    state, l, leaf_sums[l], arrs,
                    int(tree.leaf_depth[l]), allowed_l, feature_mask)
            else:
                emin, emax = tracker.entries[l]
                state, rec, gains_d = learner._mono_rescan(
                    state, l, leaf_sums[l], (emin, emax),
                    int(tree.leaf_depth[l]), allowed_l, feature_mask)
        if upd:
            # jaxlint: disable=JLT001 -- re-sync after constrained
            # rescans of updated leaves (monotone host loop)
            pending, gains_h = jax.device_get((rec, gains_d))
    return state


def train_stepwise(learner, tree, state, rec, feature_mask, rand_seed=0):
    """One host round-trip per split — needed when per-node feature
    masks depend on the host-side feature path."""
    # jaxlint: disable=JLT001 -- per-node feature masks are computed
    # from the host-side feature path, so this driver syncs per split
    # by design (its docstring is the contract)
    pending = jax.device_get(rec)
    paths = {0: frozenset()}
    for k in range(1, learner.L):
        if not record_is_valid(pending):
            break
        leaf = int(pending.leaf)
        f = int(pending.feature)
        apply_split_record(tree, learner.dataset, pending)
        allowed = learner._splittable(int(tree.leaf_depth[leaf]))
        paths[leaf] = paths[k] = paths.get(leaf, frozenset()) | {f}
        mask_left = learner._node_mask(feature_mask, paths[leaf])
        mask_right = learner._node_mask(feature_mask, paths[k])
        state, rec = learner._node_step(state, leaf, k, allowed,
                                        mask_left, mask_right, rand_seed)
        # jaxlint: disable=JLT001 -- per-split sync (stepwise host loop)
        pending = jax.device_get(rec)
    return state
