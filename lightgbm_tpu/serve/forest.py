"""StackedForest: the whole forest as one device dispatch.

Training-side device prediction (ops/predict.py ``DeviceTree``) walks one
tree at a time over dataset-binned rows — fine for per-iteration valid
scoring, wrong shape for serving: T trees mean T dispatches and the rows
arrive as raw floats, not bins. This module packs ALL T trees' flat node
arrays into single ``[T, NI_max]`` arrays so a single jitted program
quantizes raw rows and walks the entire forest via a vmapped lockstep
traversal (reference analogue: the CUDA build's whole-model
``AddPredictionToScoreKernel``; see also arXiv:1806.11248 / 2011.02022 —
inference throughput comes from batching the forest, not the tree).

Quantization is derived from the model itself: every numeric node's real
threshold is (by construction) one of the feature's BinMapper
``bin_upper_bound`` values, so the per-feature sorted unique threshold
set IS the model's bin grid. Thresholds are stored as the largest f32
<= t ("round-down f32"), which makes every device decision EXACT for
f32-representable inputs:

    v <= t  (host, f64)  ⟺  v <= rd32(t)  (device, f32)

because rd32(t) is the largest f32 not above t and v is itself an f32.
``bin(v) = #{thresholds < v}`` then reduces each node decision to an
integer compare ``bin <= rank(threshold)``, and NaN / zero-as-missing
semantics are folded into sentinel bins during quantization (matching
``models/tree.py _decide`` per-node semantics; per-feature missing types
are validated to be consistent — a model that mixes them on one feature
is rejected and served by the host path instead).

The bins matrix the walk gathers from is COMPACTED to the features the
forest actually splits on ([n, U], U = #used features) — on wide sparse
models (EFB-trained one-hot data) that cuts the walk's gather width by
the sparsity factor. With ``lut=True`` (auto-enabled for wide sparse
models) every node additionally becomes a boolean LUT row over its
feature's bin space — one gather decides numeric, categorical, and
missing semantics alike (the "LUT node" encoding; docs/SERVING.md).

**f64 requests** no longer fall back to the host walk: ``encode_dd``
splits each f64 value into a double-double pair (round-down f32 "hi" +
an exact int32 residual rank "lo"), thresholds are packed the same way,
and a lexicographic pair count reproduces the host's f64 comparisons
bit-for-bit (exact whenever |value| is not in the f32-subnormal range,
i.e. always in practice).

**Linear-leaf models** (``linear_tree``) pack their per-leaf
const/coeff/feature arrays alongside the node arrays, so they ride the
device fast path too: the device computes leaf ids (and, on the f32
throughput path, the linear values); the bit-exact ``predict`` /
``predict_raw`` contract accumulates the linear values on host in f64
in the same per-tree order as ``GBDT.predict_raw``.

``place(device)`` returns a copy with every array committed to one
device — the replication primitive serve/replicate.py and the
multi-replica PredictServer build on. Placed copies share the module's
jitted programs (same shapes → zero extra traces per replica).
"""
from __future__ import annotations

import copy as _copy
import threading
from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from ..io.binning import MissingType, kZeroThreshold
from ..models.tree import Tree, kCategoricalMask, kDefaultLeftMask
from ..ops.predict import (LinearLeaves, QuantizerTables, QuantizerTablesDD,
                           StackedNodes, stacked_forest_leaves,
                           stacked_forest_leaves_dd, stacked_forest_raw,
                           stacked_forest_raw_dd)
from ..utils import next_pow2


def round_down_f32(x) -> np.ndarray:
    """Largest float32 <= x (elementwise). The quantizer's exactness
    hinges on this rounding direction — see module docstring."""
    x = np.asarray(x, dtype=np.float64)
    with np.errstate(over="ignore"):  # |x| > f32 max rounds to ±inf,
        x32 = x.astype(np.float32)    # then steps down to ±f32 max
        too_big = x32.astype(np.float64) > x
        return np.where(too_big,
                        np.nextafter(x32, np.float32(-np.inf)),
                        x32).astype(np.float32)


# the double-double residual rank: the f64s inside one f32 gap
# [hi, next32(hi)) sit on a 2^29-step grid (53 - 24 mantissa bits), so
# lo = (v - hi) / (gap / 2^29) is an EXACT int32 for normal-range hi
kDDSteps = float(2 ** 29)


def _dd_pair(x: np.ndarray):
    """Split f64 values into (hi: round-down f32, lo: exact int32
    residual rank). Monotone and injective on the f64s the pair can
    resolve; exact for every value whose f32 round-down is normal."""
    x = np.asarray(x, dtype=np.float64)
    hi = round_down_f32(x)
    hi64 = hi.astype(np.float64)
    with np.errstate(invalid="ignore", over="ignore", divide="ignore"):
        gap = (np.nextafter(hi, np.float32(np.inf)).astype(np.float64)
               - hi64)
        finite = np.isfinite(x) & np.isfinite(hi64) & (gap > 0)
        scale = np.where(finite, kDDSteps / np.where(gap > 0, gap, 1.0),
                         0.0)
        res = np.where(finite, x - hi64, 0.0) * scale
        lo = np.floor(np.where(np.isfinite(res), res, 0.0)) \
            .astype(np.int32)
    return hi, lo


def f32_exact(X: np.ndarray) -> bool:
    """True when every finite value of X survives an f32 round-trip —
    THE dd-vs-f32 routing predicate, shared by ``StackedForest._route``
    and ``BucketedPredictor.predict`` so the bucket key and the program
    actually dispatched can never disagree."""
    return bool(np.all((X.astype(np.float32).astype(np.float64) == X)
                       | np.isnan(X)))


_KIND_NONE, _KIND_NUM, _KIND_CAT = 0, 1, 2


class StackedForest:
    """Immutable packed forest + quantizer tables (device-resident)."""

    def __init__(self, models: List[Tree], num_tree_per_iteration: int = 1,
                 num_features: Optional[int] = None, objective=None,
                 average_output: bool = False, lut="auto"):
        models = list(models)
        if not models:
            raise ValueError("StackedForest needs at least one tree")
        K = max(int(num_tree_per_iteration), 1)
        if len(models) % K != 0:
            raise ValueError("len(models)=%d is not a multiple of "
                             "num_tree_per_iteration=%d" % (len(models), K))
        if num_features is None:
            num_features = 1 + max(
                (int(t.split_feature[:t.num_internal].max())
                 for t in models if t.num_internal > 0), default=0)
        F = max(int(num_features), 1)
        self.num_trees = len(models)
        self.num_classes = K
        self.num_features = F
        self.objective = objective
        self.average_output = bool(average_output)
        self.has_linear = any(t.is_linear for t in models)

        # --- per-feature scan: kind, missing type, threshold set --------
        kind = np.zeros(F, dtype=np.int8)
        missing = np.full(F, -1, dtype=np.int8)
        thresholds: List[List[float]] = [[] for _ in range(F)]
        cat_nodes: List[tuple] = []  # (tree_idx, node, cat_idx)
        for ti, tree in enumerate(models):
            dt = tree.decision_type
            for node in range(tree.num_internal):
                f = int(tree.split_feature[node])
                if f >= F:
                    raise ValueError("node feature %d out of range (%d)"
                                     % (f, F))
                bits = int(dt[node])
                want = _KIND_CAT if bits & kCategoricalMask else _KIND_NUM
                if kind[f] not in (_KIND_NONE, want):
                    raise ValueError(
                        "feature %d has both numeric and categorical "
                        "splits; cannot build a stacked quantizer" % f)
                kind[f] = want
                if want == _KIND_CAT:
                    cat_nodes.append((ti, node,
                                      int(tree.threshold_in_bin[node])))
                    continue
                m = (bits >> 2) & 3
                m = min(m, MissingType.NAN)
                if missing[f] not in (-1, m):
                    raise ValueError(
                        "feature %d mixes missing types across nodes; "
                        "cannot quantize once per row" % f)
                missing[f] = m
                t = float(tree.threshold[node])
                if not np.isnan(t):
                    thresholds[f].append(t)

        # --- used-feature compaction ------------------------------------
        # the walk only ever gathers columns the forest splits on: the
        # bins matrix is [n, U] over this list, not [n, F] — the gather
        # width cut for wide sparse (EFB-style one-hot) models
        used = sorted(int(f) for f in np.nonzero(kind != _KIND_NONE)[0])
        if not used:
            used = [0]
        col_of = {f: u for u, f in enumerate(used)}
        U = len(used)
        k_used = kind[used]
        m_used = missing[used]
        self._h_kind = kind          # full-F host mirrors (encode_dd)
        self._h_missing = missing

        # --- quantizer tables (f32 grid + exact f64 dd grid) ------------
        thr32 = [np.unique(round_down_f32(np.asarray(thresholds[f])))
                 if thresholds[f] else np.zeros(0, dtype=np.float32)
                 for f in used]
        thr64 = [np.unique(np.asarray(thresholds[f], dtype=np.float64))
                 if thresholds[f] else np.zeros(0, dtype=np.float64)
                 for f in used]
        M = max(1, max((len(u) for u in thr32), default=1))
        M64 = max(1, max((len(u) for u in thr64), default=1))
        thr = np.full((U, M), np.inf, dtype=np.float32)
        for u, vals in enumerate(thr32):
            thr[u, :len(vals)] = vals
        thr_hi = np.full((U, M64), np.inf, dtype=np.float32)
        thr_lo = np.zeros((U, M64), dtype=np.int32)
        for u, vals in enumerate(thr64):
            hi_u, lo_u = _dd_pair(vals)
            thr_hi[u, :len(vals)] = hi_u
            thr_lo[u, :len(vals)] = lo_u
        vmax = max((models[ti].cat_value_words(ci) * 32 - 1
                    for ti, _, ci in cat_nodes), default=-1)
        vmax = max(vmax, 0)
        # shared LUT over category values; row 0 (non-cat nodes) and the
        # vmax+1 column (out-of-range/NaN values) are all-False == go
        # right. The last TWO columns are reserved for the walk's
        # NaN/zero sentinel remap (dead for compare-encoded cat nodes).
        cat_lut = np.zeros((len(cat_nodes) + 1, vmax + 4), dtype=bool)
        cat_slot_of = {}
        for slot, (ti, node, ci) in enumerate(cat_nodes, start=1):
            cat_lut[slot, :vmax + 1] = models[ti].cat_value_mask(ci, vmax)
            cat_slot_of[(ti, node)] = slot

        # --- stacked node arrays ---------------------------------------
        T = len(models)
        NI = next_pow2(max((t.num_internal for t in models), default=1))
        NL = next_pow2(max(t.num_leaves for t in models))
        feat = np.zeros((T, NI), dtype=np.int32)
        tbin = np.full((T, NI), -1, dtype=np.int32)
        tbin_dd = np.full((T, NI), -1, dtype=np.int32)
        dleft = np.zeros((T, NI), dtype=bool)
        left = np.full((T, NI), ~0, dtype=np.int32)
        right = np.full((T, NI), ~0, dtype=np.int32)
        is_cat = np.zeros((T, NI), dtype=bool)
        cat_slot = np.zeros((T, NI), dtype=np.int32)
        leaf_f32 = np.zeros((T, NL), dtype=np.float32)
        leaf_f64 = np.zeros((T, NL), dtype=np.float64)
        depth = 0
        n_internal_total = sum(t.num_internal for t in models)
        if lut == "auto":
            # wide sparse models (most features never split on) are
            # where the unified LUT walk pays for its table
            lut = F >= 32 and 2 * U <= F
        self.lut_nodes = bool(lut)
        if self.lut_nodes:
            W = max(M + 1, vmax + 2) + 2
            node_lut = np.zeros((n_internal_total + 1, W), dtype=bool)
            lut_slot = np.zeros((T, NI), dtype=np.int32)
            next_slot = 1
        for ti, tree in enumerate(models):
            ni = tree.num_internal
            nl = tree.num_leaves
            leaf_f64[ti, :nl] = tree.leaf_value[:nl]
            leaf_f32[ti, :nl] = tree.leaf_value[:nl].astype(np.float32)
            depth = max(depth, tree.structure_depth())
            if ni == 0:
                continue  # stump: padded root falls through to leaf 0
            dt = tree.decision_type[:ni]
            feat[ti, :ni] = [col_of[int(f)]
                             for f in tree.split_feature[:ni]]
            dleft[ti, :ni] = (dt.astype(np.int64) & kDefaultLeftMask) != 0
            left[ti, :ni] = tree.left_child[:ni]
            right[ti, :ni] = tree.right_child[:ni]
            for node in range(ni):
                slot = cat_slot_of.get((ti, node))
                if self.lut_nodes:
                    ls = next_slot
                    next_slot += 1
                    lut_slot[ti, node] = ls
                if slot is not None:
                    is_cat[ti, node] = True
                    cat_slot[ti, node] = slot
                    if self.lut_nodes:
                        node_lut[ls, :vmax + 2] = cat_lut[slot, :vmax + 2]
                    continue
                dl = bool(int(dt[node]) & kDefaultLeftMask)
                t = float(tree.threshold[node])
                u = col_of[int(tree.split_feature[node])]
                if not np.isnan(t):
                    # tbin stays -1 for NaN: "v <= NaN" is always False
                    tbin[ti, node] = int(np.searchsorted(
                        thr32[u], round_down_f32(t), side="left"))
                    tbin_dd[ti, node] = int(np.searchsorted(
                        thr64[u], t, side="left"))
                if self.lut_nodes:
                    nb = len(thr32[u]) + 1
                    node_lut[ls, :nb] = (np.arange(nb)
                                         <= tbin[ti, node])
                    node_lut[ls, W - 2] = dl  # NaN sentinel column
                    node_lut[ls, W - 1] = dl  # zero sentinel column

        self.trips = next_pow2(max(depth, 1))
        self._leaf_value_host = leaf_f64
        self._models = models if self.has_linear else None
        nodes_cmp = StackedNodes(
            feat=jnp.asarray(feat), tbin=jnp.asarray(tbin),
            default_left=jnp.asarray(dleft), left=jnp.asarray(left),
            right=jnp.asarray(right), is_cat=jnp.asarray(is_cat),
            cat_slot=jnp.asarray(cat_slot),
            leaf_value=jnp.asarray(leaf_f32))
        if self.lut_nodes:
            # LUT encoding: every node is one gather into node_lut —
            # tbin/-1 + default_left/False keep the compare lanes inert
            self._nodes = nodes_cmp._replace(
                tbin=jnp.full((T, NI), -1, dtype=jnp.int32),
                default_left=jnp.zeros((T, NI), dtype=bool),
                is_cat=jnp.ones((T, NI), dtype=bool),
                cat_slot=jnp.asarray(lut_slot))
            self._cat_lut = jnp.asarray(node_lut)
        else:
            self._nodes = nodes_cmp
            self._cat_lut = jnp.asarray(cat_lut)
        # the dd walk always uses compare encoding (its bins live in the
        # f64 grid, whose ranks differ from the f32 grid whenever two
        # f64 thresholds collapse onto one f32)
        self._nodes_dd = nodes_cmp._replace(tbin=jnp.asarray(tbin_dd))
        self._cat_lut_dd = jnp.asarray(cat_lut)
        used_j = jnp.asarray(np.asarray(used, dtype=np.int32))
        self._qt = QuantizerTables(
            used=used_j,
            thresholds=jnp.asarray(thr),
            is_cat=jnp.asarray(k_used == _KIND_CAT),
            nan_feat=jnp.asarray((k_used == _KIND_NUM)
                                 & (m_used == MissingType.NAN)),
            zero_feat=jnp.asarray((k_used == _KIND_NUM)
                                  & (m_used == MissingType.ZERO)),
            vmax=jnp.asarray(np.int32(vmax)),
            zero_eps=jnp.asarray(round_down_f32(kZeroThreshold)))
        self._qt_dd = QuantizerTablesDD(
            used=used_j,
            thr_hi=jnp.asarray(thr_hi), thr_lo=jnp.asarray(thr_lo),
            is_cat=jnp.asarray(k_used == _KIND_CAT),
            nan_feat=jnp.asarray((k_used == _KIND_NUM)
                                 & (m_used == MissingType.NAN)),
            zero_feat=jnp.asarray((k_used == _KIND_NUM)
                                  & (m_used == MissingType.ZERO)),
            vmax=jnp.asarray(np.int32(vmax)))
        self._lin = self._pack_linear(models, NL) \
            if self.has_linear else None
        self._device = None           # None = follow the default device
        self._placed = {}
        self._place_lock = threading.Lock()

    # ------------------------------------------------------------------
    @staticmethod
    def _pack_linear(models, NL) -> LinearLeaves:
        C = max((len(t.leaf_coeff[leaf])
                 for t in models if t.is_linear
                 for leaf in range(t.num_leaves)
                 if t.leaf_features[leaf]), default=1)
        parts = zip(*(t.linear_arrays(NL, max(C, 1)) for t in models))
        const, coeff, lfeat, valid, has = (np.stack(p) for p in parts)
        return LinearLeaves(
            const=jnp.asarray(const), coeff=jnp.asarray(coeff),
            feat=jnp.asarray(lfeat), valid=jnp.asarray(valid),
            has=jnp.asarray(has))

    # ------------------------------------------------------------------
    @classmethod
    def from_gbdt(cls, gbdt, start_iteration: int = 0,
                  num_iteration: int = -1, lut="auto") -> "StackedForest":
        """Pack a trained or text-loaded GBDT (same tree slice as
        ``GBDT.predict_raw``)."""
        gbdt = getattr(gbdt, "inner", gbdt)  # accept a Booster too
        models = gbdt._used_models(start_iteration, num_iteration)
        return cls(models, gbdt.num_tree_per_iteration,
                   gbdt.max_feature_idx + 1, objective=gbdt.objective,
                   average_output=gbdt.average_output, lut=lut)

    # ------------------------------------------------------------------
    def place(self, device) -> "StackedForest":
        """A copy of this forest with every device array committed to
        ``device`` (cached per device id) — the replication primitive.
        Placed copies dispatch through the SAME module-level jitted
        programs, so N replicas add zero traces beyond the first."""
        if device is None:
            return self
        key = getattr(device, "id", None)
        if key is None:
            return self
        with self._place_lock:
            got = self._placed.get(key)
            if got is not None:
                return got
            import jax

            def put(tree):
                return jax.tree_util.tree_map(
                    lambda a: jax.device_put(a, device), tree)

            cp = _copy.copy(self)
            cp._nodes = put(self._nodes)
            cp._cat_lut = put(self._cat_lut)
            cp._qt = put(self._qt)
            cp._nodes_dd = put(self._nodes_dd)
            cp._cat_lut_dd = put(self._cat_lut_dd)
            cp._qt_dd = put(self._qt_dd)
            if self._lin is not None:
                cp._lin = put(self._lin)
            cp._device = device
            cp._placed = {}
            cp._place_lock = threading.Lock()
            self._placed[key] = cp
            return cp

    @property
    def device(self):
        """The device this placement is pinned to (None = default)."""
        return self._device

    # ------------------------------------------------------------------
    def _check_shape(self, X: np.ndarray) -> np.ndarray:
        if X.ndim == 1:
            X = X.reshape(1, -1)
        if X.shape[1] != self.num_features:
            raise ValueError(
                "X has %d features, model expects %d"
                % (X.shape[1], self.num_features))
        return X

    def _prep(self, X) -> np.ndarray:
        X = self._check_shape(np.asarray(X))
        # the f32 serving contract: rows are interpreted as float32
        # (the quantizer is exact for f32-representable values); f64
        # rows that exceed f32 precision route through encode_dd
        return np.ascontiguousarray(X, dtype=np.float32)

    def _route(self, X, dd=None):
        """("f32", X_f32) or ("dd", X_f64): f64 rows the f32 quantizer
        cannot represent exactly take the double-double device path.
        ``dd`` forces the mode (the bucket cache decides ONCE for a
        whole chunked batch and passes it down, so the bucket key and
        the dispatched program can never disagree); None re-derives it
        via :func:`f32_exact`."""
        X = self._check_shape(np.asarray(X))
        if dd is None:
            dd = X.dtype == np.float64 and not f32_exact(X)
        if dd:
            return "dd", np.ascontiguousarray(X, dtype=np.float64)
        return "f32", np.ascontiguousarray(X, dtype=np.float32)

    def encode_dd(self, X64: np.ndarray):
        """Host-side double-double row encoding: [n, F] f64 →
        (hi [n, F] f32, lo [n, F] i32). NaN is PRESERVED in ``hi`` for
        every column (the device quantizer substitutes the exact (0, 0)
        pair on non-NaN-missing numeric features itself — keeping the
        NaN visible lets the linear-leaf NaN-fallback mask see it, same
        as the f32 path's raw X); the only f64-exact decision resolved
        here is zero-as-missing, marked with the ``lo == -1`` sentinel
        (NaN behaves as 0.0 on those features, per the host's
        ``_decide``)."""
        X = np.asarray(X64, dtype=np.float64)
        kind, missing = self._h_kind, self._h_missing
        zerof = (kind == _KIND_NUM) & (missing == MissingType.ZERO)
        isnan = np.isnan(X)
        hi, lo = _dd_pair(X)
        zs = zerof[None, :] & (isnan
                               | (np.abs(np.where(isnan, 0.0, X))
                                  <= kZeroThreshold))
        lo = np.where(zs, np.int32(-1), lo)
        return hi, np.ascontiguousarray(lo)

    # ------------------------------------------------------------------
    def _leaves_device(self, X, dd=None):
        """[T, n] leaf ids on device (committed to this placement's
        device). Both transfers are EXPLICIT (device_put in, the caller
        device_gets out) so a warmed serving dispatch passes the
        transfer-guard sanitizer like the training loop does."""
        import jax
        mode, Xp = self._route(X, dd)
        if mode == "dd":
            hi, lo = self.encode_dd(Xp)
            hid = jax.device_put(hi, self._device)
            lod = jax.device_put(lo, self._device)
            return stacked_forest_leaves_dd(hid, lod, self._qt_dd,
                                            self._nodes_dd,
                                            self._cat_lut_dd, self.trips)
        Xd = jax.device_put(Xp, self._device)
        return stacked_forest_leaves(Xd, self._qt, self._nodes,
                                     self._cat_lut, self.trips)

    def leaves_device(self, X, dd=None):
        """[T, n] leaf ids ON device, no host sync — the refit replay's
        entry point (``boosting/refit.py:refit_model_device`` feeds
        these straight into per-leaf ``segment_sum`` reductions);
        :meth:`leaves` is the host-facing wrapper."""
        return self._leaves_device(X, dd)

    def leaves(self, X, dd=None) -> np.ndarray:
        """[n, T] leaf index of every row in every tree (one device
        dispatch for quantize + forest walk)."""
        import jax
        out = self._leaves_device(X, dd)
        # jaxlint: disable=JLT001 -- the serving boundary: leaf ids
        # leave the device exactly once per dispatch, by design
        return jax.device_get(out).T

    def predict_raw(self, X, dd=None) -> np.ndarray:
        """Raw scores, bit-identical to ``GBDT.predict_raw``: device leaf
        ids + host f64 accumulation in the same per-tree order (linear
        leaves evaluate their fits on host in f64 too)."""
        leaves = self.leaves(X, dd)
        n = leaves.shape[0]
        K = self.num_classes
        out = np.zeros((n, K), dtype=np.float64)
        lv = self._leaf_value_host
        if self.has_linear:
            from ..models.linear import linear_predict
            X64 = self._check_shape(np.asarray(X, dtype=np.float64))
            for i, tree in enumerate(self._models):
                if tree.is_linear:
                    out[:, i % K] += linear_predict(tree, X64,
                                                    leaves[:, i])
                else:
                    out[:, i % K] += lv[i][leaves[:, i]]
        else:
            for i in range(self.num_trees):
                out[:, i % K] += lv[i][leaves[:, i]]
        if self.average_output and self.num_trees:
            out /= max(self.num_trees // K, 1)
        return out[:, 0] if K == 1 else out

    def predict(self, X, raw_score: bool = False, dd=None) -> np.ndarray:
        """Transformed output, bit-identical to the host
        ``Booster.predict`` (same objective ``convert_output``)."""
        raw = self.predict_raw(X, dd)
        if raw_score or self.objective is None:
            return raw
        return self.objective.convert_output(raw)

    def predict_raw_device(self, X, dd=None) -> jnp.ndarray:
        """[n, K] f32 raw scores summed ON DEVICE — the serving
        throughput path (f32 accumulation: fast, not bit-identical to
        the host's f64 sum). Linear leaves evaluate on device in f32."""
        import jax
        mode, Xp = self._route(X, dd)
        if mode == "dd":
            hi, lo = self.encode_dd(Xp)
            hid = jax.device_put(hi, self._device)
            lod = jax.device_put(lo, self._device)
            out = stacked_forest_raw_dd(hid, lod, self._qt_dd,
                                        self._nodes_dd, self._cat_lut_dd,
                                        self.trips, self.num_classes,
                                        self._lin)
        else:
            Xd = jax.device_put(Xp, self._device)
            out = stacked_forest_raw(Xd, self._qt, self._nodes,
                                     self._cat_lut, self.trips,
                                     self.num_classes, self._lin)
        if self.average_output and self.num_trees:
            # RF-style averaging, same factor as the host predict_raw
            out = out / np.float32(
                max(self.num_trees // self.num_classes, 1))
        return out
