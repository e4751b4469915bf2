"""Row sampling strategies: bagging and GOSS.

TPU-native equivalents of the reference's ``SampleStrategy`` family
(reference: src/boosting/sample_strategy.cpp:12 factory,
src/boosting/bagging.hpp:26, src/boosting/goss.hpp:30). The reference
produces a compacted ``bag_data_indices`` list consumed by the learner;
dynamic-length index lists don't fit XLA's static shapes, so here a
strategy returns a full-length f32 in-bag indicator (0/1) plus possibly
rescaled (grad, hess) — the learner multiplies gradients by the indicator
and counts in-bag rows via its histogram count channel, which is the same
masked-row trick the CUDA learner's bagging path uses.

What the mask costs, and where the in-bag counts live. The grower
(treelearner/grow.py) ignores the bag: ``GrowState.order`` holds every
row, the smaller child is the one with fewer rows in all, and each
histogram pass, gather and window reorder visits the out-of-bag rows
too, whose gradient and hessian are zero. On a TPU v5e at 1,000,000 x
968 under GOSS (top 20% + 10% of the rest: the benchmark's cell
``bosch-train-goss``, PERF.md, PR 35) a sampled iteration takes 3.11 s
where an unsampled one takes 3.05 s: 13% of the rows the smaller
children's histogram passes visit carry weight
(``grow/hist_rows_in_bag`` over ``grow/hist_rows_bucketed``), and the
sampling itself is 1.5 ms, 1.4 of them ``lax.top_k``, which is a full
sort of the weights there (and 24 s of compiling). Users who set GOSS get
no speed from it until the grower learns to skip rows that carry no
weight. The in-bag indicator travels as channel 2 of ``gh``, so a split
record's ``left_count``/``right_count`` and a tree's
``internal_count``/``leaf_count`` (the model text's) are in-bag counts,
while ``left_total_count``/``right_total_count`` count every row; the
score update covers every row through ``leaf_of_row``. The sampling is
named ``obs_goss`` on the device clock, and while the stage timer is on
``sample/goss_trees`` counts the trees of the iterations GOSS sampled
(docs/OBSERVABILITY.md). Plain bagging's draw is ``obs_bag`` there, its
trees ``sample/bag_trees`` and its draws ``sample/bag_draws`` (one per
``bagging_freq`` iterations); with ``feature_fraction`` 0.8 beside
``bagging_fraction`` 0.8 (the cell ``bosch-train-subsample``, PR 37) 59%
of the row-features the smaller children's passes visit carry weight.

Draws happen ON DEVICE, keyed by ``fold_in(PRNGKey(bagging_seed),
draw_index)`` where the draw index is a pure function of the iteration
number (``iter // bagging_freq`` for bagging, the iteration itself for
GOSS). Stateless draws buy two things at once:

- the per-iteration looped path performs no host RNG draw and no
  host→device bag transfer (one jitted dispatch yields the device
  indicator), and checkpoint resume needs NO sampler state — the bag at
  iteration *i* is recomputed from (seed, i) bit-identically;
- the batched multi-iteration scan (``train_many``,
  parallel/data_parallel.py) computes the SAME fold-in inside the traced
  loop, so bagged runs batch with bit-identical indicators to the
  looped path (``apply_traced`` below is the scan-side entry).

The pre-pipelined implementation drew bags from a host MT19937 stream;
that sequence cannot be reproduced inside a traced scan, which is why
bagging used to force the per-iteration path (checkpoints of that era
carry the MT19937 state and are rejected by the current format version).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import log
from ..obs import compile as obs_compile
from ..obs.registry import registry as obs
from ..utils.scalars import dev_i32


def _bag_draw(base_key, draw_idx, frac, n: int):
    """[n] f32 in-bag indicator for one draw index: ``u < frac`` with
    ``u ~ U[0,1)`` under ``fold_in(base_key, draw_idx)``. ``frac`` is a
    scalar (plain bagging) or an [n] per-row vector (balanced pos/neg
    bagging). Integer key bits → exact compare: the indicator is
    BIT-deterministic, identical inside a traced scan and as its own
    dispatch. Named ``obs_bag`` on the device clock, in whichever program
    it is traced (``boost.bag_draw``, the scan body of ``train_many``)."""
    with jax.named_scope("obs_bag"):
        key = jax.random.fold_in(base_key, draw_idx)
        u = jax.random.uniform(key, (n,))
        return (u < frac).astype(jnp.float32)


bag_draw = obs_compile.instrument_jit("boost.bag_draw", _bag_draw,
                                      static_argnums=(3,))


class SampleStrategy:
    """No-op default: every row in bag."""

    is_hessian_change = False

    def __init__(self, config, num_data: int, num_tree_per_iteration: int):
        self.config = config
        self.num_data = num_data
        self.num_tree_per_iteration = num_tree_per_iteration

    def reset_metadata(self, metadata) -> None:
        pass

    def refresh_config(self, config) -> None:
        """Re-derive config-cached draw state after a mid-run
        ``reset_parameter`` (schedulable bagging params); the base
        strategy caches nothing."""
        self.config = config

    def bagging(self, iter_idx: int, grad: jnp.ndarray, hess: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
        """Returns (grad, hess, bag) — bag is None for all-rows."""
        return grad, hess, None

    # ------------------------------------------------------------------
    # Batched-scan protocol (parallel/data_parallel.py train_many): the
    # strategy's draw runs INSIDE the traced multi-iteration loop, keyed
    # on the traced iteration index — the same fold_in sequence
    # ``bagging`` consumes one dispatch at a time on the looped path.
    # ------------------------------------------------------------------
    def supports_device_draw(self) -> bool:
        """True when ``apply_traced`` reproduces ``bagging``'s draw from
        the iteration index alone (no host RNG, no cross-iteration
        state) — the eligibility bit ``GBDT.can_train_batched`` checks.
        A subclass that customizes ``bagging`` without providing a
        matching ``apply_traced`` AT THE SAME LEVEL (or deeper)
        DECLINES: an inherited traced draw — the base no-op, or a
        parent strategy's — would silently replace its sampling inside
        the scan."""
        cls = type(self)

        def defining(name):
            for c in cls.__mro__:
                if name in c.__dict__:
                    return c
            return SampleStrategy

        return issubclass(defining("apply_traced"), defining("bagging"))

    def apply_traced(self, iter_idx, grad, hess):
        """Traceable twin of :meth:`bagging`: ``iter_idx`` is a traced
        i32 scalar. Returns (grad, hess, ind) with ``ind`` None when
        every row is in bag."""
        return grad, hess, None

    # the scan-rebuild check (and jax's static-arg cache for jitted
    # methods) compares strategies by VALUE: config-identical strategies
    # must trace identically
    def _jit_key(self):
        return (self.num_data,)

    def __hash__(self):
        return hash((type(self), self._jit_key()))

    def __eq__(self, other):
        return (type(other) is type(self)
                and other._jit_key() == self._jit_key())

    def __ne__(self, other):
        return not self.__eq__(other)


class BaggingStrategy(SampleStrategy):
    """Random row subsampling every ``bagging_freq`` iterations
    (reference: bagging.hpp:26-110; balanced pos/neg variant at :88-103,
    :180-195). The indicator for iteration *i* depends only on
    ``(bagging_seed, i // bagging_freq)`` — see the module docstring."""

    def __init__(self, config, num_data, num_tree_per_iteration):
        super().__init__(config, num_data, num_tree_per_iteration)
        self.freq = max(int(config.bagging_freq), 1)
        self.balanced = (config.pos_bagging_fraction < 1.0
                         or config.neg_bagging_fraction < 1.0)
        # base key staged once at setup (a per-draw PRNGKey would be an
        # implicit scalar transfer inside the training loop)
        self._base_key = jax.random.PRNGKey(
            int(config.bagging_seed) & 0x7FFFFFFF)
        # plain bagging: scalar fraction; balanced: per-row [N] vector
        # built at reset_metadata from the labels
        self._frac = jnp.float32(config.bagging_fraction)
        self._is_pos: Optional[np.ndarray] = None
        # looped-path cache: the indicator is reused for freq iterations
        self._bag: Optional[jnp.ndarray] = None
        self._bag_draw_idx = -1

    def reset_metadata(self, metadata) -> None:
        if self.balanced:
            self._is_pos = np.asarray(metadata.label) > 0
            self._frac = self._balanced_frac()

    def _balanced_frac(self):
        frac = np.where(self._is_pos,
                        np.float32(self.config.pos_bagging_fraction),
                        np.float32(self.config.neg_bagging_fraction))
        return jnp.asarray(frac.astype(np.float32))

    def refresh_config(self, config) -> None:
        """A scheduled bagging_fraction/freq change takes effect at the
        next redraw window (the pre-refactor semantics: `_resample`
        read the live config at each freq boundary). The cached
        current-window bag stays valid — its draw index has not
        changed."""
        self.config = config
        self.freq = max(int(config.bagging_freq), 1)
        if self.balanced and getattr(self, "_is_pos", None) is not None:
            self._frac = self._balanced_frac()
        elif not self.balanced:
            self._frac = jnp.float32(config.bagging_fraction)

    def bagging(self, iter_idx, grad, hess):
        d = int(iter_idx) // self.freq
        redraw = self._bag is None or d != self._bag_draw_idx
        if redraw:
            self._bag = bag_draw(self._base_key, dev_i32(d), self._frac,
                                 self.num_data)
            self._bag_draw_idx = d
        if obs.enabled:
            # the trees of an iteration share one bag, which lasts
            # ``freq`` iterations
            obs.inc("sample/bag_trees", self.num_tree_per_iteration)
            if redraw:
                obs.inc("sample/bag_draws")
        return grad, hess, self._bag

    def apply_traced(self, iter_idx, grad, hess):
        d = (iter_idx // jnp.int32(self.freq)).astype(jnp.int32)
        ind = bag_draw(self._base_key, d, self._frac, self.num_data)
        return grad, hess, ind

    def _jit_key(self):
        # the balanced per-row fraction vector is label-derived; two
        # strategies agree iff seed + fractions + row count do (labels
        # are fixed per dataset, covered by num_data for this in-process
        # comparison)
        return (self.num_data, self.freq, self.balanced,
                int(self.config.bagging_seed),
                float(self.config.bagging_fraction),
                float(self.config.pos_bagging_fraction),
                float(self.config.neg_bagging_fraction))


class GOSSStrategy(SampleStrategy):
    """Gradient-based one-side sampling (reference: goss.hpp:30-165):
    keep the top ``top_rate`` rows by sum_k |grad_k * hess_k|, sample the
    rest with probability other_k/(cnt-top_k), amplify sampled small-grad
    rows' (grad, hess) by (cnt-top_k)/other_k. Skipped while
    iter < 1/learning_rate (goss.hpp:33). The per-iteration uniform draw
    keys on ``fold_in(PRNGKey(bagging_seed), iter)`` (module
    docstring), so the batched scan reproduces the looped sequence."""

    is_hessian_change = True

    def __init__(self, config, num_data, num_tree_per_iteration):
        super().__init__(config, num_data, num_tree_per_iteration)
        if config.top_rate + config.other_rate > 1.0:
            log.fatal("top_rate + other_rate must be <= 1.0 for GOSS")
        if config.top_rate <= 0.0 or config.other_rate <= 0.0:
            log.fatal("top_rate and other_rate must be positive for GOSS")
        if config.bagging_freq > 0 and config.bagging_fraction != 1.0:
            log.fatal("Cannot use bagging in GOSS")
        log.info("Using GOSS")
        self._base_key = jax.random.PRNGKey(
            int(config.bagging_seed) & 0x7FFFFFFF)
        self.top_k = max(1, int(num_data * config.top_rate))
        self.other_k = max(1, int(num_data * config.other_rate))
        self.warmup = int(1.0 / max(config.learning_rate, 1e-12))

    def refresh_config(self, config) -> None:
        """learning_rate is schedulable; the GOSS warm-up horizon reads
        it live (pre-refactor semantics computed 1/lr per call)."""
        self.config = config
        self.warmup = int(1.0 / max(config.learning_rate, 1e-12))

    def _jit_key(self):
        # covers every self-read of the jitted body (top_k/other_k are
        # num_data-derived) plus the draw sequence identity
        return (self.top_k, self.other_k,
                int(self.config.bagging_seed))

    @obs_compile.instrument_jit_method("boost.goss")
    def _goss(self, grad, hess, base_key, iter_idx):
        # grad/hess: [N] or [N, K]
        with jax.named_scope("obs_goss"):
            g2 = jnp.abs(grad * hess)
            w = g2 if g2.ndim == 1 else jnp.sum(g2, axis=1)
            n = w.shape[0]
            thresh = jax.lax.top_k(w, self.top_k)[0][-1]
            is_top = w >= thresh
            multiply = (n - self.top_k) / self.other_k
            prob = self.other_k / jnp.maximum(n - self.top_k, 1)
            u = jax.random.uniform(jax.random.fold_in(base_key, iter_idx),
                                   (n,))
            sampled = (~is_top) & (u < prob)
            bag = (is_top | sampled).astype(jnp.float32)
            scale = jnp.where(sampled, multiply, 1.0)
            if grad.ndim > 1:
                scale = scale[:, None]
            return grad * scale, hess * scale, bag

    def bagging(self, iter_idx, grad, hess):
        if iter_idx < self.warmup:
            return grad, hess, None
        if obs.enabled:
            # the trees of an iteration share one bag
            obs.inc("sample/goss_trees", self.num_tree_per_iteration)
        return self._goss(grad, hess, self._base_key, dev_i32(iter_idx))

    def apply_traced(self, iter_idx, grad, hess):
        g2, h2, bag = self._goss(grad, hess, self._base_key,
                                 iter_idx.astype(jnp.int32))
        # warm-up iterations pass gradients through untouched (the
        # looped path returns bag=None there; an all-ones indicator
        # stages identically)
        active = iter_idx >= jnp.int32(self.warmup)
        g = jnp.where(active, g2, grad)
        h = jnp.where(active, h2, hess)
        ind = jnp.where(active, bag, jnp.ones_like(bag))
        return g, h, ind


def create_sample_strategy(config, num_data: int,
                           num_tree_per_iteration: int) -> SampleStrategy:
    """reference: SampleStrategy::CreateSampleStrategy
    (src/boosting/sample_strategy.cpp:12): GOSS either as
    data_sample_strategy=goss or legacy boosting=goss."""
    if (config.data_sample_strategy == "goss"
            or config.boosting == "goss"):
        return GOSSStrategy(config, num_data, num_tree_per_iteration)
    balanced = (config.pos_bagging_fraction < 1.0
                or config.neg_bagging_fraction < 1.0)
    if ((config.bagging_fraction < 1.0 or balanced)
            and config.bagging_freq > 0):
        return BaggingStrategy(config, num_data, num_tree_per_iteration)
    return SampleStrategy(config, num_data, num_tree_per_iteration)
