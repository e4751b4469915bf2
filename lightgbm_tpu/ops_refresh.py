"""Refresh a live learner's device-side split parameters after
``reset_parameter`` (reference: GBDT::ResetConfig →
TreeLearner::ResetConfig, serial_tree_learner.cpp). SplitParams fields are
traced values, so replacing the NamedTuple reuses the compiled kernels."""
from __future__ import annotations

from .ops.split import SplitParams


def refresh_learner_params(learner, config) -> None:
    learner.params = SplitParams.from_config(config)
    learner.max_depth = int(config.max_depth)
    if hasattr(learner, "_K"):
        learner._K = max(1, min(
            int(getattr(config, "tpu_frontier_splits", 8)),
            learner.L - 1))
    if hasattr(learner, "_rebind_compiled"):
        # sharded learner: max_depth and K are STATIC keys of its
        # cached finish/kfinish/spec programs — re-resolve them (a
        # stale binding would keep gating depth at the old max_depth)
        learner._rebind_compiled()
    # jitted step closures bake the old params as constants — drop them
    # so the next tree re-traces with the new values
    if hasattr(learner, "_step_cache"):
        learner._step_cache.clear()
    if hasattr(learner, "_root_impl"):
        # mesh learners: the per-instance jits bake params/max_depth as
        # constants — drop them; train()/the adapters rebuild lazily
        for attr in ("_root_fn", "_tree_fn", "_step_fn", "_cegb_root_fn",
                     "_mono_step_fn", "_mono_root_fn", "_adv_rescan_fn",
                     "_many_fn", "_many_multi_fn"):
            if hasattr(learner, attr):
                setattr(learner, attr, None)
