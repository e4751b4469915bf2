"""The system under test, and nothing of the benchmark's own arithmetic.

The one module that imports ``lightgbm_tpu``: the entry a user calls
(``lgb.Dataset``, ``lgb.Booster``, ``Booster.update``, ``Booster.predict``,
``PredictServer.submit``), the state the checks read (the training rows' raw
scores), its counters (``jit_trace/<name>``) and its spans. The tests break
the timed path by patching the callee of ``Program.update`` or
``Scorer.submit`` underneath.
"""
from __future__ import annotations

import numpy as np


def enable_compile_cache() -> str:
    from lightgbm_tpu.utils.compile_cache import enable_compile_cache
    return enable_compile_cache()


def enable_spans() -> None:
    """The program's ``obs`` scopes open ``TraceAnnotation`` ranges only
    while its stage timer is on; the fencing mode stays off."""
    from lightgbm_tpu.obs.registry import registry
    registry.timer.enable()


def disable_spans() -> None:
    """Off again, so that the program prints no stage table at exit."""
    from lightgbm_tpu.obs.registry import registry
    registry.timer.disable()


def trace_counts() -> dict:
    from lightgbm_tpu.obs import compile as obs_compile
    return dict(obs_compile.trace_counts())


class Program:
    """One booster on one training table, as a user builds it."""

    def __init__(self, params: dict):
        self.params = dict(params)
        self.train_set = None
        self.valid_set = None
        self.booster = None

    def bin(self, X, y, X_valid=None, y_valid=None, **dataset_kw) -> None:
        """``dataset_kw``: what else the generator says a ``Dataset`` of
        this deployment needs (``categorical_feature``, ``group``, ...)."""
        import lightgbm_tpu as lgb
        self.train_set = lgb.Dataset(X, label=y, params=dict(self.params),
                                     **dataset_kw).construct()
        if X_valid is not None:
            self.valid_set = lgb.Dataset(
                X_valid, label=y_valid, reference=self.train_set).construct()

    def build(self) -> None:
        import lightgbm_tpu as lgb
        self.booster = lgb.Booster(params=dict(self.params),
                                   train_set=self.train_set)
        if self.valid_set is not None:
            self.booster.add_valid(self.valid_set, "test")

    def update(self) -> list:
        """One boosting iteration and, with a validation set, its
        evaluation, through the user's calls."""
        self.booster.update()
        return self.booster.eval_valid()

    def warm_validation_walk(self) -> list:
        """The validation rows walk each new tree in ``next_pow2(depth)``
        lockstep hops, one compiled program per power of two, so a tree
        deeper or shallower than the warm steps' trees would compile inside
        the window. Walk the last tree once at every hop count a tree of
        this many leaves can need (more hops than its depth leave the rows
        on their leaves); the outputs are dropped."""
        import copy
        inner = self.booster.inner
        tree = copy.deepcopy(inner.models[-1])
        # a tree of n leaves is at least log2(n) and at most n - 1 deep
        hops = []
        least = max(tree.num_leaves - 1, 1).bit_length()
        depth = 1 << (least - 1).bit_length()
        if not inner.valid_data:
            return hops
        while depth < 2 * max(tree.num_leaves - 1, 1):
            tree.leaf_depth[:tree.num_leaves] = depth
            for vd in inner.valid_data:
                out = vd._tree_outputs(tree, inner._bin_meta)
                if out is not None:
                    out.block_until_ready()
            hops.append(depth)
            depth *= 2
        return hops

    def scores(self) -> np.ndarray:
        """The training rows' raw scores now, on the host (waits for the
        device)."""
        return np.asarray(self.booster.inner.train_score,
                          dtype=np.float32).reshape(-1).copy()

    def wait(self) -> None:
        self.booster.inner.train_score.block_until_ready()

    def iterations(self) -> int:
        return int(self.booster.current_iteration)

    def learner_name(self) -> str:
        return type(self.booster.inner.learner).__name__

    def predict_raw(self, X: np.ndarray, num_iteration: int) -> np.ndarray:
        return np.asarray(self.booster.predict(
            X, num_iteration=num_iteration, raw_score=True,
            predict_on_device=False), dtype=np.float64)

    def model_text(self) -> str:
        return self.booster.model_to_string(num_iteration=-1)

    def free(self) -> None:
        self.booster = None
        self.train_set = None
        self.valid_set = None


class Scorer:
    """A model as a user serves it: LightGBM model text loaded through
    ``lgb.Booster(model_str=...)`` into a ``PredictServer``."""

    def __init__(self, model_text: str, server: dict):
        import lightgbm_tpu as lgb
        from lightgbm_tpu.serve.server import PredictServer
        self.booster = lgb.Booster(model_str=model_text)
        self.server = PredictServer(self.booster, **server)

    def submit(self, block: np.ndarray):
        """A ``Future`` of the block's answer, as a client gets it."""
        return self.server.submit(block)

    def stop(self) -> None:
        """Drains the queue and joins the server's worker."""
        self.server.stop()
        self.server = None
        self.booster = None
