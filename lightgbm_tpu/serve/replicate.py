"""Mesh replication for serving: per-device placement + one-program
row-sharded dispatch.

Two ways to put a device mesh behind the predict queue:

- **Replica placement** (what PredictServer uses): ``ReplicatedForest``
  places one ``StackedForest``'s stacked node arrays on every device
  (``StackedForest.place`` — explicit ``device_put``, cached per device)
  and per-replica dispatch workers drain one admission queue. Dispatch
  capacity scales with device count while the PR-10 overload semantics
  stay global.

- **Single sharded program**: ``predict_raw_sharded`` pads the row
  buffer to a multiple of the mesh size and runs ONE compiled program
  that shards rows across devices with the forest replicated — built
  through :func:`compile_predict_with_plan`, the ``compile_step_with_plan``
  pattern: ``pjit``-style explicit shardings when the caller provides
  them, a ``shard_map``-wrapped ``jax.jit`` fallback otherwise, and
  ``donate_argnums`` on the padded row buffer (donation is skipped on
  CPU backends, which cannot reuse donated buffers and would warn).

Per-row traversal is embarrassingly parallel, so the sharded program is
BIT-identical to the single-device ``predict_raw_device`` — pinned in
tests/test_serve_fleet.py.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import numpy as np

from ..utils import next_pow2
from .forest import StackedForest


def sharded_bucket(n_rows: int, n_devices: int,
                   min_bucket: int = 16) -> int:
    """Padded row count for a sharded dispatch: the power-of-two bucket
    rounded UP to a multiple of the mesh size, so the leading axis
    always divides evenly across the devices (a bare power of two does
    not for 3- or 6-device meshes)."""
    D = max(int(n_devices), 1)
    bucket = max(next_pow2(max(n_rows, 1)), next_pow2(min_bucket))
    return ((bucket + D - 1) // D) * D


def compile_predict_with_plan(fn: Callable, mesh: Any, *,
                              in_shardings: Optional[Any] = None,
                              out_shardings: Optional[Any] = None,
                              donate_argnums: tuple = (),
                              axis: str = "replica",
                              name: str = "serve.sharded_predict"
                              ) -> Callable:
    """Compile ``fn(rows) -> out`` for ``mesh``. When explicit shardings
    are provided we prefer the pjit route (``jax.jit`` with
    in/out_shardings) so ``PartitionSpec`` configurations are honoured;
    otherwise a ``shard_map``-wrapped ``jax.jit`` keeps map-style
    ergonomics under the same mesh. A 1-device mesh compiles a plain
    ``jax.jit`` — no partitioning machinery in the hot path. All three
    routes compile through obs/compile.instrument_jit under ``name``,
    so fleet compiles stay visible in jit_trace/roofline telemetry."""
    from ..obs import compile as obs_compile

    if mesh is None or np.prod(mesh.devices.shape) == 1:
        return obs_compile.instrument_jit(
            name, fn, donate_argnums=donate_argnums)
    if in_shardings is not None or out_shardings is not None:
        if in_shardings is None or out_shardings is None:
            raise ValueError(
                "compile_predict_with_plan needs BOTH in_shardings and "
                "out_shardings for the pjit route; pass neither to use "
                "the shard_map fallback")
        return obs_compile.instrument_jit(
            name, fn, in_shardings=in_shardings,
            out_shardings=out_shardings, donate_argnums=donate_argnums)
    import jax
    from jax.sharding import PartitionSpec as P
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=P(axis),
                           out_specs=P(axis), check_vma=False)
    return obs_compile.instrument_jit(
        name, mapped, donate_argnums=donate_argnums)


class ReplicatedForest:
    """One ``StackedForest`` across a device mesh.

    ``replica(k)`` returns the forest placed on device k (the
    PredictServer workers' view). ``predict_raw_sharded`` is the
    one-program alternative: rows shard across the mesh, the forest
    arrays replicate as closed-over constants, and the padded row
    buffer is donated (off-CPU) so steady-state serving reuses its HBM."""

    def __init__(self, forest: StackedForest, devices=None,
                 in_shardings=None, out_shardings=None):
        import threading

        import jax
        self.base = forest
        self.devices = list(devices) if devices else list(jax.devices())
        self.mesh = jax.sharding.Mesh(
            np.asarray(self.devices), ("replica",))
        self._in_shardings = in_shardings
        self._out_shardings = out_shardings
        self._fn = None          # built once; jax.jit caches per shape
        self._fn_lock = threading.Lock()

    @property
    def num_replicas(self) -> int:
        return len(self.devices)

    def replica(self, k: int) -> StackedForest:
        """The forest placed on device ``k`` (cached; all replicas share
        the module-level jitted programs — zero extra traces)."""
        return self.base.place(self.devices[k % len(self.devices)])

    # ------------------------------------------------------------------
    def _sharded_fn(self):
        """The ONE compiled wrapper (bucket-independent: jax.jit caches
        executables per input shape underneath it; the lock stops two
        dispatch threads double-building it)."""
        if self._fn is not None:
            return self._fn
        with self._fn_lock:
            if self._fn is not None:
                return self._fn
            import jax
            forest = self.base
            K = forest.num_classes

            def raw_rows(X):
                from ..ops.predict import (_quantize_rows_impl,
                                           _raw_from_leaves,
                                           _walk_stacked)
                bins = _quantize_rows_impl(X, forest._qt)
                leaves = _walk_stacked(bins, forest._nodes,
                                       forest._cat_lut, forest.trips)
                out = _raw_from_leaves(X, leaves, forest._nodes, K,
                                       forest._lin)
                if forest.average_output and forest.num_trees:
                    out = out / np.float32(
                        max(forest.num_trees // K, 1))
                return out

            donate = (0,) if jax.default_backend() != "cpu" else ()
            self._fn = compile_predict_with_plan(
                raw_rows, self.mesh, in_shardings=self._in_shardings,
                out_shardings=self._out_shardings, donate_argnums=donate)
        return self._fn

    def predict_raw_sharded(self, X, min_bucket: int = 16) -> np.ndarray:
        """[n, K] f32 raw scores from ONE sharded dispatch over the
        whole mesh (row-parallel: bit-identical to the single-device
        ``predict_raw_device``). Rows pad to a power-of-two bucket
        rounded up to a multiple of the mesh size
        (:func:`sharded_bucket`), so repeat buckets hit the compile
        cache and the row axis shards evenly on ANY device count."""
        import jax
        D = self.num_replicas
        X = np.asarray(X)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        X = np.ascontiguousarray(X, dtype=np.float32)
        n = X.shape[0]
        bucket = sharded_bucket(n, D, min_bucket)
        if n < bucket:
            X = np.concatenate(
                [X, np.zeros((bucket - n, X.shape[1]), X.dtype)], axis=0)
        fn = self._sharded_fn()
        # jaxlint: disable=JLT001 -- serving boundary: the sharded sum
        # comes home exactly once per dispatch, by design
        return np.asarray(jax.device_get(fn(X)))[:n]
