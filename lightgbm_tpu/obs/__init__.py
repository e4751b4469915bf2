"""Structured telemetry for the whole training pipeline.

The reference builds per-stage observability directly into the trainer
(``Common::Timer``/``FunctionTimer`` RAII scopes around every pipeline
stage, include/LightGBM/utils/common.h:973,1037, aggregated table printed
at exit under -DUSE_TIMETAG). This package is the TPU-native superset:

- :mod:`registry`  — counters, gauges, and the stage timer (scopes open
  ``jax.profiler.TraceAnnotation`` ranges so stages are attributable in
  TensorBoard/perfetto device traces).
- :mod:`events`    — a JSON-lines event sink (``LIGHTGBM_TPU_EVENT_LOG``
  env var or a programmatic callback mirroring
  ``log.register_log_callback``).
- :mod:`compile`   — XLA compile/retrace tracking per jitted function,
  plus opt-in ``lower().cost_analysis()`` capture (FLOPs / bytes / HLO
  size on the ``jit_trace`` event).
- :mod:`health`    — backend selection / fallback events, plus the SLO
  :class:`~lightgbm_tpu.obs.health.Watchdog` (threshold rules over the
  snapshot stream, one ``health`` event per breach).
- :mod:`export`    — OpenMetrics-style snapshot rendering: periodic
  file dumps (``LIGHTGBM_TPU_METRICS=path``) and the HTTP ``/metrics``
  listener the serving plane mounts (text-format primitives live in
  the stdlib-pure :mod:`openmetrics`).
- :mod:`gateway`   — the FLEET plane: per-process
  :class:`~lightgbm_tpu.obs.gateway.SnapshotPusher` POSTs
  (``LIGHTGBM_TPU_METRICS_GATEWAY=url``) into one
  :class:`~lightgbm_tpu.obs.gateway.MetricsGateway` serving aggregated
  ``{rank=,process=}`` metrics + per-rank push staleness, watched by
  ``health.fleet_rules`` (rank_skew / dead_rank / fleet_shed_rate).
- :mod:`trace`     — span tracing layered onto the scopes and events
  above, exported as Chrome-trace/Perfetto JSON
  (``LIGHTGBM_TPU_TRACE=path.json``), with the async readiness drainer
  that replaces stage fences under ``LIGHTGBM_TPU_TIMETAG=sample``;
  streaming runs can write the compact binary segment format of
  :mod:`trace_compact` (``LIGHTGBM_TPU_TRACE_FORMAT=compact``).

Enable stage timing with ``LIGHTGBM_TPU_TIMETAG=1`` (the analogue of
-DUSE_TIMETAG; fencing) or ``=sample`` (non-perturbing) or
``registry.enable()``; route events to a file with
``LIGHTGBM_TPU_EVENT_LOG=path`` or ``events.register_event_callback``.
See docs/OBSERVABILITY.md for the event schema and trace format.
"""
from __future__ import annotations

from . import compile as compile_tracking  # noqa: F401
from . import events, faults, health  # noqa: F401
from . import openmetrics, trace_compact  # noqa: F401  (stdlib-pure)
from .registry import MetricsRegistry, StageTimer, registry  # noqa: F401
from . import trace  # noqa: F401  (installs the span hooks/taps)
from . import export  # noqa: F401  (OpenMetrics snapshots + /metrics)
from . import gateway  # noqa: F401  (fleet push gateway)

scope = registry.scope
counter = registry.inc
gauge = registry.gauge
observe = registry.observe
watch_ready = registry.watch_ready

__all__ = [
    "MetricsRegistry", "StageTimer", "registry", "events", "health",
    "compile_tracking", "trace", "trace_compact", "openmetrics",
    "export", "gateway", "scope", "counter", "gauge",
    "observe", "watch_ready",
]
