"""XLA compile / retrace tracking + compile-cost capture.

``jax.jit`` re-runs the wrapped Python body once per new static
signature — every execution of the body IS a trace (and, absent a
compilation-cache hit, a compile). Wrapping the body with
:func:`traced` therefore counts compilations per function without
reaching into jax internals, and surfaces unexpected retraces: a
function that keeps re-tracing is burning compile time the device
trace will never show. The recorded seconds cover the Python trace
only — XLA lowering + backend compilation happen after the body
returns and are read from jax's own monitoring events instead (below).

:func:`instrument_jit` goes further: it owns the ``jax.jit`` call and,
when cost capture is on (``LIGHTGBM_TPU_COMPILE_COST=1`` or an active
span trace), runs ``jit(...).lower(args).cost_analysis()`` for every
call that actually compiled — the ``jit_trace`` event then carries
FLOPs, bytes accessed, and the HLO module text size, so the compile
boundary is costed, not just counted. Compiles are detected by the
deferred trace records the call itself produced, so steady-state
(cache-hit) dispatches pay no signature hashing; the explicit
re-lowering hits jax's shared jaxpr cache and re-runs nothing.

The per-name counters live in the metrics registry under
``jit_trace/<name>``; each trace also emits a ``jit_trace`` event.

What a compile costs after the trace comes from ``jax.monitoring``,
whichever module asked for the compilation and only when jax compiles
(nothing runs per dispatch). While the stage timer is on, lowering and
backend compilation (a read of the persistent cache included) aggregate
as the stage totals ``jit_lower_s/<fun_name>`` and
``jit_backend_compile_s/<fun_name>`` under jax's own name of the program
(``jit(_tree_impl)``), each compile shows in a device trace as a
``jit::compile <fun_name>`` range, and the persistent cache's hits and
the entries it wrote count as ``jit_cache_hits`` / ``jit_cache_misses``.
The learners legitimately compile several shape variants (the serial
learner's ~log2(N) gather buckets), so the retrace warning fires only
past ``LIGHTGBM_TPU_RETRACE_WARN`` traces of one name (default 32;
0 disables). The warned-name dedup set resets with ``registry.reset()``
so repeated runs in one process warn again.
"""
from __future__ import annotations

import functools
import os
import threading
import time
from typing import Callable, Dict

from ..utils import log
from . import events
from .registry import _get_profiler, add_reset_hook, registry

_WARNED = set()


def reset_warned() -> None:
    """Clear the retrace-warning dedup set (also wired into
    ``registry.reset()`` below)."""
    _WARNED.clear()


add_reset_hook(reset_warned)

# While instrument_jit lowers explicitly for cost analysis, trace
# records are DEFERRED (stashed on _tls.defer) and replayed once the
# cost is known — the lowering IS the trace (jax shares the jaxpr cache
# between .lower() and the call), so counting it twice or before the
# cost exists would both be wrong. The captured cost_analysis results
# hand off through _tls.pending (capture and replay happen on the SAME
# thread; a shared name-keyed dict would let two threads compiling the
# same fn swap each other's FLOPs).
_tls = threading.local()


def _pending(create: bool = False) -> Dict[str, dict]:
    pending = getattr(_tls, "pending", None)
    if pending is None:
        pending = {}
        if create:
            _tls.pending = pending
    return pending


def _warn_threshold() -> int:
    try:
        return int(os.environ.get("LIGHTGBM_TPU_RETRACE_WARN", "32"))
    except ValueError:
        return 32


def record_trace(name: str, seconds: float = 0.0,
                 ended_at: float = None) -> int:
    """Count one trace/compile of ``name``; returns the cumulative
    count. ``seconds`` is the Python-trace wall time (a lower bound on
    the compile cost — see module docstring); it aggregates under the
    ``jit::<name>`` stage regardless of the TIMETAG gate so the retrace
    evidence survives into BENCH phases. ``ended_at`` (unix seconds) is
    set on deferred replays: the trace actually finished back then, and
    the span exporter must place the compile span at its true time, not
    at replay time."""
    deferred = getattr(_tls, "defer", None)
    if deferred is not None:
        deferred.append((name, seconds, time.time()))
        return registry.count("jit_trace/" + name)
    n = registry.inc("jit_trace/" + name)
    registry.timer.record("jit::" + name, seconds)
    extra = _pending(create=False).pop(name, None) or {}
    if ended_at is not None:
        extra["ended_ts"] = round(ended_at, 6)
    events.emit("jit_trace", fn=name, count=n,
                trace_seconds=round(seconds, 6), **extra)
    thr = _warn_threshold()
    if thr and n == thr + 1 and name not in _WARNED:
        _WARNED.add(name)
        log.warning("jit function %r traced %d times — unexpected "
                    "retraces? (threshold LIGHTGBM_TPU_RETRACE_WARN=%d)"
                    % (name, n, thr))
    return n


def traced(name: str) -> Callable:
    """Decorator for a function about to be ``jax.jit``-ed: the wrapper
    records a trace each time the Python body runs (i.e. each
    compilation), timing the trace itself. Positional-argument
    passthrough keeps ``donate_argnums``/``static_argnums`` indices
    valid."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                record_trace(name, time.perf_counter() - t0)
        return wrapper
    return deco


# ----------------------------------------------------------------------
# lowering / backend-compile seconds and persistent-cache outcomes
# ----------------------------------------------------------------------

# jax's event -> the stage-total prefix it aggregates under
COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit_lower_s/",
    "/jax/core/compile/backend_compile_duration": "jit_backend_compile_s/",
}
CACHE_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "jit_cache_hits",
    "/jax/compilation_cache/cache_misses": "jit_cache_misses",
}
_listening = False


def _on_compile_start(event: str, value, fun_name: str = "?",
                      **kwargs) -> None:
    """jax records a scalar as it enters a timed compile phase: open
    the phase's range in the profiler's trace. From here on the
    persistent cache's key holds the programs' metadata too: the device
    stages are read from the ``obs_*`` names in it, and jax's default key
    would hand a profiled run an entry compiled from other source, with
    that source's names (the key is computed inside the backend phase,
    after this). Runs that never switch the timer on keep jax's key and
    share entries whatever the names."""
    if event not in COMPILE_STAGES or not registry.timer.enabled:
        return
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    profiler = _get_profiler()
    if profiler is None:
        return
    annotation = profiler.TraceAnnotation("jit::compile %s" % fun_name)
    annotation.__enter__()
    _tls.compiling = annotation


def _on_compile_end(event: str, duration: float, fun_name: str = "?",
                    **kwargs) -> None:
    prefix = COMPILE_STAGES.get(event)
    if prefix is None:
        return
    annotation = getattr(_tls, "compiling", None)
    if annotation is not None:
        _tls.compiling = None
        annotation.__exit__(None, None, None)
    if registry.timer.enabled:
        registry.timer.record(prefix + fun_name, duration)


def _on_cache_event(event: str, **kwargs) -> None:
    name = CACHE_COUNTERS.get(event)
    if name is not None and registry.timer.enabled:
        registry.inc(name)


def _listen() -> None:
    """Register the three listeners with ``jax.monitoring``, once per
    process; every ``instrument_jit`` site calls this before it can
    compile."""
    global _listening
    if _listening:
        return
    _listening = True
    from jax import monitoring
    monitoring.register_scalar_listener(_on_compile_start)
    monitoring.register_event_duration_secs_listener(_on_compile_end)
    monitoring.register_event_listener(_on_cache_event)


# ----------------------------------------------------------------------
# compile-cost capture
# ----------------------------------------------------------------------

# obs.trace resolved once (same rule as registry's jax.profiler):
# cost_capture_enabled sits on every instrumented dispatch and must not
# pay import machinery per call
_trace_mod = None


def _get_trace():
    global _trace_mod
    if _trace_mod is None:
        from . import trace
        _trace_mod = trace
    return _trace_mod


def cost_capture_enabled() -> bool:
    """On under ``LIGHTGBM_TPU_COMPILE_COST`` (1/0 wins outright) or
    whenever the span trace is active — traces should cost their
    compile boundaries."""
    v = os.environ.get("LIGHTGBM_TPU_COMPILE_COST")
    if v is not None:
        return v.strip().lower() not in ("", "0", "false", "off")
    return _get_trace().active()


def _extract_cost(lowered) -> dict:
    cost: dict = {}
    try:
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if ca:
            if "flops" in ca:
                cost["flops"] = float(ca["flops"])
            if "bytes accessed" in ca:
                cost["bytes_accessed"] = float(ca["bytes accessed"])
            # bytes/FLOP roofline position: > the hardware's balance
            # point means the program is bandwidth-bound — exactly what
            # the quantized histogram mode attacks (fewer bytes, same
            # one-hot FLOPs), so the ratio is the direct evidence of
            # the bytes moving
            if cost.get("flops", 0) > 0 and "bytes_accessed" in cost:
                cost["bytes_per_flop"] = round(
                    cost["bytes_accessed"] / cost["flops"], 6)
    except Exception:
        pass
    try:
        cost["hlo_bytes"] = len(lowered.as_text())
    except Exception:
        pass
    return cost


def _capture_cost(name: str, jitted, args, kwargs, deferred) -> None:
    """A compiling call just happened (``deferred`` holds its stashed
    trace records): re-lower — jax shares the jaxpr cache between the
    call and ``.lower()``, so this re-runs nothing — extract FLOPs /
    bytes accessed / HLO size, and replay the trace records so the
    ``jit_trace`` event carries the cost of the very compile it
    counts."""
    cost: dict = {}
    prev = getattr(_tls, "defer", None)
    _tls.defer = []  # swallow any re-trace from an older jax
    try:
        cost = _extract_cost(jitted.lower(*args, **kwargs))
    except Exception:
        pass
    finally:
        _tls.defer = prev
    if cost:
        _pending(create=True)[name] = cost
        if "flops" in cost:
            registry.gauge("compile/%s/flops" % name, cost["flops"])
        if "bytes_accessed" in cost:
            registry.gauge("compile/%s/bytes_accessed" % name,
                           cost["bytes_accessed"])
        if "hlo_bytes" in cost:
            registry.gauge("compile/%s/hlo_bytes" % name,
                           float(cost["hlo_bytes"]))
    for deferred_name, seconds, t_end in deferred:
        record_trace(deferred_name, seconds, ended_at=t_end)


def instrument_jit(name: str, fun: Callable, **jit_kwargs) -> Callable:
    """``jax.jit(traced(name)(fun), **jit_kwargs)`` plus opt-in compile
    cost capture. Drop-in replacement for the bare composition at every
    learner/serving jit site: same call signature, same donation /
    static-argument semantics (positional passthrough).

    jax names the program after ``fun`` (``_tree_impl`` runs as
    ``jit__tree_impl``), and that name is all a device trace shows of
    it: hand in a named function, since every lambda runs as
    ``jit__lambda`` (tests/test_device_scopes.py holds the package to
    it).

    Hot-path cost: with capture off, two env lookups per dispatch; with
    capture on, one thread-local set/restore per dispatch — the
    expensive lowering runs ONLY on calls that actually compiled (a
    fresh trace was observed), so steady-state dispatches stay
    unperturbed even while profiling."""
    import jax
    _listen()
    jitted = jax.jit(traced(name)(fun), **jit_kwargs)

    @functools.wraps(fun)
    def wrapper(*args, **kwargs):
        if not cost_capture_enabled():
            return jitted(*args, **kwargs)
        prev = getattr(_tls, "defer", None)
        _tls.defer = deferred = []
        try:
            out = jitted(*args, **kwargs)
        except BaseException:
            _tls.defer = prev
            # the failing dispatch may be the very compile being
            # diagnosed: replay its trace records (without the cost
            # re-lowering) so the jit_trace evidence survives the crash
            try:
                for deferred_name, seconds, t_end in deferred:
                    record_trace(deferred_name, seconds, ended_at=t_end)
            except Exception:
                pass
            raise
        _tls.defer = prev
        if deferred:
            _capture_cost(name, jitted, args, kwargs, deferred)
        return out

    # AOT passthroughs: callers lower/inspect the jitted object through
    # the wrapper (tests/test_hlo_size.py lowers the learner programs at
    # synthetic scale)
    wrapper.lower = jitted.lower
    wrapper._jitted = jitted
    return wrapper


def instrument_jit_method(name: str, **jit_kwargs) -> Callable:
    """Decorator twin of :func:`instrument_jit` for methods whose
    ``self`` is the static argument — the objectives' former
    ``@partial(jax.jit, static_argnums=0)`` pattern::

        @obs_compile.instrument_jit_method("obj.binary.grads")
        def _grads(self, score, label, weights): ...

    The returned wrapper is a plain function, so class-attribute access
    still binds ``self`` (which jax then treats as the static arg);
    each objective instance compiles once per score signature and its
    compiles surface as ``jit_trace`` events like every learner site."""
    def deco(fn):
        return instrument_jit(name, fn, static_argnums=0, **jit_kwargs)
    return deco


def trace_count(name: str) -> int:
    return registry.count("jit_trace/" + name)


def trace_counts() -> dict:
    prefix = "jit_trace/"
    return {k[len(prefix):]: v for k, v in registry.counters.items()
            if k.startswith(prefix)}
