"""What the program recorded about itself, for the per-layer readers.

Beside ``program.py`` the one module that imports ``lightgbm_tpu``: its
stage timer's totals (``io::find_bins``, ``jit_backend_compile_s/<fun>``)
and its registry's counters (``grow/hist_rows_needed``). The harness
switches the stage timer on before set-up in a traced run and off when
the window closes (``program.enable_spans``/``disable_spans``), and the
program records all of these only while it is on: what is read here
after the run is the state at the window's end, and the window compiles
nothing, so the set-up numbers are those at the window's start. Asking a
program that lacks an entry gives None, never an error.
"""
from __future__ import annotations


def stage_totals(prefix: str = "") -> dict:
    """``{name: seconds}`` of the stage timer's stages whose name starts
    with ``prefix``."""
    from lightgbm_tpu.obs.registry import registry
    return {name: total for name, total in dict(registry.timer.totals).items()
            if name.startswith(prefix)}


def stage_total(name: str):
    """Seconds the stage timer aggregated under ``name``; None where it
    has no such stage."""
    return stage_totals(name).get(name)


def counter(name: str):
    """The registry's counter ``name``; None where the program has never
    touched it."""
    from lightgbm_tpu.obs.registry import registry
    return registry.counters.get(name)


def counters() -> dict:
    """Every counter of the registry as it stands; a runner notes them
    when the window opens, and a reader takes what they moved by since."""
    from lightgbm_tpu.obs.registry import registry
    return dict(registry.counters)


def counts_cache_outcomes() -> bool:
    """Whether this program counts what the persistent compilation cache
    did (``jit_cache_hits``/``jit_cache_misses``), so that a counter it
    never touched reads 0 and not None."""
    from lightgbm_tpu.obs import compile as obs_compile
    return "jit_cache_misses" in getattr(
        obs_compile, "CACHE_COUNTERS", {}).values()
