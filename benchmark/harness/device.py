"""The look for a chip: a measurement path that finds none fails."""
from __future__ import annotations

import json
import os

from .spec import BENCH_DIR


class NoChip(Exception):
    """No accelerator, too few of them, or one the table of peaks lacks."""


def load_peaks() -> dict:
    with open(os.path.join(BENCH_DIR, "trace", "peaks.json")) as f:
        return json.load(f)


def peaks_for(kind: str) -> dict:
    table = load_peaks()
    if kind not in table:
        raise NoChip("device_kind %r is not in benchmark/trace/peaks.json "
                     "(has: %s)" % (kind, ", ".join(sorted(table))))
    return table[kind]


def require_chips(devices, chips: int):
    """The first ``chips`` TPU devices and their peaks, or ``NoChip``."""
    if not devices or devices[0].platform != "tpu":
        raise NoChip("jax.devices()[0].platform is %r, not 'tpu' "
                     "(JAX_PLATFORMS=%r)" % (
                         devices[0].platform if devices else None,
                         os.environ.get("JAX_PLATFORMS")))
    if len(devices) < chips:
        raise NoChip("the cell asks for %d chip(s), jax.devices() has %d"
                     % (chips, len(devices)))
    return list(devices[:chips]), peaks_for(devices[0].device_kind)
