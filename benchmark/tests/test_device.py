"""A run with no TPU, or on a chip the table of peaks lacks, fails and
prints no result line."""
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import device, spec


def _dev(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_cpu_is_no_chip():
    with pytest.raises(device.NoChip, match="not 'tpu'"):
        device.require_chips([_dev("cpu", "cpu")], 1)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(device.NoChip, match="peaks.json"):
        device.require_chips([_dev("tpu", "TPU v99")], 1)


def test_too_few_chips():
    with pytest.raises(device.NoChip, match="asks for 4"):
        device.require_chips([_dev("tpu", "TPU v5 lite")], 4)


def test_known_chip_gets_its_peaks():
    devs, peaks = device.require_chips([_dev("tpu", "TPU v5 lite")] * 2, 1)
    assert len(devs) == 1 and peaks["hbm_bytes_per_s"] == 819e9
    assert "source" in peaks


def test_run_without_tpu_exits_nonzero_with_no_result_line():
    doc = spec.Spec().doc
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable] + doc["command"][1:] + [
            "--workload", doc["workloads"][0]["name"], "--seed", "1",
            "--seconds", "1", "--trace", "0"],
        cwd=spec.CHECKOUT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        try:
            parsed = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(parsed, dict) and "correct" in parsed)
    assert "not 'tpu'" in proc.stderr
