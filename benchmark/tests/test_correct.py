"""``correct`` comes out true for the program as configured and false for
the control and for each fault a training cell can have, at a size a test
run can hold.

The runs skip the harness's look for a chip and drive the rest of a run on
the CPU. The faults are planted in the program, underneath the timed
``Booster.update``: a step that leaves its state unchanged (the score
update does nothing), and half of the batch left out (every odd row's
gradient and hessian zeroed before the tree is grown). The control is the
reference put in the program's place with gradients and hessians rounded to
bfloat16, and the program's own lower-precision path, quantized gradients.
The limits are this size's own, set between the readings the module's
comment lists.
"""
import time

import numpy as np
import pytest

from benchmark.harness import device, spec, train

# Readings at this size (CPU, seeds 11 and 12), largest per number:
#   program as configured  4e-7 (step1_norm), losses under 2e-8
#   ref-bf16               1.5e-4 change3_norm, 1e-5 loss2/loss3
#   ref-half               4e-2 step1_norm, 3e-4..1e-3 losses
#   frozen                 1.0 step1_norm, 3e-2.. losses
LIMITS = {"loss1": 2e-6, "loss2": 2e-6, "step1_norm": 1e-5,
          "change2_norm": 1e-5, "holdout_loss2": 2e-6, "window_compiles": 0}


CELLS = [w["name"] for w in spec.Spec().doc["workloads"]
         if spec.Spec().cell(w["name"])["traffic"]["kind"] == "train"]


def tiny_cell(name=CELLS[0]):
    cell = spec.Spec().cell(name)
    cell["config"] = dict(cell["config"], rows=20000, features=40,
                          valid_rows=2048)
    cell["config"]["params"] = dict(cell["config"]["params"], num_leaves=31,
                                    min_sum_hessian_in_leaf=20.0)
    cell["limits"] = dict(LIMITS)
    return cell


def drive(variant=None, seed=11, seconds=0.5, name=CELLS[0]):
    import jax
    peaks = device.peaks_for("TPU v5 lite")
    this, result, compared = train.run(
        tiny_cell(name), seed, seconds, False, jax.devices()[0], peaks,
        time.perf_counter(), variant)
    return result, this.end_to_end, compared


@pytest.mark.parametrize("name", CELLS)
def test_program_as_configured_is_correct(name):
    """The CPU rehearsal of every cell at a tiny size."""
    result, end_to_end, compared = drive(name=name, seed=2**31 + 11)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1 and end_to_end["train_iter_s"] > 0
    assert set(compared) == set(LIMITS)
    assert compared["window_compiles"]["value"] == 0


@pytest.mark.parametrize("variant", ["ref-bf16", "quantized"])
def test_control_in_lower_precision_is_not_correct(variant):
    result, _, compared = drive(variant, seconds=0)
    assert result["correct"] is False
    over = [k for k, c in compared.items() if c["value"] > c["limit"]]
    assert over, compared


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    from lightgbm_tpu.objective.binary import BinaryLogloss
    real = BinaryLogloss.get_gradients

    def half(self, score):
        g, h = real(self, score)
        keep = (np.arange(g.shape[0]) % 2 == 0).astype(np.float32)
        return g * keep, h * keep

    monkeypatch.setattr(BinaryLogloss, "get_gradients", half)
    result, _, compared = drive(seconds=0)
    assert result["correct"] is False
    assert compared["step1_norm"]["value"] > 10 * LIMITS["step1_norm"]


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(monkeypatch):
    from lightgbm_tpu.boosting.gbdt import GBDT
    monkeypatch.setattr(GBDT, "_update_score", lambda self, *a, **k: None)
    result, _, compared = drive(seconds=0)
    assert result["correct"] is False
    assert compared["change2_norm"]["value"] > 0.5


def test_a_compilation_inside_the_window_is_a_failed_run(monkeypatch):
    import jax
    import jax.numpy as jnp
    from benchmark.harness import program
    real = program.Program.update
    calls = []

    def update(self):
        calls.append(1)
        if len(calls) > 2:      # inside the window: a shape never seen
            jax.jit(lambda v: v * 2)(jnp.ones(len(calls) + 7)).block_until_ready()
        real(self)

    monkeypatch.setattr(program.Program, "update", update)
    result, _, compared = drive(seconds=0.3)
    assert compared["window_compiles"]["value"] >= 1
    assert result["correct"] is False
