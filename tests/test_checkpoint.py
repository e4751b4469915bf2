"""Crash-consistent checkpoint/resume (ft/checkpoint.py + the
lgb.train(checkpoint_dir=, checkpoint_freq=, resume=True) wiring):
bit-identical resume parity across exact/quantized8/bagging x
serial/sharded learners (+ DART drop state), atomic finalize +
manifest hash validation with loud fallback past corrupt checkpoints,
atomic model writes, and the transfer-guard over a warmed checkpointed
iteration (checkpointing must add ZERO hot-loop host transfers)."""
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import create_boosting
from lightgbm_tpu.config import Config
from lightgbm_tpu.ft import checkpoint as ckpt
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.shards import ShardedBinnedDataset
from lightgbm_tpu.obs import events
from lightgbm_tpu.utils.atomic import atomic_write
from lightgbm_tpu.utils.log import LightGBMError

BASE = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
        "bin_construct_sample_cnt": 800, "min_data_in_leaf": 5}

MATRIX = [
    ({}, "exact"),
    ({"use_quantized_grad": True}, "quantized8"),
    ({"bagging_fraction": 0.7, "bagging_freq": 2}, "bagging"),
]


def _data(n=800, f=6, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] - X[:, 1] + 0.3 * rng.randn(n) > 0).astype(np.float64)
    return X, y


def _source(X, y, chunk=250):
    def src():
        for lo in range(0, X.shape[0], chunk):
            yield X[lo:lo + chunk], y[lo:lo + chunk].astype(np.float32)
    return src


def _make_ds(kind, params, spill_dir):
    X, y = _data()
    cfg = Config.from_params(dict(params))
    if kind == "serial":
        return BinnedDataset.from_matrix(X, cfg, label=y)
    return ShardedBinnedDataset.from_chunk_source(
        _source(X, y), cfg, spill_dir, shard_rows=300,
        total_rows=X.shape[0])


def _score_bits(gbdt):
    return np.asarray(gbdt.train_score,
                      dtype=np.float32).view(np.uint32)


class TestResumeParityMatrix:
    """The acceptance pin: kill-at-iteration-k -> resume produces
    BIT-identical trees AND training scores vs the uninterrupted run.
    The resumed booster is a brand-new process-equivalent: fresh
    dataset objects (fresh spill dir + prefetcher on the sharded arm),
    fresh learner, state restored only through the checkpoint dir."""

    @pytest.mark.parametrize("extra", [m[0] for m in MATRIX],
                             ids=[m[1] for m in MATRIX])
    @pytest.mark.parametrize("kind", ["serial", "sharded"])
    def test_bit_identical_resume(self, tmp_path, kind, extra):
        params = dict(BASE, **extra)

        def cfg():
            return Config.from_params(dict(params, num_iterations=6))

        control = create_boosting(cfg(), _make_ds(
            kind, params, str(tmp_path / "sp_ctrl")))
        for _ in range(6):
            control.train_one_iter()

        interrupted = create_boosting(cfg(), _make_ds(
            kind, params, str(tmp_path / "sp_a")))
        for _ in range(3):
            interrupted.train_one_iter()
        ckdir = str(tmp_path / "ck")
        interrupted.save_checkpoint(ckdir)

        resumed = create_boosting(cfg(), _make_ds(
            kind, params, str(tmp_path / "sp_b")))
        assert resumed.load_checkpoint(ckdir) is not None
        assert resumed.iter == 3
        for _ in range(3):
            resumed.train_one_iter()

        assert resumed.save_model_to_string() \
            == control.save_model_to_string()
        assert np.array_equal(_score_bits(resumed),
                              _score_bits(control))

    def test_dart_drop_state_resumes(self, tmp_path):
        params = dict(BASE, boosting="dart")

        def cfg():
            return Config.from_params(dict(params, num_iterations=6))

        X, y = _data()
        control = create_boosting(cfg(), BinnedDataset.from_matrix(
            X, Config.from_params(dict(params)), label=y))
        for _ in range(6):
            control.train_one_iter()
        interrupted = create_boosting(cfg(), BinnedDataset.from_matrix(
            X, Config.from_params(dict(params)), label=y))
        for _ in range(3):
            interrupted.train_one_iter()
        ckdir = str(tmp_path / "ck")
        interrupted.save_checkpoint(ckdir)
        resumed = create_boosting(cfg(), BinnedDataset.from_matrix(
            X, Config.from_params(dict(params)), label=y))
        assert resumed.load_checkpoint(ckdir) is not None
        for _ in range(3):
            resumed.train_one_iter()
        assert resumed.save_model_to_string() \
            == control.save_model_to_string()
        assert resumed.tree_weight == control.tree_weight

    def test_resume_mid_bagging_window(self, tmp_path):
        """Checkpoint at an iteration where the bag vector is REUSED
        (bagging_freq=3, stop at iter 4): the stateless fold_in draw
        (sample_strategy.py) recomputes THAT window's bag — keyed on
        iter // freq, not on any saved sampler state — so iterations
        5-6 continue on the exact in-bag rows the uninterrupted run
        used (no bag.npy in the checkpoint any more)."""
        params = dict(BASE, bagging_fraction=0.6, bagging_freq=3)

        def cfg():
            return Config.from_params(dict(params, num_iterations=7))

        X, y = _data()
        control = create_boosting(cfg(), BinnedDataset.from_matrix(
            X, Config.from_params(dict(params)), label=y))
        for _ in range(7):
            control.train_one_iter()
        a = create_boosting(cfg(), BinnedDataset.from_matrix(
            X, Config.from_params(dict(params)), label=y))
        for _ in range(4):
            a.train_one_iter()
        ckdir = str(tmp_path / "ck")
        a.save_checkpoint(ckdir)
        files = os.listdir(os.path.join(ckdir, "ckpt-%08d" % 4))
        assert "bag.npy" not in files  # nothing to capture: draws are
        #                                a pure function of (seed, iter)
        b = create_boosting(cfg(), BinnedDataset.from_matrix(
            X, Config.from_params(dict(params)), label=y))
        assert b.load_checkpoint(ckdir) is not None
        for _ in range(3):
            b.train_one_iter()
        assert b.save_model_to_string() == control.save_model_to_string()


class TestEngineAPI:
    def _xy(self):
        return _data(500)

    def test_checkpoint_freq_and_final(self, tmp_path):
        X, y = self._xy()
        ckdir = str(tmp_path / "ck")
        lgb.train(dict(BASE), lgb.Dataset(X, label=y), num_boost_round=5,
                  checkpoint_dir=ckdir, checkpoint_freq=2)
        names = sorted(os.listdir(ckdir))
        # freq-gated at 2 and 4 plus the forced final at 5
        assert "ckpt-%08d" % 4 in names and "ckpt-%08d" % 5 in names
        assert not any(n.startswith(".ckpt-tmp-") for n in names)

    def test_resume_equals_uninterrupted(self, tmp_path):
        X, y = self._xy()
        ckdir = str(tmp_path / "ck")
        full = lgb.train(dict(BASE), lgb.Dataset(X, label=y),
                         num_boost_round=6)
        lgb.train(dict(BASE), lgb.Dataset(X, label=y), num_boost_round=3,
                  checkpoint_dir=ckdir, checkpoint_freq=1)
        seen = []
        events.register_event_callback(
            lambda rec: seen.append(rec)
            if rec["event"] == "checkpoint_resumed" else None)
        try:
            resumed = lgb.train(dict(BASE), lgb.Dataset(X, label=y),
                                num_boost_round=6, checkpoint_dir=ckdir,
                                resume=True)
        finally:
            events.register_event_callback(None)
        assert resumed.inner.save_model_to_string() \
            == full.inner.save_model_to_string()
        assert np.array_equal(_score_bits(resumed.inner),
                              _score_bits(full.inner))
        assert len(seen) == 1 and seen[0]["iter"] == 3

    def test_resume_with_no_checkpoint_trains_fresh(self, tmp_path):
        X, y = self._xy()
        b = lgb.train(dict(BASE), lgb.Dataset(X, label=y),
                      num_boost_round=3,
                      checkpoint_dir=str(tmp_path / "empty"),
                      resume=True)
        assert b.current_iteration == 3

    def test_resume_under_early_stopping_parity(self, tmp_path):
        """ISSUE 10 satellite: the engine-level early_stopping
        callback's closure state rides the checkpoint (state.json
        ``engine.early_stopping``), so a resumed run continues the SAME
        patience window — same stop iteration, same best_iteration,
        same model — instead of re-arming patience at the resume point
        (which would train past the true stop and report a later
        best)."""
        X, y = self._xy()
        Xv, yv = _data(250, seed=21)
        params = dict(BASE, metric="binary_logloss", learning_rate=0.3,
                      early_stopping_round=3)
        kw = dict(valid_sets=[lgb.Dataset(Xv, label=yv)],
                  valid_names=["v"])
        full = lgb.train(params, lgb.Dataset(X, label=y),
                         num_boost_round=80, **kw)
        stop_iter = full.inner.iter
        best = full.best_iteration
        # a genuine patience stop, not the end-of-horizon check
        assert stop_iter < 80 and stop_iter - best == 3, \
            (stop_iter, best)
        # interrupt mid-patience: past the best iteration, before stop
        mid = best + 1
        assert 0 < mid < stop_iter
        ckdir = str(tmp_path / "ck")
        lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=mid,
                  checkpoint_dir=ckdir, checkpoint_freq=1, **kw)
        resumed = lgb.train(params, lgb.Dataset(X, label=y),
                            num_boost_round=80, checkpoint_dir=ckdir,
                            resume=True, **kw)
        # without the carried state the resumed run would re-arm: its
        # first post-resume eval becomes a fresh "best" and training
        # runs ~patience rounds past the true stop
        assert resumed.inner.iter == stop_iter
        assert resumed.best_iteration == best
        assert resumed.best_score == full.best_score
        assert resumed.inner.save_model_to_string() \
            == full.inner.save_model_to_string()
        # the checkpoint really carried the callback state
        it, path = ckpt.list_checkpoints(ckdir)[0]
        state = json.load(open(os.path.join(path, "state.json")))
        es = state["engine"]["early_stopping"][0]
        assert len(es["best_score"]) == 1 and es["best_iter"] == [best - 1]

    def test_resume_mid_patience_with_eval_hoisting(self, tmp_path):
        """ISSUE 13 satellite: early stopping under every-k eval
        (tpu_eval_iterations) survives a mid-patience-window resume.
        The eval grid is keyed on ABSOLUTE iteration numbers and the
        early_stopping closure state rides the checkpoint, so the
        resumed k-hoisted run stops at the SAME iteration with the
        SAME best iteration and model as the uninterrupted k-hoisted
        run — and, with patience a multiple of k (the aligned case of
        the docs/PERFORMANCE.md contract), at the same iteration the
        eval-every-1 run stops at whenever its best lands on the
        grid."""
        X, y = self._xy()
        Xv, yv = _data(250, seed=21)
        k = 2
        params = dict(BASE, metric="binary_logloss", learning_rate=0.3,
                      early_stopping_round=4, tpu_eval_iterations=k)
        kw = dict(valid_sets=[lgb.Dataset(Xv, label=yv)],
                  valid_names=["v"])
        full = lgb.train(params, lgb.Dataset(X, label=y),
                         num_boost_round=80, **kw)
        stop_iter = full.inner.iter
        best = full.best_iteration
        assert stop_iter < 80 and stop_iter > best, (stop_iter, best)
        # interrupt mid-patience: past the best, before the stop
        mid = best + 1
        assert 0 < mid < stop_iter
        ckdir = str(tmp_path / "ck")
        lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=mid,
                  checkpoint_dir=ckdir, checkpoint_freq=1, **kw)
        resumed = lgb.train(params, lgb.Dataset(X, label=y),
                            num_boost_round=80, checkpoint_dir=ckdir,
                            resume=True, **kw)
        assert resumed.inner.iter == stop_iter
        assert resumed.best_iteration == best
        assert resumed.inner.save_model_to_string() \
            == full.inner.save_model_to_string()
        # the k-hoisted stop decision matches eval-every-1 whenever the
        # best iteration sits on the k-grid (patience 4 = 2k keeps the
        # expiry aligned too); otherwise the documented contract is
        # "within k-1 iterations", asserted as the bound below
        every1 = lgb.train(dict(params, tpu_eval_iterations=1),
                           lgb.Dataset(X, label=y), num_boost_round=80,
                           **kw)
        if every1.best_iteration % k == 0:
            assert full.best_iteration == every1.best_iteration
            assert full.inner.iter == every1.inner.iter
        assert abs(full.inner.iter - every1.inner.iter) < 2 * k

    def test_resume_with_valid_sets_and_eval(self, tmp_path):
        X, y = self._xy()
        Xv, yv = _data(200, seed=9)
        ckdir = str(tmp_path / "ck")
        kw = dict(valid_sets=[lgb.Dataset(Xv, label=yv)],
                  valid_names=["v"])
        full = lgb.train(dict(BASE, metric="auc"),
                         lgb.Dataset(X, label=y), num_boost_round=6,
                         **kw)
        lgb.train(dict(BASE, metric="auc"), lgb.Dataset(X, label=y),
                  num_boost_round=3, checkpoint_dir=ckdir,
                  checkpoint_freq=1, **kw)
        resumed = lgb.train(dict(BASE, metric="auc"),
                            lgb.Dataset(X, label=y), num_boost_round=6,
                            checkpoint_dir=ckdir, resume=True, **kw)
        # valid scores were replayed onto the resumed booster: the
        # final eval matches the uninterrupted run's
        assert resumed.eval_valid() == full.eval_valid()


class TestCheckpointLayoutAndValidation:
    def _booster(self, iters=3):
        X, y = _data(400)
        b = create_boosting(
            Config.from_params(dict(BASE, num_iterations=iters)),
            BinnedDataset.from_matrix(
                X, Config.from_params(dict(BASE)), label=y))
        for _ in range(iters):
            b.train_one_iter()
        return b

    def test_layout_manifest_hashes(self, tmp_path):
        b = self._booster()
        path = b.save_checkpoint(str(tmp_path))
        assert os.path.basename(path) == "ckpt-%08d" % 3
        man = json.load(open(os.path.join(path, "manifest.json")))
        for req in ("state.json", "model.txt", "score.npy"):
            assert req in man["files"]
        ckpt.validate_dir(path)  # hashes verify

    def test_corrupt_checkpoint_falls_back_loudly(self, tmp_path):
        b = self._booster(2)
        p2 = b.save_checkpoint(str(tmp_path))
        b.train_one_iter()
        p3 = b.save_checkpoint(str(tmp_path))
        assert p2 != p3
        # poison the newest checkpoint's model text (same length:
        # size check passes, the content hash must catch it)
        mp = os.path.join(p3, "model.txt")
        data = bytearray(open(mp, "rb").read())
        data[len(data) // 2] ^= 0xFF
        open(mp, "wb").write(bytes(data))
        seen = []
        events.register_event_callback(
            lambda rec: seen.append(rec)
            if rec["event"] == "checkpoint_invalid" else None)
        X, y = _data(400)
        fresh = create_boosting(
            Config.from_params(dict(BASE, num_iterations=5)),
            BinnedDataset.from_matrix(
                X, Config.from_params(dict(BASE)), label=y))
        try:
            state = fresh.load_checkpoint(str(tmp_path))
        finally:
            events.register_event_callback(None)
        assert state is not None and fresh.iter == 2  # fell back to p2
        assert len(seen) == 1 and seen[0]["path"] == p3

    def test_truncated_score_rejected(self, tmp_path):
        b = self._booster(2)
        p = b.save_checkpoint(str(tmp_path))
        sp = os.path.join(p, "score.npy")
        with open(sp, "r+b") as f:
            f.truncate(os.path.getsize(sp) - 64)
        with pytest.raises(ckpt.CheckpointError, match="truncated"):
            ckpt.validate_dir(p)

    def test_tmp_dirs_ignored_and_pruned(self, tmp_path, monkeypatch):
        b = self._booster(2)
        stale = tmp_path / (ckpt.TMP_PREFIX + "00000001-99999")
        stale.mkdir()
        (stale / "junk").write_text("x")
        monkeypatch.setenv("LIGHTGBM_TPU_CKPT_KEEP", "1")
        b.save_checkpoint(str(tmp_path))
        b.train_one_iter()
        b.save_checkpoint(str(tmp_path))
        names = sorted(os.listdir(tmp_path))
        assert names == ["ckpt-%08d" % 3]  # pruned to keep=1, tmp gone

    def test_different_dataset_refused(self, tmp_path):
        b = self._booster(2)
        b.save_checkpoint(str(tmp_path))
        X2, y2 = _data(300, seed=11)
        other = create_boosting(
            Config.from_params(dict(BASE, num_iterations=2)),
            BinnedDataset.from_matrix(
                X2, Config.from_params(dict(BASE)), label=y2))
        with pytest.raises(LightGBMError, match="different dataset"):
            other.load_checkpoint(str(tmp_path))

    def test_cegb_refused(self, tmp_path):
        X, y = _data(400)
        params = dict(BASE, cegb_penalty_split=0.1)
        b = create_boosting(
            Config.from_params(dict(params, num_iterations=2)),
            BinnedDataset.from_matrix(
                X, Config.from_params(dict(params)), label=y))
        b.train_one_iter()
        with pytest.raises(LightGBMError, match="CEGB"):
            b.save_checkpoint(str(tmp_path))


class TestAtomicWrites:
    def test_atomic_write_keeps_previous_on_failure(self, tmp_path,
                                                    monkeypatch):
        target = tmp_path / "model.txt"
        target.write_text("previous complete content")

        class Boom(RuntimeError):
            pass

        # die at the publish step (after the temp file is fully
        # written): the target must keep its previous content and the
        # temp must not linger
        import lightgbm_tpu.utils.atomic as atomic_mod

        def boom(*a):
            raise Boom()
        monkeypatch.setattr(atomic_mod.os, "replace", boom)
        with pytest.raises(Boom):
            atomic_write(str(target), "half-written new content")
        assert target.read_text() == "previous complete content"
        assert [p for p in os.listdir(tmp_path)
                if p.startswith("model.txt.tmp")] == []

    def test_save_model_is_atomic(self, tmp_path):
        X, y = _data(300)
        b = create_boosting(
            Config.from_params(dict(BASE, num_iterations=2)),
            BinnedDataset.from_matrix(
                X, Config.from_params(dict(BASE)), label=y))
        b.train_one_iter()
        path = tmp_path / "m.txt"
        b.save_model(str(path))
        s1 = path.read_text()
        assert s1.endswith("end of parameters\n")
        assert [p for p in os.listdir(tmp_path)
                if p.startswith("m.txt.tmp")] == []


class TestTransferGuardCheckpointedIteration:
    def test_warmed_checkpointed_iteration_no_implicit_transfers(
            self, tmp_path):
        """Checkpointing between iterations must leave the iteration
        itself transfer-free: the checkpoint's own score read-back is
        OUTSIDE the guarded window, exactly like its save cadence."""
        import jax
        X, y = _data(500)
        b = create_boosting(
            Config.from_params(dict(BASE, num_leaves=7,
                                    num_iterations=10)),
            BinnedDataset.from_matrix(
                X, Config.from_params(dict(BASE, num_leaves=7)),
                label=y))
        for _ in range(2):
            b.train_one_iter()
            b.save_checkpoint(str(tmp_path))
        with jax.transfer_guard("disallow"):
            b.train_one_iter()
        assert b.iter == 3
        b.save_checkpoint(str(tmp_path))


@pytest.mark.slow
class TestKillAndResumeSubprocess:
    """The real thing: SIGKILL mid-iteration with checkpoint_freq=1,
    then resume in a fresh process state and pin bit-identity against
    an uninterrupted control run."""

    CHILD = textwrap.dedent("""\
        import os, signal
        import numpy as np
        import lightgbm_tpu as lgb
        rng = np.random.RandomState(3)
        X = rng.randn(800, 6)
        y = (X[:, 0] - X[:, 1] + 0.3 * rng.randn(800) > 0).astype(
            np.float64)
        params = {"objective": "binary", "num_leaves": 15,
                  "verbosity": -1, "bin_construct_sample_cnt": 800,
                  "min_data_in_leaf": 5}

        def killer(env):
            if env.iteration + 1 == 3:
                os.kill(os.getpid(), signal.SIGKILL)
        lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8,
                  checkpoint_dir=os.environ["CKDIR"],
                  checkpoint_freq=1, callbacks=[killer])
        """)

    def test_sigkill_resume_bit_identical(self, tmp_path):
        ckdir = str(tmp_path / "ck")
        env = dict(os.environ, CKDIR=ckdir, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-c", self.CHILD], env=env,
            capture_output=True, timeout=600)
        assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]
        assert ckpt.list_checkpoints(ckdir), "no checkpoint survived"

        X, y = _data()
        control = lgb.train(dict(BASE), lgb.Dataset(X, label=y),
                            num_boost_round=8)
        resumed = lgb.train(dict(BASE), lgb.Dataset(X, label=y),
                            num_boost_round=8, checkpoint_dir=ckdir,
                            resume=True)
        assert resumed.inner.iter > 3  # actually continued past kill
        assert resumed.inner.save_model_to_string() \
            == control.inner.save_model_to_string()
        assert np.array_equal(_score_bits(resumed.inner),
                              _score_bits(control.inner))
