"""Crash-safe training suite (workflow/checkpoint.py + the chunked
``train_als*`` loops).

- Differential gates: chunked training (every chunk length, every
  placement — one device / blocked solves / sharded / bf16) is
  BYTE-IDENTICAL to the historical single-scan path, and a
  preempt-then-resume run is byte-identical to an uninterrupted one.
- Torn-file conformance: truncated blobs, truncated manifests
  (mid-multibyte included, mirroring the PR-7 jsonlfs torn-tail test)
  and manifest-without-blob all fall back to the previous intact
  checkpoint; a foreign fingerprint refuses loudly.
- Chaos (``utils/faults.py`` + real signals, ``chaos`` marker): a
  kill-9'd training subprocess resumes to byte-identical factors; an
  injected torn checkpoint write recovers; SIGTERM drains within one
  chunk into a clean exit 0.
- Model-blob integrity (satellite): the sha256 envelope refuses torn /
  corrupted blobs on every Models backend; legacy blobs still load.
"""

import io
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from predictionio_tpu.ops.als import (
    ALSParams,
    bucket_ratings_pair,
    train_als_bucketed,
    warmup_train_als_bucketed,
)
from predictionio_tpu.utils import faults, metrics
from predictionio_tpu.workflow import checkpoint
from predictionio_tpu.workflow.checkpoint import (
    CheckpointMismatchError,
    TrainingDivergedError,
    TrainingPreempted,
    chunk_schedule,
)


def make_triples(seed=0, n_u=50, n_i=30, nnz=400):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n_u, nnz)
    cols = rng.integers(0, n_i, nnz)
    vals = (rng.random(nnz).astype(np.float32) + 0.5)
    return rows, cols, vals, n_u, n_i


def make_bucketed(seed=0, **kw):
    rows, cols, vals, n_u, n_i = make_triples(seed, **kw)
    return bucket_ratings_pair(rows, cols, vals, n_u, n_i)


PARAMS = ALSParams(rank=4, num_iterations=6, seed=3)


@pytest.fixture
def ckpt_env(tmp_path, monkeypatch):
    """Activate checkpointing into a fresh dir (every=2 by default) and
    guarantee the stop flag and injector never leak across tests."""
    d = tmp_path / "ckpts"
    monkeypatch.setenv("PIO_CHECKPOINT_DIR", str(d))
    monkeypatch.setenv("PIO_CHECKPOINT_EVERY", "2")
    checkpoint.clear_stop()
    yield d
    checkpoint.clear_stop()
    faults.clear()


def manifests(d):
    return sorted(f for f in os.listdir(d) if f.endswith(".json"))


class TestChunkSchedule:
    def test_schedule(self):
        assert chunk_schedule(6, 2) == [2, 2, 2]
        assert chunk_schedule(6, 4) == [4, 2]
        assert chunk_schedule(6, None) == [6]
        assert chunk_schedule(6, 0) == [6]
        assert chunk_schedule(6, 6) == [6]
        assert chunk_schedule(6, 99) == [6]
        assert chunk_schedule(0, 2) == []

    def test_resume_alignment(self):
        # saved steps are chunk boundaries; the remaining schedule from
        # any boundary reproduces the uninterrupted boundaries
        total, every = 10, 4
        boundaries = list(np.cumsum(chunk_schedule(total, every)))
        for k in boundaries[:-1]:
            rest = list(k + np.cumsum(chunk_schedule(total - k, every)))
            assert rest == [b for b in boundaries if b > k]


class TestChunkedDifferential:
    """Chunked == unchunked, byte for byte: the per-iteration program
    (and with it every reduction order) is unchanged; only the scan
    trip count splits."""

    def test_bucketed(self, ckpt_env, monkeypatch):
        user_side, item_side = make_bucketed()
        monkeypatch.delenv("PIO_CHECKPOINT_DIR")
        X0, Y0 = train_als_bucketed(user_side, item_side, PARAMS)
        monkeypatch.setenv("PIO_CHECKPOINT_DIR", str(ckpt_env))
        for every in ("1", "2", "4"):
            monkeypatch.setenv("PIO_CHECKPOINT_EVERY", every)
            X1, Y1 = train_als_bucketed(user_side, item_side, PARAMS)
            assert np.array_equal(X0, X1) and np.array_equal(Y0, Y1)
        assert manifests(ckpt_env)  # checkpoints actually landed

    def test_slot_budget_blocked_solve(self, ckpt_env, monkeypatch):
        user_side, item_side = make_bucketed()
        # 256 slots: the 56 x 16 user table runs as lax.map blocks
        params = ALSParams(rank=4, num_iterations=6, seed=3,
                           bucket_slot_budget=256)
        monkeypatch.delenv("PIO_CHECKPOINT_DIR")
        X0, Y0 = train_als_bucketed(user_side, item_side, params)
        monkeypatch.setenv("PIO_CHECKPOINT_DIR", str(ckpt_env))
        X1, Y1 = train_als_bucketed(user_side, item_side, params)
        assert np.array_equal(X0, X1) and np.array_equal(Y0, Y1)

    def test_bf16(self, ckpt_env, monkeypatch):
        # the checkpoint stores fp32 host factors, but bf16 -> fp32 ->
        # bf16 is lossless, so the crash-safe lane stays byte-identical
        # under the bf16 policy too
        user_side, item_side = make_bucketed()
        params = ALSParams(rank=4, num_iterations=6, seed=3,
                           precision="bf16")
        monkeypatch.delenv("PIO_CHECKPOINT_DIR")
        X0, Y0 = train_als_bucketed(user_side, item_side, params)
        monkeypatch.setenv("PIO_CHECKPOINT_DIR", str(ckpt_env))
        X1, Y1 = train_als_bucketed(user_side, item_side, params)
        assert np.array_equal(X0, X1) and np.array_equal(Y0, Y1)

    def test_bucketed_sharded(self, ckpt_env, monkeypatch):
        # single-host sharded training checkpoints too (np.asarray
        # gathers the factor shards per chunk)
        from predictionio_tpu.parallel.als_sharding import (
            train_als_bucketed_sharded)
        from predictionio_tpu.parallel.mesh import data_parallel_mesh

        user_side, item_side = make_bucketed(n_u=48, n_i=32)
        monkeypatch.delenv("PIO_CHECKPOINT_DIR")
        X0, Y0 = train_als_bucketed_sharded(user_side, item_side,
                                            PARAMS, data_parallel_mesh())
        monkeypatch.setenv("PIO_CHECKPOINT_DIR", str(ckpt_env))
        X1, Y1 = train_als_bucketed_sharded(user_side, item_side,
                                            PARAMS, data_parallel_mesh())
        assert np.array_equal(X0, X1) and np.array_equal(Y0, Y1)
        assert manifests(ckpt_env)


class TestCheckpointFiles:
    def test_manifest_contents(self, ckpt_env):
        user_side, item_side = make_bucketed()
        train_als_bucketed(user_side, item_side, PARAMS)
        names = manifests(ckpt_env)
        assert names == ["ckpt-00000002.json", "ckpt-00000004.json",
                         "ckpt-00000006.json"]
        with open(ckpt_env / names[-1], encoding="utf-8") as f:
            m = json.load(f)
        assert m["step"] == 6 and m["totalIterations"] == 6
        assert m["shapes"] == {"X": [50, 4], "Y": [30, 4]}
        blob = (ckpt_env / m["file"]).read_bytes()
        import hashlib

        assert hashlib.sha256(blob).hexdigest() == m["sha256"]
        with np.load(io.BytesIO(blob)) as z:
            assert z["X"].dtype == np.float32  # host persistence policy

    def test_retention_keeps_last_n(self, ckpt_env, monkeypatch):
        monkeypatch.setenv("PIO_CHECKPOINT_EVERY", "1")
        monkeypatch.setenv("PIO_CHECKPOINT_KEEP", "2")
        user_side, item_side = make_bucketed()
        train_als_bucketed(user_side, item_side, PARAMS)
        assert manifests(ckpt_env) == ["ckpt-00000005.json",
                                       "ckpt-00000006.json"]
        # blobs of dropped steps are gone too
        assert sorted(f for f in os.listdir(ckpt_env)
                      if f.endswith(".npz")) == \
            ["ckpt-00000005.npz", "ckpt-00000006.npz"]

    def test_retention_sweeps_orphan_blobs(self, ckpt_env,
                                           monkeypatch):
        # a blob whose manifest never landed (crash in the
        # blob->manifest window) is invisible to resume and must not
        # outlive retention — factor blobs are the bytes that matter
        monkeypatch.setenv("PIO_CHECKPOINT_EVERY", "2")
        monkeypatch.setenv("PIO_CHECKPOINT_KEEP", "2")
        os.makedirs(ckpt_env, exist_ok=True)
        (ckpt_env / "ckpt-00000099.npz").write_bytes(b"orphan")
        user_side, item_side = make_bucketed()
        train_als_bucketed(user_side, item_side, PARAMS)
        assert not (ckpt_env / "ckpt-00000099.npz").exists()


class TestPreemptResume:
    def test_preempt_then_resume_byte_identical(self, ckpt_env,
                                                monkeypatch):
        user_side, item_side = make_bucketed()
        monkeypatch.delenv("PIO_CHECKPOINT_DIR")
        X0, Y0 = train_als_bucketed(user_side, item_side, PARAMS)
        monkeypatch.setenv("PIO_CHECKPOINT_DIR", str(ckpt_env))
        checkpoint.request_stop()
        with pytest.raises(TrainingPreempted):
            train_als_bucketed(user_side, item_side, PARAMS)
        checkpoint.clear_stop()
        assert manifests(ckpt_env) == ["ckpt-00000002.json"]
        saved = metrics.TRAIN_CHECKPOINTS.value(status="resumed")
        monkeypatch.setenv("PIO_RESUME", "1")
        X1, Y1 = train_als_bucketed(user_side, item_side, PARAMS)
        assert np.array_equal(X0, X1) and np.array_equal(Y0, Y1)
        assert metrics.TRAIN_CHECKPOINTS.value(status="resumed") \
            == saved + 1

    def test_resume_empty_dir_is_fresh_start(self, ckpt_env,
                                             monkeypatch):
        user_side, item_side = make_bucketed()
        monkeypatch.delenv("PIO_CHECKPOINT_DIR")
        X0, _ = train_als_bucketed(user_side, item_side, PARAMS)
        monkeypatch.setenv("PIO_CHECKPOINT_DIR", str(ckpt_env))
        monkeypatch.setenv("PIO_RESUME", "1")
        X1, _ = train_als_bucketed(user_side, item_side, PARAMS)
        assert np.array_equal(X0, X1)

    def test_resume_at_total_loads_final(self, ckpt_env, monkeypatch):
        user_side, item_side = make_bucketed()
        monkeypatch.setenv("PIO_RESUME", "1")
        X0, Y0 = train_als_bucketed(user_side, item_side, PARAMS)
        # second run resumes from the step==total checkpoint: zero
        # further iterations, same factors
        X1, Y1 = train_als_bucketed(user_side, item_side, PARAMS)
        assert np.array_equal(X0, X1) and np.array_equal(Y0, Y1)

    def test_resume_with_different_chunk_size(self, ckpt_env,
                                              monkeypatch):
        # chunking is an execution knob: a checkpoint from an every=2
        # run resumes under every=3 and still lands byte-identical
        user_side, item_side = make_bucketed()
        monkeypatch.delenv("PIO_CHECKPOINT_DIR")
        X0, Y0 = train_als_bucketed(user_side, item_side, PARAMS)
        monkeypatch.setenv("PIO_CHECKPOINT_DIR", str(ckpt_env))
        checkpoint.request_stop()
        with pytest.raises(TrainingPreempted):
            train_als_bucketed(user_side, item_side, PARAMS)
        checkpoint.clear_stop()
        monkeypatch.setenv("PIO_CHECKPOINT_EVERY", "3")
        monkeypatch.setenv("PIO_RESUME", "1")
        X1, Y1 = train_als_bucketed(user_side, item_side, PARAMS)
        assert np.array_equal(X0, X1) and np.array_equal(Y0, Y1)


class TestTornRecovery:
    """Torn-file detection with fallback to the previous intact
    checkpoint — every way a crash can shear the pair."""

    def _run_to_completion_keeping_all(self, ckpt_env, monkeypatch):
        monkeypatch.setenv("PIO_CHECKPOINT_KEEP", "10")
        user_side, item_side = make_bucketed()
        monkeypatch.delenv("PIO_CHECKPOINT_DIR")
        X0, Y0 = train_als_bucketed(user_side, item_side, PARAMS)
        monkeypatch.setenv("PIO_CHECKPOINT_DIR", str(ckpt_env))
        train_als_bucketed(user_side, item_side, PARAMS)
        return user_side, item_side, X0, Y0

    def test_torn_blob_falls_back(self, ckpt_env, monkeypatch):
        us, its, X0, Y0 = self._run_to_completion_keeping_all(
            ckpt_env, monkeypatch)
        blob = (ckpt_env / "ckpt-00000006.npz").read_bytes()
        (ckpt_env / "ckpt-00000006.npz").write_bytes(
            blob[:len(blob) // 2])  # sheared mid-write
        torn0 = metrics.TRAIN_CHECKPOINTS.value(status="torn_skipped")
        monkeypatch.setenv("PIO_RESUME", "1")
        X1, Y1 = train_als_bucketed(us, its, PARAMS)  # resumes from step 4
        assert np.array_equal(X0, X1) and np.array_equal(Y0, Y1)
        assert metrics.TRAIN_CHECKPOINTS.value(
            status="torn_skipped") == torn0 + 1

    def test_torn_manifest_mid_multibyte(self, ckpt_env, monkeypatch):
        us, its, X0, Y0 = self._run_to_completion_keeping_all(
            ckpt_env, monkeypatch)
        # a manifest carrying multibyte UTF-8, truncated INSIDE a
        # multibyte sequence (the jsonlfs torn-tail shape): the reader
        # must treat it as torn, not crash on the decode
        path = ckpt_env / "ckpt-00000006.json"
        with open(path, encoding="utf-8") as f:
            m = json.load(f)
        m["note"] = "préemption événement"
        raw = json.dumps(m, ensure_ascii=False).encode("utf-8")
        cut = raw.rindex("é".encode("utf-8")) + 1  # mid-char
        path.write_bytes(raw[:cut])
        monkeypatch.setenv("PIO_RESUME", "1")
        X1, Y1 = train_als_bucketed(us, its, PARAMS)
        assert np.array_equal(X0, X1) and np.array_equal(Y0, Y1)

    def test_manifest_without_blob_falls_back(self, ckpt_env,
                                              monkeypatch):
        us, its, X0, Y0 = self._run_to_completion_keeping_all(
            ckpt_env, monkeypatch)
        os.unlink(ckpt_env / "ckpt-00000006.npz")
        monkeypatch.setenv("PIO_RESUME", "1")
        X1, Y1 = train_als_bucketed(us, its, PARAMS)
        assert np.array_equal(X0, X1) and np.array_equal(Y0, Y1)

    def test_all_torn_is_fresh_start(self, ckpt_env, monkeypatch):
        us, its, X0, Y0 = self._run_to_completion_keeping_all(
            ckpt_env, monkeypatch)
        for f in os.listdir(ckpt_env):
            p = ckpt_env / f
            if p.is_file():  # skip the runs/ history subdir
                p.write_bytes(p.read_bytes()[:10])
        monkeypatch.setenv("PIO_RESUME", "1")
        X1, Y1 = train_als_bucketed(us, its, PARAMS)
        assert np.array_equal(X0, X1) and np.array_equal(Y0, Y1)

    def test_injected_torn_checkpoint_then_resume(self, ckpt_env,
                                                  monkeypatch):
        """utils/faults.py chaos lane: the SECOND checkpoint write
        shears mid-blob (partial bytes at the final path, no manifest)
        and fails the run; --resume falls back to the first checkpoint
        and completes byte-identically."""
        user_side, item_side = make_bucketed()
        monkeypatch.delenv("PIO_CHECKPOINT_DIR")
        X0, Y0 = train_als_bucketed(user_side, item_side, PARAMS)
        monkeypatch.setenv("PIO_CHECKPOINT_DIR", str(ckpt_env))
        faults.install(
            "backend=checkpoint,op=save,kind=torn,after=1,times=1")
        try:
            with pytest.raises(faults.InjectedTornWrite):
                train_als_bucketed(user_side, item_side, PARAMS)
        finally:
            faults.clear()
        assert manifests(ckpt_env) == ["ckpt-00000002.json"]
        assert (ckpt_env / "ckpt-00000004.npz").exists()  # the shear
        monkeypatch.setenv("PIO_RESUME", "1")
        X1, Y1 = train_als_bucketed(user_side, item_side, PARAMS)
        assert np.array_equal(X0, X1) and np.array_equal(Y0, Y1)


class TestFingerprint:
    def _checkpoints_for(self, ckpt_env, params, monkeypatch):
        user_side, item_side = make_bucketed()
        train_als_bucketed(user_side, item_side, params)
        assert manifests(ckpt_env)
        return user_side, item_side

    def test_params_change_refused(self, ckpt_env, monkeypatch):
        us, its = self._checkpoints_for(ckpt_env, PARAMS, monkeypatch)
        monkeypatch.setenv("PIO_RESUME", "1")
        with pytest.raises(CheckpointMismatchError):
            train_als_bucketed(us, its, ALSParams(
                rank=4, num_iterations=6, seed=3, lambda_=0.02))

    def test_precision_change_refused(self, ckpt_env, monkeypatch):
        us, its = self._checkpoints_for(ckpt_env, PARAMS, monkeypatch)
        monkeypatch.setenv("PIO_RESUME", "1")
        monkeypatch.setenv("PIO_ALS_PRECISION", "bf16")
        with pytest.raises(CheckpointMismatchError):
            train_als_bucketed(us, its, PARAMS)

    def test_solver_change_refused(self, ckpt_env, monkeypatch):
        us, its = self._checkpoints_for(ckpt_env, PARAMS, monkeypatch)
        monkeypatch.setenv("PIO_RESUME", "1")
        monkeypatch.setenv("PIO_ALS_SOLVER", "lanes")
        with pytest.raises(CheckpointMismatchError):
            train_als_bucketed(us, its, PARAMS)

    def test_layout_change_refused(self, ckpt_env, monkeypatch):
        self._checkpoints_for(ckpt_env, PARAMS, monkeypatch)
        monkeypatch.setenv("PIO_RESUME", "1")
        us2, its2 = make_bucketed(seed=9, n_u=64, n_i=40, nnz=500)
        with pytest.raises(CheckpointMismatchError):
            train_als_bucketed(us2, its2, PARAMS)

    def test_checkpoint_every_not_in_fingerprint(self):
        a = checkpoint.training_fingerprint(
            ("bucketed",), ALSParams(checkpoint_every=2), "cho", "fp32")
        b = checkpoint.training_fingerprint(
            ("bucketed",), ALSParams(checkpoint_every=5), "cho", "fp32")
        assert a == b
        c = checkpoint.training_fingerprint(
            ("bucketed",), ALSParams(lambda_=0.5), "cho", "fp32")
        assert a != c

    def test_bimap_scope_changes_fingerprint(self):
        from predictionio_tpu.data.bimap import StringIndexBiMap

        m1 = StringIndexBiMap(["a", "b"])
        m2 = StringIndexBiMap(["a", "c"])
        base = checkpoint.training_fingerprint(
            ("bucketed",), ALSParams(), "cho", "fp32")
        with checkpoint.fingerprint_scope(checkpoint.bimap_digest(m1)):
            fp1 = checkpoint.training_fingerprint(
                ("bucketed",), ALSParams(), "cho", "fp32")
        with checkpoint.fingerprint_scope(checkpoint.bimap_digest(m2)):
            fp2 = checkpoint.training_fingerprint(
                ("bucketed",), ALSParams(), "cho", "fp32")
        assert len({base, fp1, fp2}) == 3
        # digest is order-sensitive and injective across map boundaries
        assert checkpoint.bimap_digest(m1) != checkpoint.bimap_digest(
            StringIndexBiMap(["b", "a"]))
        assert checkpoint.bimap_digest(m1, m2) != \
            checkpoint.bimap_digest(m2, m1)


class TestDivergenceGuard:
    def _nan_sides(self):
        rows, cols, vals, n_u, n_i = make_triples()
        vals = vals.copy()
        vals[7] = np.nan
        return bucket_ratings_pair(rows, cols, vals, n_u, n_i)

    def test_nan_aborts_with_metric(self, ckpt_env):
        us, its = self._nan_sides()
        before = metrics.TRAIN_DIVERGED.value()
        with pytest.raises(TrainingDivergedError):
            train_als_bucketed(us, its, PARAMS)
        assert metrics.TRAIN_DIVERGED.value() == before + 1
        # the poisoned state was never checkpointed
        assert manifests(ckpt_env) == []

    def test_last_good_checkpoints_retained(self, ckpt_env,
                                            monkeypatch):
        monkeypatch.setenv("PIO_CHECKPOINT_KEEP", "10")
        user_side, item_side = make_bucketed()
        train_als_bucketed(user_side, item_side, PARAMS)
        kept = {f: (ckpt_env / f).read_bytes()
                for f in os.listdir(ckpt_env)
                if (ckpt_env / f).is_file()}  # runs/ is history, not ckpt
        us, its = self._nan_sides()
        with pytest.raises(TrainingDivergedError):
            train_als_bucketed(us, its, PARAMS)
        assert {f: (ckpt_env / f).read_bytes()
                for f in os.listdir(ckpt_env)
                if (ckpt_env / f).is_file()} == kept

    def test_no_guard_cost_when_off(self, monkeypatch):
        # without a checkpoint dir the single-scan path runs untouched
        monkeypatch.delenv("PIO_CHECKPOINT_DIR", raising=False)
        us, its = self._nan_sides()
        X, _ = train_als_bucketed(us, its, PARAMS)  # historical behavior: no
        assert not np.isfinite(X).all()    # guard, NaN flows out


class TestWarmupCoversChunks:
    def test_chunked_steady_state_compiles_nothing(self, ckpt_env,
                                                   monkeypatch):
        """The AOT warm-up lowers every distinct chunk trip count, so
        chunked training keeps the PR-6 zero-recompile contract: after
        one warmed chunked run, a second identical run compiles ZERO
        new programs (asserted via the jit monitor, not eyeballed)."""
        monkeypatch.setenv("PIO_CHECKPOINT_EVERY", "4")  # chunks [4, 2]
        user_side, item_side = make_bucketed(seed=4)
        assert warmup_train_als_bucketed(user_side, item_side, PARAMS)
        assert metrics.install_jit_compile_listener()
        train_als_bucketed(user_side, item_side, PARAMS)
        c0 = metrics.JIT_COMPILES.value()
        train_als_bucketed(user_side, item_side, PARAMS)
        assert metrics.JIT_COMPILES.value() == c0


class TestModelBlobIntegrity:
    """Satellite: sha256 integrity on model load, every backend. The
    envelope lives in serialize/deserialize_models so the blob is
    protected end to end no matter which Models DAO stores it."""

    def _models_dao(self, backend, tmp_path, request):
        from predictionio_tpu.data import storage

        if backend == "localfs":
            from predictionio_tpu.data.storage.localfs import (
                LocalFSModels)

            return LocalFSModels({"path": str(tmp_path / "models")})
        request.getfixturevalue(
            "mem_storage" if backend == "memory" else "sqlite_storage")
        return storage.get_model_data_models()

    @pytest.mark.parametrize("backend", ["memory", "sqlite", "localfs"])
    def test_round_trip_and_corruption_refused(self, backend, tmp_path,
                                               request):
        from predictionio_tpu.data.storage.base import Model
        from predictionio_tpu.workflow import (
            ModelIntegrityError,
            deserialize_models,
            serialize_models,
        )

        dao = self._models_dao(backend, tmp_path, request)
        blob = serialize_models([{"w": [1.0, 2.0]}, "second"])
        dao.insert(Model(id="ei_1", models=blob))
        assert deserialize_models(dao.get("ei_1").models) == [
            {"w": [1.0, 2.0]}, "second"]

        # flipped byte mid-payload -> loud refusal, not a garbage model
        corrupt = bytearray(blob)
        corrupt[len(corrupt) // 2] ^= 0xFF
        dao.insert(Model(id="ei_2", models=bytes(corrupt)))
        with pytest.raises(ModelIntegrityError):
            deserialize_models(dao.get("ei_2").models)

        # torn (truncated) blob -> same refusal
        dao.insert(Model(id="ei_3", models=blob[:len(blob) - 7]))
        with pytest.raises(ModelIntegrityError):
            deserialize_models(dao.get("ei_3").models)

    def test_torn_file_on_disk_refused(self, tmp_path):
        # the localfs flavor of the same fault, sheared ON DISK under
        # the DAO (as a crashed non-atomic writer would leave it)
        from predictionio_tpu.data.storage.base import Model
        from predictionio_tpu.data.storage.localfs import LocalFSModels
        from predictionio_tpu.workflow import (
            ModelIntegrityError,
            deserialize_models,
            serialize_models,
        )

        dao = LocalFSModels({"path": str(tmp_path / "models")})
        dao.insert(Model(id="ei", models=serialize_models([1, 2, 3])))
        [fname] = os.listdir(tmp_path / "models")
        path = tmp_path / "models" / fname
        path.write_bytes(path.read_bytes()[:-9])
        with pytest.raises(ModelIntegrityError):
            deserialize_models(dao.get("ei").models)

    def test_legacy_blob_still_loads(self):
        import pickle

        from predictionio_tpu.workflow import deserialize_models

        legacy = pickle.dumps(["old", "model"],
                              protocol=pickle.HIGHEST_PROTOCOL)
        assert deserialize_models(legacy) == ["old", "model"]


class TestCLIFlags:
    def _args(self, **kw):
        import argparse

        ns = argparse.Namespace(
            checkpoint_every=None, checkpoint_dir=None,
            checkpoint_keep=None, resume=False)
        for k, v in kw.items():
            setattr(ns, k, v)
        return ns

    def test_parser_accepts_flags(self):
        from predictionio_tpu.tools.cli import build_parser

        args = build_parser().parse_args(
            ["train", "--checkpoint-every", "5", "--checkpoint-dir",
             "/tmp/ck", "--checkpoint-keep", "4", "--resume"])
        assert args.checkpoint_every == 5
        assert args.checkpoint_dir == "/tmp/ck"
        assert args.checkpoint_keep == 4
        assert args.resume is True

    def test_flags_set_env(self, tmp_path, monkeypatch):
        from predictionio_tpu.tools.run_commands import (
            _apply_checkpoint_flags)

        for var in ("PIO_CHECKPOINT_DIR", "PIO_CHECKPOINT_EVERY",
                    "PIO_CHECKPOINT_KEEP", "PIO_RESUME"):
            monkeypatch.delenv(var, raising=False)
        monkeypatch.setattr(os, "environ", dict(os.environ))
        # don't rebind the test runner's real SIGTERM/SIGINT handlers
        monkeypatch.setattr(checkpoint, "install_signal_handlers",
                            lambda: True)
        _apply_checkpoint_flags(self._args(
            checkpoint_every=3, checkpoint_dir=str(tmp_path),
            checkpoint_keep=5, resume=True))
        assert os.environ["PIO_CHECKPOINT_EVERY"] == "3"
        assert os.environ["PIO_CHECKPOINT_DIR"] == str(tmp_path)
        assert os.environ["PIO_CHECKPOINT_KEEP"] == "5"
        assert os.environ["PIO_RESUME"] == "1"

    def test_every_without_dir_refused(self, monkeypatch):
        from predictionio_tpu.tools.run_commands import (
            _apply_checkpoint_flags)

        for var in ("PIO_CHECKPOINT_EVERY", "PIO_CHECKPOINT_DIR",
                    "PIO_RESUME"):
            monkeypatch.delenv(var, raising=False)
        with pytest.raises(SystemExit):
            _apply_checkpoint_flags(self._args(checkpoint_every=3))
        with pytest.raises(SystemExit):
            _apply_checkpoint_flags(self._args(resume=True))
        with pytest.raises(SystemExit):
            _apply_checkpoint_flags(self._args(
                checkpoint_every=0, checkpoint_dir="/tmp/x"))
        # a refused invocation must not half-apply: it used to leave
        # $PIO_RESUME/$PIO_CHECKPOINT_EVERY behind in the REAL environ,
        # silently turning every later in-process training into a
        # resume (this test has no environ sandbox on purpose)
        for var in ("PIO_CHECKPOINT_EVERY", "PIO_CHECKPOINT_DIR",
                    "PIO_RESUME"):
            assert var not in os.environ

    def test_dir_alone_installs_no_handlers(self, tmp_path,
                                            monkeypatch):
        # a dir with no cadence runs the single-scan path: installing
        # drain handlers would swallow the first SIGTERM against a
        # stop flag no chunk boundary will ever honor
        from predictionio_tpu.tools.run_commands import (
            _apply_checkpoint_flags)

        for var in ("PIO_CHECKPOINT_DIR", "PIO_CHECKPOINT_EVERY",
                    "PIO_RESUME"):
            monkeypatch.delenv(var, raising=False)
        monkeypatch.setattr(os, "environ", dict(os.environ))
        calls = []
        monkeypatch.setattr(checkpoint, "install_signal_handlers",
                            lambda: calls.append(1))
        _apply_checkpoint_flags(self._args(
            checkpoint_dir=str(tmp_path)))
        assert calls == []
        _apply_checkpoint_flags(self._args(
            checkpoint_dir=str(tmp_path), checkpoint_every=2))
        assert calls == [1]


WORKER = os.path.join(os.path.dirname(__file__), "train_ckpt_worker.py")


def _worker_env(ckpt_dir, **extra):
    env = dict(os.environ)
    env.pop("PIO_FAULTS", None)
    env.pop("PIO_RESUME", None)
    repo_root = os.path.dirname(os.path.dirname(WORKER))
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": repo_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH")
            else ""),
        "PIO_CHECKPOINT_DIR": str(ckpt_dir),
        "PIO_CHECKPOINT_EVERY": "1",
        "PIO_CHECKPOINT_KEEP": "50",
    })
    env.update({k: str(v) for k, v in extra.items()})
    return env


def _wait_for(predicate, timeout=60.0, interval=0.02):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if predicate():
            return True
        time.sleep(interval)
    return False


@pytest.mark.chaos
class TestChaosSubprocess:
    """Real-process chaos: the PIO_FAULTS slow rule on checkpoint saves
    is the deterministic window the parent uses to catch the worker
    mid-run."""

    def _reference_factors(self, monkeypatch):
        # the uninterrupted run, in-process: same problem, same code
        # path, checkpointing off
        from tests.train_ckpt_worker import build_inputs

        for var in ("PIO_CHECKPOINT_DIR", "PIO_CHECKPOINT_EVERY",
                    "PIO_RESUME", "PIO_FAULTS"):
            monkeypatch.delenv(var, raising=False)
        us, its, params = build_inputs()
        return train_als_bucketed(us, its, params)

    def test_kill9_then_resume_byte_identical(self, tmp_path,
                                              monkeypatch):
        X0, Y0 = self._reference_factors(monkeypatch)
        ckpt_dir = tmp_path / "ck"
        out = tmp_path / "final.npz"
        # ~0.35s per checkpoint save keeps the run alive long enough
        # to kill-9 it deterministically after the 2nd checkpoint
        proc = subprocess.Popen(
            [sys.executable, WORKER, str(out)],
            env=_worker_env(
                ckpt_dir,
                PIO_FAULTS="backend=checkpoint,op=save,kind=slow,"
                           "delay=0.35"),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        try:
            if not _wait_for(
                    lambda: (ckpt_dir / "ckpt-00000002.json").exists()):
                proc.kill()
                pytest.fail("no checkpoint appeared: %r"
                            % proc.communicate()[0])
            assert proc.poll() is None, "worker finished before kill-9"
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:  # pragma: no cover - cleanup
                proc.kill()
        assert not out.exists()
        # resume in a fresh process: byte-identical final factors
        rc = subprocess.run(
            [sys.executable, WORKER, str(out)],
            env=_worker_env(ckpt_dir, PIO_RESUME="1"),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            timeout=120)
        assert rc.returncode == 0, rc.stdout
        with np.load(out) as z:
            assert np.array_equal(z["X"], X0)
            assert np.array_equal(z["Y"], Y0)

    def test_sigterm_drains_within_one_chunk(self, tmp_path,
                                             monkeypatch):
        X0, Y0 = self._reference_factors(monkeypatch)
        ckpt_dir = tmp_path / "ck"
        out = tmp_path / "final.npz"
        proc = subprocess.Popen(
            [sys.executable, WORKER, str(out)],
            env=_worker_env(
                ckpt_dir,
                PIO_FAULTS="backend=checkpoint,op=save,kind=slow,"
                           "delay=0.35"),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        if not _wait_for(
                lambda: (ckpt_dir / "ckpt-00000001.json").exists()):
            proc.kill()
            pytest.fail("no checkpoint appeared: %r"
                        % proc.communicate()[0])
        assert proc.poll() is None, "worker finished before SIGTERM"
        t0 = time.monotonic()
        proc.terminate()  # SIGTERM: graceful drain, NOT a traceback
        stdout, _ = proc.communicate(timeout=60)
        drained = time.monotonic() - t0
        assert proc.returncode == 0, stdout
        assert b"Training interrupted" in stdout
        assert b"Traceback" not in stdout
        # drained within ~one chunk (1 iteration + one slowed save +
        # process teardown), not the rest of the run
        assert drained < 20.0
        assert not out.exists()  # no final factors: preempted
        steps = sorted(ckpt_dir.glob("ckpt-*.json"))
        assert steps  # a final checkpoint committed before exit
        # and the saved state resumes to byte-identical factors
        rc = subprocess.run(
            [sys.executable, WORKER, str(out)],
            env=_worker_env(ckpt_dir, PIO_RESUME="1"),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            timeout=120)
        assert rc.returncode == 0, rc.stdout
        with np.load(out) as z:
            assert np.array_equal(z["X"], X0)
            assert np.array_equal(z["Y"], Y0)


class TestWorkflowEndToEnd:
    """run_train through the DASE engine: preempt -> resume -> the
    COMPLETED instance's persisted model equals a clean train's."""

    def test_preempt_resume_model_equals_clean(self, mem_storage,
                                               tmp_path, monkeypatch):
        from predictionio_tpu.data import storage
        from tests.test_foldin import _seed_app, _train

        _seed_app("ckapp")
        iid_clean = _train("ckapp")
        blob_clean = storage.get_model_data_models().get(iid_clean)

        monkeypatch.setenv("PIO_CHECKPOINT_DIR", str(tmp_path / "ck"))
        monkeypatch.setenv("PIO_CHECKPOINT_EVERY", "1")
        checkpoint.request_stop()
        try:
            with pytest.raises(TrainingPreempted):
                _train("ckapp")
        finally:
            checkpoint.clear_stop()
        # the preempted instance is terminal, not a phantom
        # in-progress training (preempt->resume is a routine loop)
        interrupted = [
            i for i in
            storage.get_metadata_engine_instances().get_all()
            if i.status == "INTERRUPTED"]
        assert len(interrupted) == 1
        monkeypatch.setenv("PIO_RESUME", "1")
        iid_resumed = _train("ckapp")
        monkeypatch.delenv("PIO_CHECKPOINT_DIR")

        from predictionio_tpu.workflow import deserialize_models

        [clean] = deserialize_models(blob_clean.models)
        [resumed] = deserialize_models(
            storage.get_model_data_models().get(iid_resumed).models)
        assert np.array_equal(clean.user_factors, resumed.user_factors)
        assert np.array_equal(clean.item_factors, resumed.item_factors)
