"""Training-plane observability suite (ISSUE 17: ops/als.py
``training_objective`` + workflow/runlog.py + the telemetry-aware
chunk loops + ``pio runs``).

- Objective correctness: the fused on-device pack matches dense numpy
  references for both the implicit (Hu-Koren-Volinsky) and explicit
  (ALS-WR) losses, duplicates summed, and the fused ``finite``
  element flags non-finite factors.
- Observer purity: telemetry-on factors are BYTE-IDENTICAL to
  telemetry-off across the one-device / sharded / grid / bf16
  lanes (``PIO_TRAIN_TELEMETRY=0`` is the kill switch), and the loss
  decreases monotonically on the seeded smoke shape.
- Run-log crash-safety: a preempted-then-resumed run appends to the
  SAME run id with a monotone step sequence; a torn trailing JSONL
  line (kill mid-append) is tolerated by readers and repaired on
  ``--resume``.
- Graded divergence reporting: ``TrainingDivergedError`` names the
  failing chunk and quotes the last finite loss sample; the grid
  variant lists exactly which config indices died and when.
- Surfaces: ``pio runs list|show|compare`` renders real run history
  (ASCII loss curve included), the grid leaderboard rows carry
  per-config loss trajectories, and ``run_grid`` streams a usable
  partial leaderboard after each completed sub-batch.
"""

import os

import numpy as np
import pytest

from predictionio_tpu.ops.als import (
    ALSParams,
    bucket_ratings_pair,
    train_als_bucketed,
    training_objective,
)
from predictionio_tpu.ops.tuning import (
    grid_leaderboard,
    make_grid,
    train_als_grid_bucketed,
)
from predictionio_tpu.tools.cli import main as cli_main
from predictionio_tpu.utils import faults
from predictionio_tpu.workflow import checkpoint, runlog
from predictionio_tpu.workflow import tuning as wf_tuning
from predictionio_tpu.workflow.checkpoint import (
    TrainingDivergedError,
    TrainingPreempted,
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


def unique_triples(seed=0, n_u=12, n_i=8, nnz=40):
    """Unique (u, i) pairs so dense references need no duplicate
    merging."""
    rng = np.random.default_rng(seed)
    flat = rng.choice(n_u * n_i, size=nnz, replace=False)
    rows = (flat // n_i).astype(np.int64)
    cols = (flat % n_i).astype(np.int64)
    vals = (rng.random(nnz).astype(np.float32) + 0.5)
    return rows, cols, vals, n_u, n_i


PARAMS = ALSParams(rank=4, num_iterations=6, seed=3)
GRID_BASE = ALSParams(rank=4, num_iterations=4, seed=3)


@pytest.fixture
def ckpt_env(tmp_path, monkeypatch):
    """Checkpointing into a fresh dir (every=2), telemetry at its
    default-on state, stop flag + injector cleared either side."""
    d = tmp_path / "ckpts"
    monkeypatch.setenv("PIO_CHECKPOINT_DIR", str(d))
    monkeypatch.setenv("PIO_CHECKPOINT_EVERY", "2")
    monkeypatch.delenv("PIO_TRAIN_TELEMETRY", raising=False)
    # fresh-start semantics are load-bearing here (separate runs must
    # get separate ids; on/off purity pairs must both actually train)
    monkeypatch.delenv("PIO_RESUME", raising=False)
    checkpoint.clear_stop()
    yield d
    checkpoint.clear_stop()
    faults.clear()


def one_run(ckpt_env):
    """The single run recorded under ``ckpt_env``, as read_run output."""
    runs = runlog.list_runs(str(ckpt_env))
    assert len(runs) == 1
    return runlog.read_run(runs[0]["path"])


class TestTrainingObjective:
    def test_implicit_matches_dense_reference(self):
        rows, cols, vals, n_u, n_i = unique_triples(seed=1)
        params = ALSParams(rank=3, lambda_=0.05, alpha=2.0)
        rng = np.random.default_rng(2)
        X = rng.normal(size=(n_u, 3)).astype(np.float32) * 0.3
        Y = rng.normal(size=(n_i, 3)).astype(np.float32) * 0.3
        us, _ = bucket_ratings_pair(rows, cols, vals, n_u, n_i)
        obj = training_objective(X, Y, us, params)

        # dense HKV loss over ALL pairs: c = 1 + alpha*r (observed),
        # 1 elsewhere; p = 1 iff observed
        R = np.zeros((n_u, n_i))
        R[rows, cols] = vals
        C = 1.0 + params.alpha * R
        P = (R > 0).astype(np.float64)
        S = X.astype(np.float64) @ Y.astype(np.float64).T
        fit = float((C * (P - S) ** 2).sum())
        l2 = params.lambda_ * float((X.astype(np.float64) ** 2).sum()
                                    + (Y.astype(np.float64) ** 2).sum())
        assert obj["finite"] is True
        np.testing.assert_allclose(obj["fit"], fit, rtol=2e-4)
        np.testing.assert_allclose(obj["l2"], l2, rtol=2e-4)
        np.testing.assert_allclose(obj["total"], fit + l2, rtol=2e-4)

    def test_explicit_matches_numpy_reference(self):
        rows, cols, vals, n_u, n_i = unique_triples(seed=3)
        params = ALSParams(rank=3, lambda_=0.07, implicit_prefs=False)
        rng = np.random.default_rng(4)
        X = rng.normal(size=(n_u, 3)).astype(np.float32) * 0.3
        Y = rng.normal(size=(n_i, 3)).astype(np.float32) * 0.3
        us, _ = bucket_ratings_pair(rows, cols, vals, n_u, n_i)
        obj = training_objective(X, Y, us, params)

        S = X.astype(np.float64) @ Y.astype(np.float64).T
        fit = float(((vals - S[rows, cols]) ** 2).sum())
        # ALS-WR count-weighted regularizer, both sides
        n_per_u = np.bincount(rows, minlength=n_u).astype(np.float64)
        n_per_i = np.bincount(cols, minlength=n_i).astype(np.float64)
        l2 = params.lambda_ * float(
            (n_per_u * (X.astype(np.float64) ** 2).sum(axis=1)).sum()
            + (n_per_i * (Y.astype(np.float64) ** 2).sum(axis=1)).sum())
        np.testing.assert_allclose(obj["fit"], fit, rtol=2e-4)
        np.testing.assert_allclose(obj["l2"], l2, rtol=2e-4)

    def test_duplicate_pairs_match_dense_reference(self):
        # 400 draws over 50 x 30: repeated pairs are summed into one
        # rating before the loss sees them
        rows, cols, vals, n_u, n_i = make_triples(seed=5)
        assert len(set(zip(rows.tolist(), cols.tolist()))) < len(rows)
        params = ALSParams(rank=4, lambda_=0.1, alpha=1.5)
        rng = np.random.default_rng(6)
        X = rng.normal(size=(n_u, 4)).astype(np.float32) * 0.2
        Y = rng.normal(size=(n_i, 4)).astype(np.float32) * 0.2
        us_b, _ = bucket_ratings_pair(rows, cols, vals, n_u, n_i)
        obj = training_objective(X, Y, us_b, params)
        R = np.zeros((n_u, n_i))
        np.add.at(R, (rows, cols), vals)
        S = X.astype(np.float64) @ Y.astype(np.float64).T
        fit = float(((1.0 + params.alpha * R) * ((R > 0) - S) ** 2).sum())
        l2 = params.lambda_ * float((X.astype(np.float64) ** 2).sum()
                                    + (Y.astype(np.float64) ** 2).sum())
        np.testing.assert_allclose(obj["fit"], fit, rtol=2e-4)
        np.testing.assert_allclose(obj["l2"], l2, rtol=2e-4)

    def test_nonfinite_factors_flagged(self):
        rows, cols, vals, n_u, n_i = unique_triples(seed=7)
        us, _ = bucket_ratings_pair(rows, cols, vals, n_u, n_i)
        X = np.zeros((n_u, 3), np.float32)
        Y = np.zeros((n_i, 3), np.float32)
        X[2, 1] = np.nan
        obj = training_objective(X, Y, us, ALSParams(rank=3))
        assert obj["finite"] is False


class TestObserverPurity:
    """PIO_TRAIN_TELEMETRY on vs off must land byte-identical factors
    on every lane: the objective only READS the carries."""

    def _on_off(self, monkeypatch, train):
        monkeypatch.setenv("PIO_TRAIN_TELEMETRY", "0")
        off = train()
        monkeypatch.setenv("PIO_TRAIN_TELEMETRY", "1")
        on = train()
        return off, on

    def test_bucketed(self, ckpt_env, monkeypatch):
        us, its = make_bucketed()
        (X0, Y0), (X1, Y1) = self._on_off(
            monkeypatch, lambda: train_als_bucketed(us, its, PARAMS))
        assert np.array_equal(X0, X1) and np.array_equal(Y0, Y1)
        # and the on lane actually recorded history
        assert runlog.list_runs(str(ckpt_env))

    def test_bf16(self, ckpt_env, monkeypatch):
        us, its = make_bucketed()
        params = ALSParams(rank=4, num_iterations=6, seed=3,
                           precision="bf16")
        (X0, Y0), (X1, Y1) = self._on_off(
            monkeypatch, lambda: train_als_bucketed(us, its, params))
        assert np.array_equal(X0, X1) and np.array_equal(Y0, Y1)

    @pytest.mark.multichip
    def test_sharded(self, ckpt_env, monkeypatch):
        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU scaffold")
        from predictionio_tpu.parallel import (
            data_parallel_mesh,
            train_als_bucketed_sharded,
        )

        mesh = data_parallel_mesh(8)
        us, its = make_bucketed()
        (X0, Y0), (X1, Y1) = self._on_off(
            monkeypatch,
            lambda: train_als_bucketed_sharded(us, its, PARAMS, mesh))
        assert np.array_equal(X0, X1) and np.array_equal(Y0, Y1)
        assert runlog.list_runs(str(ckpt_env))

    def test_grid(self, ckpt_env, monkeypatch):
        us, its = make_bucketed(seed=2)
        grid = make_grid(GRID_BASE, [{"lambda": 0.1}, {"lambda": 0.4}])
        r0, r1 = self._on_off(
            monkeypatch,
            lambda: train_als_grid_bucketed(us, its, grid))
        for i in range(grid.k):
            X0, Y0 = r0.factors_for(i)
            X1, Y1 = r1.factors_for(i)
            assert np.array_equal(X0, X1) and np.array_equal(Y0, Y1)
        assert r0.loss_history is None
        assert r1.loss_history  # per-chunk entries under checkpointing
        assert [e["step"] for e in r1.loss_history] == [2, 4]

    def test_loss_monotone_on_smoke_shape(self, ckpt_env, monkeypatch):
        monkeypatch.setenv("PIO_CHECKPOINT_EVERY", "1")
        us, its = make_bucketed(seed=9)
        train_als_bucketed(us, its, PARAMS)
        samples = one_run(ckpt_env)["samples"]
        totals = [runlog._loss_total(s) for s in samples]
        assert len(totals) == PARAMS.num_iterations
        assert all(t is not None for t in totals)
        # each ALS half-step minimizes its side exactly, so the
        # objective is non-increasing up to fp32 reduction noise
        for a, b in zip(totals, totals[1:]):
            assert b <= a * (1 + 1e-3) + 1e-6
        assert totals[-1] < totals[0]

    def test_kill_switch_writes_nothing(self, ckpt_env, monkeypatch):
        monkeypatch.setenv("PIO_TRAIN_TELEMETRY", "0")
        us, its = make_bucketed()
        train_als_bucketed(us, its, PARAMS)
        assert runlog.list_runs(str(ckpt_env)) == []


class TestRunLogCrashSafety:
    def _preempt(self, us, its):
        checkpoint.request_stop()
        try:
            with pytest.raises(TrainingPreempted):
                train_als_bucketed(us, its, PARAMS)
        finally:
            checkpoint.clear_stop()

    def test_resume_continues_same_run(self, ckpt_env, monkeypatch):
        us, its = make_bucketed()
        self._preempt(us, its)
        interrupted = one_run(ckpt_env)
        assert [s["step"] for s in interrupted["samples"]] == [2]
        monkeypatch.setenv("PIO_RESUME", "1")
        train_als_bucketed(us, its, PARAMS)
        run = one_run(ckpt_env)  # still ONE run file
        assert run["runId"] == interrupted["runId"]
        steps = [s["step"] for s in run["samples"]]
        assert steps == [2, 4, 6]  # monotone, no duplicates
        assert all(s["runId"] == run["runId"] for s in run["samples"])

    def test_torn_tail_repaired_on_resume(self, ckpt_env, monkeypatch):
        us, its = make_bucketed()
        self._preempt(us, its)
        run = one_run(ckpt_env)
        path = runlog.run_path(str(ckpt_env), run["runId"])
        with open(path, "ab") as f:  # kill mid-append: no newline
            f.write(b'{"type":"sample","runId":"x","step":99')
        monkeypatch.setenv("PIO_RESUME", "1")
        train_als_bucketed(us, its, PARAMS)
        with open(path, "rb") as f:
            raw = f.read()
        # every surviving line parses; the torn fragment is gone
        assert raw.endswith(b"\n")
        assert b'"step":99' not in raw.replace(b" ", b"")
        steps = [s["step"] for s in one_run(ckpt_env)["samples"]]
        assert steps == [2, 4, 6]

    def test_phantom_future_sample_dropped_on_resume(self, ckpt_env,
                                                     monkeypatch):
        # a crash AFTER the append but BEFORE its checkpoint committed
        # leaves a sample past the resumed step: repair drops it so the
        # resumed history stays monotone without doubled steps
        us, its = make_bucketed()
        self._preempt(us, its)
        run = one_run(ckpt_env)
        path = runlog.run_path(str(ckpt_env), run["runId"])
        rl = runlog.RunLog(path, run["runId"])
        rl.append({"step": 4, "totalIterations": 6,
                   "loss": {"fit": 1.0, "l2": 1.0, "total": 2.0}})
        rl.close()
        monkeypatch.setenv("PIO_RESUME", "1")
        train_als_bucketed(us, its, PARAMS)
        steps = [s["step"] for s in one_run(ckpt_env)["samples"]]
        assert steps == [2, 4, 6]

    def test_reader_tolerates_torn_tail(self, ckpt_env):
        us, its = make_bucketed()
        train_als_bucketed(us, its, PARAMS)
        run = one_run(ckpt_env)
        path = runlog.run_path(str(ckpt_env), run["runId"])
        with open(path, "ab") as f:
            f.write(b'{"type":"sample","st')
        repaired = runlog.read_run(path)
        assert [s["step"] for s in repaired["samples"]] == [2, 4, 6]
        assert runlog.list_runs(str(ckpt_env))[0]["lastStep"] == 6

    def test_separate_trainings_get_separate_runs(self, ckpt_env):
        us, its = make_bucketed()
        train_als_bucketed(us, its, PARAMS)
        train_als_bucketed(us, its, PARAMS)  # fresh start, not a resume
        runs = runlog.list_runs(str(ckpt_env))
        assert len(runs) == 2
        assert runs[0]["runId"] != runs[1]["runId"]


class TestDivergedReporting:
    def _nan_sides(self):
        rows, cols, vals, n_u, n_i = make_triples()
        vals = vals.copy()
        vals[7] = np.nan
        return bucket_ratings_pair(rows, cols, vals, n_u, n_i)

    def test_serial_message_names_chunk_and_loss_state(self, ckpt_env):
        us, its = self._nan_sides()
        with pytest.raises(TrainingDivergedError) as ei:
            train_als_bucketed(us, its, PARAMS)
        msg = str(ei.value)
        assert "iteration 2/6" in msg
        assert "no finite loss sample was recorded" in msg

    def test_loss_clause_quotes_last_finite_sample(self):
        assert "no finite loss sample" in checkpoint._loss_clause(None)
        clause = checkpoint._loss_clause((4, 1.5, 0.25, 1.75))
        assert "total=1.75" in clause
        assert "fit=1.5" in clause and "l2=0.25" in clause
        assert "at iteration 4" in clause

    def test_grid_all_dead_names_config_indices(self, ckpt_env):
        us, its = make_bucketed(seed=6)
        grid = make_grid(GRID_BASE, [{"alpha": 1e38}, {"alpha": 2e38}])
        with pytest.raises(TrainingDivergedError) as ei:
            train_als_grid_bucketed(us, its, grid)
        msg = str(ei.value)
        assert "config 0 at iteration" in msg
        assert "config 1 at iteration" in msg


class TestRunsCli:
    def _interrupted_then_resumed(self, ckpt_env, monkeypatch):
        us, its = make_bucketed()
        checkpoint.request_stop()
        try:
            with pytest.raises(TrainingPreempted):
                train_als_bucketed(us, its, PARAMS)
        finally:
            checkpoint.clear_stop()
        monkeypatch.setenv("PIO_RESUME", "1")
        train_als_bucketed(us, its, PARAMS)
        monkeypatch.delenv("PIO_RESUME")
        return one_run(ckpt_env)["runId"]

    def test_list_show_compare(self, ckpt_env, monkeypatch, capsys):
        rid = self._interrupted_then_resumed(ckpt_env, monkeypatch)
        d = str(ckpt_env)

        assert cli_main(["runs", "list", "--dir", d]) == 0
        out = capsys.readouterr().out
        assert rid in out and "6/6" in out

        # the acceptance surface: a loss curve rendered from a REAL
        # interrupted-then-resumed run's history
        assert cli_main(["runs", "show", rid, "--dir", d]) == 0
        out = capsys.readouterr().out
        assert rid in out
        assert "*" in out  # chart sample markers
        assert "TOTAL" in out  # per-sample table

        # unique-prefix resolution
        assert cli_main(["runs", "show", rid[:16], "--dir", d]) == 0
        capsys.readouterr()

        us, its = make_bucketed()
        train_als_bucketed(us, its, PARAMS)  # a second run to diff against
        runs = runlog.list_runs(d)
        assert len(runs) == 2
        other = next(r["runId"] for r in runs if r["runId"] != rid)
        assert cli_main(["runs", "compare", rid, other,
                         "--dir", d]) == 0
        out = capsys.readouterr().out
        assert "B - A" in out

    def test_dir_from_env(self, ckpt_env, monkeypatch, capsys):
        us, its = make_bucketed()
        train_als_bucketed(us, its, PARAMS)
        # --dir omitted: $PIO_CHECKPOINT_DIR (set by ckpt_env) wins
        assert cli_main(["runs", "list"]) == 0
        assert one_run(ckpt_env)["runId"] in capsys.readouterr().out

    def test_errors(self, ckpt_env, monkeypatch, capsys):
        assert cli_main(["runs", "list", "--dir",
                         str(ckpt_env / "missing")]) == 2
        os.makedirs(ckpt_env, exist_ok=True)
        assert cli_main(["runs", "show", "run-nope",
                         "--dir", str(ckpt_env)]) == 2
        assert cli_main(["runs"]) == 2
        capsys.readouterr()


class TestTrajectoriesAndStreaming:
    def test_leaderboard_rows_carry_trajectories(self, ckpt_env):
        us, its = make_bucketed(seed=8, n_u=30, n_i=20, nnz=250)
        grid = make_grid(GRID_BASE, [{"lambda": 0.1}, {"lambda": 0.5}])
        result = train_als_grid_bucketed(us, its, grid)
        rng = np.random.default_rng(5)
        tr = rng.integers(0, 30, 150)
        tc = rng.integers(0, 20, 150)
        held = {u: {int(rng.integers(0, 20))} for u in range(10)}
        board = grid_leaderboard(result, tr, tc, held, topk=5)
        for row in board["rows"]:
            traj = row["lossTrajectory"]
            assert [e["step"] for e in traj] == [2, 4]
            for e in traj:
                assert set(e) == {"step", "fit", "l2", "total"}
                assert np.isfinite(e["total"])

    def test_unchunked_grid_records_end_sample(self, monkeypatch):
        monkeypatch.delenv("PIO_CHECKPOINT_DIR", raising=False)
        monkeypatch.delenv("PIO_TRAIN_TELEMETRY", raising=False)
        us, its = make_bucketed(seed=8)
        grid = make_grid(GRID_BASE, [{"lambda": 0.1}, {"lambda": 0.5}])
        result = train_als_grid_bucketed(us, its, grid)
        # no chunk boundaries to sample at: one end-of-run entry
        assert [e["step"] for e in result.loss_history] == [4]

    def test_run_grid_streams_partial_leaderboards(self, monkeypatch):
        monkeypatch.delenv("PIO_CHECKPOINT_DIR", raising=False)
        us, its = make_bucketed(seed=12, n_u=40, n_i=30, nnz=350)
        grid = make_grid(GRID_BASE, [{"lambda": 0.05}, {"lambda": 0.2},
                                     {"lambda": 0.4}, {"lambda": 0.8}])
        rng = np.random.default_rng(3)
        tr = rng.integers(0, 40, 250)
        tc = rng.integers(0, 30, 250)
        held = {u: {int(rng.integers(0, 30))} for u in range(15)}
        per = wf_tuning.grid_bytes_per_config(40, 30, grid, us, its)
        partials = []
        board = wf_tuning.run_grid(
            us, its, grid, train_rows=tr, train_cols=tc, held=held,
            warmup=False, budget_bytes=2 * per,
            on_partial=partials.append)
        assert board["batches"] == [2, 2]
        # one partial after the first sub-batch; none after the last
        # (the final board supersedes it)
        assert len(partials) == 1
        partial = partials[0]
        assert partial["partial"] is True
        assert partial["batchesCompleted"] == 1
        by_cfg = {r["config"]: r for r in partial["rows"]}
        for cfg in (0, 1):  # trained in batch one
            assert "pending" not in by_cfg[cfg]
            assert by_cfg[cfg]["metric"] is not None
        for cfg in (2, 3):  # not yet trained: pending, NOT diverged
            assert by_cfg[cfg]["pending"] is True
            assert by_cfg[cfg]["diverged"] is False
        assert "partial" not in board
        assert {r["config"] for r in board["rows"]
                if r.get("pending")} == set()
