"""Vmapped multi-config training suite (ops/tuning.py +
workflow/tuning.py + the grid-aware checkpoint/warmup plumbing +
``pio eval --grid``).

Differential contracts (the ISSUE-16 acceptance gates):

- vmapped grid == k serial ``train_als_bucketed`` runs. fp32 at
  near-machine tolerance (vmapped batched matmuls tile their reductions
  differently than the unbatched serial program, so bit-exactness is
  not on offer — observed drift is ~2e-6 relative; the gate is 50x
  tighter than any hyperparameter-visible difference). bf16 at the
  PR-5 EPS_BF16 envelope. Rank sweeps: the leading r columns match the
  serial rank-r run and the padded columns are EXACT zeros.
- A diverging config (alpha overflow -> inf weights -> NaN in one
  iteration) is masked out while its neighbors finish equal to their
  serial runs; all-dead raises TrainingDivergedError.
- Preempt-then-resume mid-grid is byte-identical to an uninterrupted
  grid run, alive mask included (it rides the PR-13 manifest).
- The HBM scheduler's serial sub-batches reproduce the full-grid
  results exactly (lanes are independent under vmap).
"""

import json

import numpy as np
import pytest

from predictionio_tpu.data import storage
from predictionio_tpu.ops import tuning as ops_tuning
from predictionio_tpu.ops.als import (
    ALSParams,
    bucket_ratings_pair,
    train_als_bucketed,
    warmup_train_als_bucketed,
)
from predictionio_tpu.ops.tuning import (
    ConfigGrid,
    GridConfigError,
    grid_from_spec,
    grid_leaderboard,
    make_grid,
    train_als_grid_bucketed,
)
from predictionio_tpu.tools.cli import main
from predictionio_tpu.utils import metrics
from predictionio_tpu.workflow import checkpoint
from predictionio_tpu.workflow import tuning as wf_tuning
from predictionio_tpu.workflow.checkpoint import (
    TrainingDivergedError,
    TrainingPreempted,
)

pytestmark = pytest.mark.tuning

# vmapped-vs-serial fp32 gate: reduction-order drift only (see module
# docstring); 50x tighter than any metric-visible difference
RTOL, ATOL = 1e-4, 1e-5
EPS_BF16 = 2.0 ** -8

BASE = ALSParams(rank=4, num_iterations=4, seed=3)


def make_sides(seed=0, n_u=60, n_i=40, nnz=500):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n_u, nnz)
    cols = rng.integers(0, n_i, nnz)
    vals = (rng.random(nnz).astype(np.float32) + 0.5)
    return bucket_ratings_pair(rows, cols, vals, n_u, n_i)


def assert_grid_matches_serial(result, user_side, item_side,
                               tol=(RTOL, ATOL)):
    """Every live lane's true-rank factors match its own serial run."""
    rtol, atol = tol
    for i, cfg in enumerate(result.grid.configs):
        if not result.alive[i]:
            continue
        Xs, Ys = train_als_bucketed(user_side, item_side, cfg)
        Xg, Yg = result.factors_for(i)
        np.testing.assert_allclose(Xg, Xs, rtol=rtol, atol=atol)
        np.testing.assert_allclose(Yg, Ys, rtol=rtol, atol=atol)


class TestGridSpecValidation:
    """The loudness contract: every offending field named, with a
    reason, before any device work."""

    def test_unknown_field_named(self):
        with pytest.raises(GridConfigError) as e:
            make_grid(BASE, [{"lambda": 0.1}, {"lambada": 0.2}])
        msg = str(e.value)
        assert "configs[1].lambada: unknown ALSParams field" in msg
        assert "sweepable fields: rank, lambda, alpha" in msg

    def test_non_sweepable_field_named_with_reason(self):
        with pytest.raises(GridConfigError) as e:
            make_grid(BASE, [{"num_iterations": 9}, {"seed": 7}])
        msg = str(e.value)
        assert "configs[0].num_iterations: not sweepable" in msg
        assert "SAME compiled scan" in msg
        assert "configs[1].seed: not sweepable" in msg
        assert "set it in 'base' instead" in msg

    def test_all_problems_collected_not_just_first(self):
        with pytest.raises(GridConfigError) as e:
            make_grid(BASE, [{"bogus": 1, "precision": "bf16"},
                             {"rank": 0}])
        msg = str(e.value)
        assert "configs[0].bogus" in msg
        assert "configs[0].precision: not sweepable" in msg
        assert "configs[1].rank" in msg

    def test_aliases_lambda_and_camel_case(self):
        g = make_grid(BASE, [{"lambda": 0.5}, {"lambda_": 0.7},
                             {"alpha": 2.0}])
        assert [c.lambda_ for c in g.configs[:2]] == [0.5, 0.7]
        spec = {"base": {"rank": 4, "numIterations": 3, "seed": 1},
                "configs": [{"lambda": 0.5}]}
        g2 = grid_from_spec(spec)
        assert g2.base.num_iterations == 3

    def test_spec_unknown_section_and_base_fields(self):
        with pytest.raises(GridConfigError, match="unknown grid section"):
            grid_from_spec({"bsae": {}, "configs": [{}]})
        with pytest.raises(GridConfigError, match="base.frobnicate"):
            grid_from_spec({"base": {"frobnicate": 1},
                            "configs": [{}]})
        with pytest.raises(GridConfigError, match="non-empty list"):
            grid_from_spec({"base": {}, "configs": []})

    def test_constructor_requires_uniform_statics(self):
        import dataclasses
        cfgs = (BASE, dataclasses.replace(BASE, num_iterations=9))
        with pytest.raises(GridConfigError, match="num_iterations"):
            ConfigGrid(cfgs)

    def test_subset_and_describe(self):
        g = make_grid(BASE, [{"rank": 2}, {"rank": 4}, {"rank": 3}])
        assert g.max_rank == 4 and g.ranks == (2, 4, 3)
        sub = g.subset([2, 0])
        assert sub.ranks == (3, 2)
        assert g.describe()[0] == {"rank": 2, "lambda": BASE.lambda_,
                                   "alpha": BASE.alpha}


class TestGridDifferential:
    def test_fp32_lambda_alpha_sweep_matches_serial(self):
        user_side, item_side = make_sides()
        grid = make_grid(BASE, [{"lambda": 0.01}, {"lambda": 0.3},
                                {"alpha": 5.0},
                                {"lambda": 1.0, "alpha": 20.0}])
        result = train_als_grid_bucketed(user_side, item_side, grid)
        assert result.alive.all()
        assert_grid_matches_serial(result, user_side, item_side)

    def test_rank_sweep_pads_are_exact_zeros(self):
        user_side, item_side = make_sides(seed=1)
        grid = make_grid(BASE, [{"rank": 2}, {"rank": 4},
                                {"rank": 3, "lambda": 0.5}])
        result = train_als_grid_bucketed(user_side, item_side, grid)
        # leading r columns == the serial rank-r run (same RNG draw)
        assert_grid_matches_serial(result, user_side, item_side)
        for i, r in enumerate(grid.ranks):
            assert not result.user_factors[i, :, r:].any()
            assert not result.item_factors[i, :, r:].any()

    def test_bf16_grid_matches_serial(self):
        user_side, item_side = make_sides(seed=2)
        base = ALSParams(rank=4, num_iterations=3, seed=3,
                         precision="bf16")
        grid = make_grid(base, [{"lambda": 0.05}, {"lambda": 0.4}])
        result = train_als_grid_bucketed(user_side, item_side, grid)
        for i, cfg in enumerate(grid.configs):
            Xs, Ys = train_als_bucketed(user_side, item_side, cfg)
            Xg, Yg = result.factors_for(i)
            iters = base.num_iterations
            for got, want in ((Xg, Xs), (Yg, Ys)):
                err = np.linalg.norm(got - want) / np.linalg.norm(want)
                assert err < 4 * iters * EPS_BF16

    def test_single_config_grid_degenerates_cleanly(self):
        user_side, item_side = make_sides(seed=4)
        grid = make_grid(BASE, [{"lambda": 0.2}])
        result = train_als_grid_bucketed(user_side, item_side, grid)
        assert result.alive.tolist() == [True]
        assert_grid_matches_serial(result, user_side, item_side)


class TestDivergenceMasking:
    # alpha ~ 1e38 overflows the fp32 confidence weights to inf in one
    # half-step -> NaN factors: the canonical per-config divergence
    DEAD_ALPHA = 1e38

    def test_dead_lane_masked_neighbors_finish(self):
        user_side, item_side = make_sides(seed=5)
        grid = make_grid(BASE, [{"lambda": 0.1},
                                {"alpha": self.DEAD_ALPHA},
                                {"lambda": 0.7}])
        diverged0 = metrics.TRAIN_DIVERGED.value()
        result = train_als_grid_bucketed(user_side, item_side, grid)
        assert result.alive.tolist() == [True, False, True]
        assert metrics.TRAIN_DIVERGED.value() == diverged0 + 1
        # dead lane is zeroed (and STAYS zero: inf*0 regenerates NaN,
        # so the mask is re-applied every chunk), finite everywhere
        assert not result.user_factors[1].any()
        assert not result.item_factors[1].any()
        assert np.isfinite(result.user_factors).all()
        assert_grid_matches_serial(result, user_side, item_side)

    def test_all_dead_raises(self):
        user_side, item_side = make_sides(seed=6)
        grid = make_grid(BASE, [{"alpha": self.DEAD_ALPHA},
                                {"alpha": 2e38}])
        with pytest.raises(TrainingDivergedError):
            train_als_grid_bucketed(user_side, item_side, grid)

    def test_leaderboard_sinks_diverged(self):
        user_side, item_side = make_sides(seed=7, n_u=30, n_i=20,
                                          nnz=300)
        grid = make_grid(BASE, [{"lambda": 0.1},
                                {"alpha": self.DEAD_ALPHA}])
        result = train_als_grid_bucketed(user_side, item_side, grid)
        rng = np.random.default_rng(0)
        tr = rng.integers(0, 30, 200)
        tc = rng.integers(0, 20, 200)
        held = {u: {int(rng.integers(0, 20))} for u in range(10)}
        board = grid_leaderboard(result, tr, tc, held, topk=5)
        assert board["rows"][-1]["config"] == 1
        assert board["rows"][-1]["diverged"] is True
        assert board["rows"][-1]["metric"] is None
        assert board["winner"]["config"] == 0
        assert isinstance(board["winner"]["metric"], float)


class TestGridCheckpointResume:
    @pytest.fixture
    def ckpt_env(self, tmp_path, monkeypatch):
        d = tmp_path / "grid_ckpts"
        monkeypatch.setenv("PIO_CHECKPOINT_DIR", str(d))
        monkeypatch.setenv("PIO_CHECKPOINT_EVERY", "2")
        checkpoint.clear_stop()
        yield d
        checkpoint.clear_stop()

    def test_resume_mid_grid_equals_uninterrupted(self, ckpt_env,
                                                  monkeypatch):
        user_side, item_side = make_sides(seed=8)
        grid = make_grid(ALSParams(rank=4, num_iterations=6, seed=3),
                         [{"lambda": 0.05}, {"lambda": 0.5},
                          {"rank": 2}])
        monkeypatch.delenv("PIO_CHECKPOINT_DIR")
        ref = train_als_grid_bucketed(user_side, item_side, grid)
        monkeypatch.setenv("PIO_CHECKPOINT_DIR", str(ckpt_env))
        checkpoint.request_stop()
        with pytest.raises(TrainingPreempted):
            train_als_grid_bucketed(user_side, item_side, grid)
        checkpoint.clear_stop()
        monkeypatch.setenv("PIO_RESUME", "1")
        got = train_als_grid_bucketed(user_side, item_side, grid)
        assert np.array_equal(got.user_factors, ref.user_factors)
        assert np.array_equal(got.item_factors, ref.item_factors)
        assert got.alive.tolist() == ref.alive.tolist()

    def test_alive_mask_rides_the_manifest(self, ckpt_env,
                                           monkeypatch):
        """A config that diverges BEFORE the preemption stays masked
        after resume — the mask is state, so it lives in the manifest
        (``extra.aliveConfigs``), not just in process memory."""
        user_side, item_side = make_sides(seed=9)
        grid = make_grid(ALSParams(rank=4, num_iterations=6, seed=3),
                         [{"lambda": 0.1}, {"alpha": 1e38}])
        monkeypatch.delenv("PIO_CHECKPOINT_DIR")
        ref = train_als_grid_bucketed(user_side, item_side, grid)
        assert ref.alive.tolist() == [True, False]
        monkeypatch.setenv("PIO_CHECKPOINT_DIR", str(ckpt_env))
        checkpoint.request_stop()
        with pytest.raises(TrainingPreempted):
            train_als_grid_bucketed(user_side, item_side, grid)
        checkpoint.clear_stop()
        manifest = sorted(ckpt_env.glob("*.json"))[-1]
        extra = json.loads(manifest.read_text())["extra"]
        assert extra["aliveConfigs"] == [True, False]
        assert extra["gridK"] == 2
        diverged0 = metrics.TRAIN_DIVERGED.value()
        monkeypatch.setenv("PIO_RESUME", "1")
        got = train_als_grid_bucketed(user_side, item_side, grid)
        assert got.alive.tolist() == [True, False]
        # the dead lane was restored dead, not re-detected (no second
        # divergence count) and not resurrected
        assert metrics.TRAIN_DIVERGED.value() == diverged0
        assert np.array_equal(got.user_factors, ref.user_factors)


class TestGridWarmup:
    def test_warmup_gives_zero_steady_state_compiles(self):
        metrics.install_jit_compile_listener()
        user_side, item_side = make_sides(seed=10)
        user_side = user_side.to_device()
        item_side = item_side.to_device()
        grid = make_grid(BASE, [{"lambda": 0.1}, {"lambda": 0.9}])
        assert warmup_train_als_bucketed(user_side, item_side, grid)
        # first dispatch absorbs the finite-guard jit; every train
        # after it must hit the AOT-cached grid program cold-free
        train_als_grid_bucketed(user_side, item_side, grid)
        compiles0 = metrics.JIT_COMPILES.value()
        train_als_grid_bucketed(user_side, item_side, grid)
        assert metrics.JIT_COMPILES.value() == compiles0


class TestHbmScheduler:
    def test_budget_env_override_and_reserved_reports(self, monkeypatch):
        monkeypatch.setenv("PIO_TUNING_HBM_BUDGET", "1000000")
        assert wf_tuning.hbm_budget_bytes() == 1_000_000
        reports = [{"totalBytes": 300_000},
                   {"memory": {"totalBytes": 200_000}}]
        assert wf_tuning.hbm_budget_bytes(reports) == 500_000

    def test_plan_splits_to_budget(self):
        user_side, item_side = make_sides(seed=11)
        grid = make_grid(BASE, [{"lambda": l}
                                for l in (0.1, 0.2, 0.3, 0.4)])
        per = wf_tuning.grid_bytes_per_config(60, 40, grid, user_side,
                                              item_side)
        assert per > 0
        assert wf_tuning.plan_grid_batches(
            grid, 60, 40, budget_bytes=None) in ([[0, 1, 2, 3]],)
        assert wf_tuning.plan_grid_batches(
            grid, 60, 40, user_side, item_side,
            budget_bytes=2 * per) == [[0, 1], [2, 3]]
        # budget below one config still trains: 1-config sub-batches
        assert wf_tuning.plan_grid_batches(
            grid, 60, 40, user_side, item_side,
            budget_bytes=1) == [[0], [1], [2], [3]]

    def test_sub_batched_run_equals_full_grid(self):
        user_side, item_side = make_sides(seed=12, n_u=40, n_i=30,
                                          nnz=350)
        grid = make_grid(BASE, [{"lambda": 0.05}, {"lambda": 0.2},
                                {"rank": 2}, {"lambda": 0.8}])
        rng = np.random.default_rng(3)
        tr = rng.integers(0, 40, 250)
        tc = rng.integers(0, 30, 250)
        held = {u: {int(rng.integers(0, 30))} for u in range(15)}
        per = wf_tuning.grid_bytes_per_config(40, 30, grid, user_side,
                                              item_side)
        full = wf_tuning.run_grid(
            user_side, item_side, grid, train_rows=tr, train_cols=tc,
            held=held, warmup=False)
        split = wf_tuning.run_grid(
            user_side, item_side, grid, train_rows=tr, train_cols=tc,
            held=held, warmup=False, budget_bytes=2 * per)
        assert full["batches"] == [4] and split["batches"] == [2, 2]
        for a, b in zip(full["rows"], split["rows"]):
            assert a == b
        assert full["winner"]["config"] == split["winner"]["config"]

    def test_fully_diverged_sub_batch_does_not_kill_sweep(self):
        """Found by driving the CLI: a 1-config sub-batch holding ONLY
        a diverging config used to surface the all-dead
        TrainingDivergedError and abort the whole sweep — it must mark
        those configs dead and let the other batches finish."""
        user_side, item_side = make_sides(seed=13, n_u=30, n_i=20,
                                          nnz=250)
        grid = make_grid(BASE, [{"lambda": 0.1}, {"alpha": 1e38},
                                {"lambda": 0.5}])
        rng = np.random.default_rng(5)
        tr = rng.integers(0, 30, 180)
        tc = rng.integers(0, 20, 180)
        held = {u: {int(rng.integers(0, 20))} for u in range(10)}
        per = wf_tuning.grid_bytes_per_config(30, 20, grid, user_side,
                                              item_side)
        board = wf_tuning.run_grid(
            user_side, item_side, grid, train_rows=tr, train_cols=tc,
            held=held, warmup=False, budget_bytes=per)  # 1-config batches
        assert board["batches"] == [1, 1, 1]
        by_cfg = {r["config"]: r for r in board["rows"]}
        assert by_cfg[1]["diverged"] is True
        assert by_cfg[0]["diverged"] is False
        assert by_cfg[2]["diverged"] is False
        assert board["winner"]["config"] in (0, 2)


class TestCliGridEval:
    def seed_app(self, app_name="tuneapp", n_users=16, n_items=8):
        import datetime as dt

        from predictionio_tpu.data.event import Event
        from predictionio_tpu.data.storage.base import App

        aid = storage.get_metadata_apps().insert(App(0, app_name))
        le = storage.get_levents()
        le.init(aid)
        rng = np.random.default_rng(4)
        t0 = dt.datetime(2021, 1, 1, tzinfo=dt.timezone.utc)
        le.insert_batch([
            Event(event="rate", entity_type="user", entity_id=f"u{u}",
                  target_entity_type="item",
                  target_entity_id=f"i{rng.integers(0, n_items)}",
                  properties={"rating": float(rng.integers(1, 6))},
                  event_time=t0 + dt.timedelta(minutes=j))
            for u in range(n_users) for j in range(6)], aid)
        return aid

    def grid_file(self, tmp_path, **spec_over):
        spec = {"base": {"rank": 4, "numIterations": 2, "seed": 1},
                "configs": [{"lambda": 0.05}, {"lambda": 0.5}],
                "data": {"appName": "tuneapp"}}
        spec.update(spec_over)
        path = tmp_path / "grid.json"
        path.write_text(json.dumps(spec))
        return str(path)

    def test_grid_eval_end_to_end(self, mem_storage, tmp_path, capsys):
        self.seed_app()
        out = tmp_path / "board.json"
        assert main(["eval", "--grid", self.grid_file(tmp_path),
                     "--grid-out", str(out), "--topk", "5"]) == 0
        printed = capsys.readouterr().out
        assert "winner: config" in printed
        board = json.loads(out.read_text())
        assert board["metricName"] == "precision@5"
        assert len(board["rows"]) == 2
        assert board["gridK"] == 2
        winner = board["winner"]
        assert winner["diverged"] is False
        # the winner is redeployable as-is: full EngineParams pinned
        ep = winner["engineParams"]
        algo = ep["algorithms"][0]
        assert algo["name"] == "als"
        assert algo["params"]["rank"] == 4
        assert algo["params"]["lambda_"] == winner["params"]["lambda"]
        assert ep["datasource"]["params"]["app_name"] == "tuneapp"

    def test_rejects_unknown_and_non_sweepable_fields(self, mem_storage,
                                                      tmp_path, capsys):
        self.seed_app()
        path = self.grid_file(
            tmp_path,
            configs=[{"lambda": 0.1, "typo_field": 1},
                     {"seed": 9}])
        assert main(["eval", "--grid", path]) == 1
        err = capsys.readouterr().err
        assert "configs[0].typo_field: unknown ALSParams field" in err
        assert "configs[1].seed: not sweepable" in err

    def test_rejects_unknown_section_and_missing_app(self, mem_storage,
                                                     tmp_path, capsys):
        path = self.grid_file(tmp_path, gird="oops")
        assert main(["eval", "--grid", path]) == 1
        assert "unknown section 'gird'" in capsys.readouterr().err
        path2 = self.grid_file(tmp_path, data={})
        assert main(["eval", "--grid", path2]) == 1
        assert "missing data.appName" in capsys.readouterr().err

    def test_rejects_unreadable_file_and_missing_events(self,
                                                        mem_storage,
                                                        tmp_path,
                                                        capsys):
        assert main(["eval", "--grid",
                     str(tmp_path / "nope.json")]) == 1
        assert "cannot read grid file" in capsys.readouterr().err
        path = self.grid_file(tmp_path,
                              data={"appName": "ghostapp"})
        assert main(["eval", "--grid", path]) == 1
        err = capsys.readouterr().err
        assert "[ERROR]" in err

    def test_eval_without_grid_or_evaluation_errors(self, mem_storage,
                                                    capsys):
        assert main(["eval"]) == 1
        assert "[ERROR]" in capsys.readouterr().err
