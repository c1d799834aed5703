"""Vmapped multi-config ALS training: one device program trains the
whole hyperparameter grid.

The reference's tuning story (``Evaluation`` + ``EngineParamsGenerator``
driving batched ``pio eval``) is embarrassingly serial: k configs = k
full trains = k jit compiles = k passes over the same ratings. Here a
:class:`ConfigGrid` of k :class:`~predictionio_tpu.ops.als.ALSParams`
variants (lambda, alpha, and — via rank padding — rank) is stacked on a
leading axis and the bucketed normal-equation half-steps run under
``vmap`` (DrJAX's map-over-leading-axis idiom), so:

- the bucketed ratings tables are device-resident ONCE (vmap broadcasts
  them — HBM cost is k factor sets, never k table copies);
- ``lambda``/``alpha`` become traced ``[k]`` vectors instead of static
  jit args, so one compiled program serves any values at fixed k;
- rank sweeps ride zero-padded factor columns: each config initializes
  at its TRUE rank (identical RNG draw to its serial run) and pads to
  the grid max; a unit ridge on pad diagonals makes the padded
  coordinates solve to EXACT zeros, so the leading r columns match the
  serial rank-r run (differential-gated in tests/test_tuning_grid.py);
- divergence is PER-CONFIG: a non-finite config is masked out (factors
  zeroed — zero is a fixed point of the ALS half-step, so the lane
  freezes) while its neighbors keep training;
- the PR-13 crash-safe lifecycle extends with the config axis
  (``workflow.checkpoint.run_chunked_grid`` carries the alive mask in
  the manifest), and the grid-aware ``warmup_train_als_bucketed`` keeps
  the zero-steady-state-compile contract.

Grid-spec validation is LOUD and per-field (:func:`grid_from_spec`):
unknown ``ALSParams`` fields and non-sweepable statics (solver knobs,
``checkpoint_every``, ...) are each named with the reason, instead of
surfacing as a trace-time failure half a training later.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, List, Mapping, Optional, Sequence, Set, Tuple

import numpy as np

from predictionio_tpu.ops import als as _als
from predictionio_tpu.ops.als import ALSParams, BucketedRatings

logger = logging.getLogger("predictionio_tpu.ops.tuning")


class GridConfigError(ValueError):
    """A grid spec referenced unknown or non-sweepable fields; the
    message carries ONE line per offending field."""


#: The ALSParams fields a grid may vary per config. Everything else is
#: either a static argument of the compiled program (one trace for the
#: whole grid) or an execution knob — set those in the spec's "base".
SWEEPABLE_FIELDS = ("rank", "lambda_", "alpha")

_NOT_SWEEPABLE_WHY = {
    "num_iterations": "every config advances inside the SAME compiled "
                      "scan, so the trip count is shared",
    "implicit_prefs": "the implicit/explicit switch selects a different "
                      "traced program (static jit arg)",
    "seed": "the per-config init already varies by rank; a per-config "
            "seed would break the grid==serial differential contract",
    "bucket_slot_budget": "static shape knob of the shared program",
    "precision": "the factor dtype is the stacked array's dtype — one "
                 "per grid",
    "solve_refine": "static jit arg of the shared program",
    "checkpoint_every": "execution knob (excluded from checkpoint "
                        "fingerprints); set via base or PIO_CHECKPOINT_EVERY",
}

# statics the ConfigGrid constructor requires to be uniform across
# configs — exactly the non-sweepable ALSParams fields
_SHARED_FIELDS = tuple(_NOT_SWEEPABLE_WHY)


def _als_field_names() -> Set[str]:
    return {f.name for f in dataclasses.fields(ALSParams)}


def _canonical_field(key: str, fields: Set[str]) -> Optional[str]:
    """Resolve a spec key to an ALSParams field name, accepting the
    camelCase and keyword-collision aliases ``params_from_dict`` does
    (``lambda`` -> ``lambda_``, ``numIterations`` -> ``num_iterations``)."""
    if key in fields:
        return key
    snake = "".join("_" + c.lower() if c.isupper() else c for c in key)
    for alt in (snake, key + "_", snake + "_"):
        if alt in fields:
            return alt
    return None


def _coerce(canon: str, value):
    """Type-coerce a sweepable field value; raises ValueError/TypeError
    on garbage (caller turns that into a per-field problem line)."""
    if canon == "rank":
        r = int(value)
        if r < 1:
            raise ValueError(f"rank must be >= 1, got {r}")
        return r
    return float(value)


@dataclasses.dataclass(frozen=True)
class ConfigGrid:
    """k resolved ALSParams variants destined for one vmapped training
    program. Construction validates the invariants the compiled program
    depends on: non-empty, and every non-sweepable field uniform across
    configs (they are static arguments of the SHARED trace)."""

    configs: Tuple[ALSParams, ...]

    def __post_init__(self):
        if not self.configs:
            raise GridConfigError("a ConfigGrid needs at least 1 config")
        base = self.configs[0]
        problems = []
        for i, c in enumerate(self.configs):
            if int(c.rank) < 1:
                problems.append(f"configs[{i}]: rank must be >= 1")
            for f in _SHARED_FIELDS:
                if getattr(c, f) != getattr(base, f):
                    problems.append(
                        f"configs[{i}].{f}: differs from configs[0] — "
                        f"{_NOT_SWEEPABLE_WHY[f]}")
        if problems:
            raise GridConfigError(
                "invalid config grid:\n  " + "\n  ".join(problems))

    @property
    def k(self) -> int:
        return len(self.configs)

    @property
    def base(self) -> ALSParams:
        return self.configs[0]

    @property
    def max_rank(self) -> int:
        return max(int(c.rank) for c in self.configs)

    @property
    def ranks(self) -> Tuple[int, ...]:
        return tuple(int(c.rank) for c in self.configs)

    def subset(self, indices: Sequence[int]) -> "ConfigGrid":
        """The sub-grid at ``indices`` — lanes are independent under
        vmap and each config's init depends only on its own params, so
        training a subset reproduces exactly the same factors those
        configs get in the full grid (how the HBM scheduler's serial
        sub-batches stay differential-equivalent)."""
        return ConfigGrid(tuple(self.configs[int(i)] for i in indices))

    def describe(self) -> List[Dict]:
        return [{"rank": int(c.rank), "lambda": float(c.lambda_),
                 "alpha": float(c.alpha)} for c in self.configs]


def make_grid(base: ALSParams, overrides: Sequence[Mapping]) -> ConfigGrid:
    """Build a ConfigGrid from a base ALSParams plus one override
    mapping per config. Validation is collected-then-raised: EVERY
    offending field across every config is named in one
    :class:`GridConfigError` (the ``pio eval --grid`` loudness
    contract), not just the first."""
    fields = _als_field_names()
    problems: List[str] = []
    configs: List[ALSParams] = []
    valid = ", ".join(("lambda" if f == "lambda_" else f)
                      for f in SWEEPABLE_FIELDS)
    for i, ov in enumerate(overrides):
        if not isinstance(ov, Mapping):
            problems.append(
                f"configs[{i}]: expected an object of field overrides, "
                f"got {type(ov).__name__}")
            continue
        kw = {}
        for key, value in ov.items():
            canon = _canonical_field(str(key), fields)
            if canon is None:
                problems.append(
                    f"configs[{i}].{key}: unknown ALSParams field "
                    f"(sweepable fields: {valid})")
            elif canon not in SWEEPABLE_FIELDS:
                why = _NOT_SWEEPABLE_WHY.get(
                    canon, "static argument of the shared program")
                problems.append(
                    f"configs[{i}].{key}: not sweepable — {why}; set it "
                    f"in 'base' instead")
            else:
                try:
                    kw[canon] = _coerce(canon, value)
                except (TypeError, ValueError) as e:
                    problems.append(f"configs[{i}].{key}: {e}")
        configs.append(dataclasses.replace(base, **kw))
    if problems:
        raise GridConfigError(
            "grid rejected:\n  " + "\n  ".join(problems))
    if not configs:
        raise GridConfigError("grid rejected: 'configs' is empty — "
                              "give at least one override object")
    return ConfigGrid(tuple(configs))


def grid_from_spec(spec: Mapping) -> ConfigGrid:
    """Parse ``{"base": {...ALSParams...}, "configs": [{...}, ...]}``
    (the ``pio eval --grid`` file shape) into a ConfigGrid with loud
    per-field errors for both sections."""
    if not isinstance(spec, Mapping):
        raise GridConfigError(
            f"grid spec must be an object, got {type(spec).__name__}")
    unknown = sorted(set(spec) - {"base", "configs"})
    if unknown:
        raise GridConfigError(
            "grid rejected:\n  " + "\n  ".join(
                f"{k}: unknown grid section (expected: base, configs)"
                for k in unknown))
    fields = _als_field_names()
    problems: List[str] = []
    base_kw = {}
    base_raw = spec.get("base", {})
    if not isinstance(base_raw, Mapping):
        raise GridConfigError(
            f"base: expected an object of ALSParams fields, got "
            f"{type(base_raw).__name__}")
    for key, value in base_raw.items():
        canon = _canonical_field(str(key), fields)
        if canon is None:
            problems.append(
                f"base.{key}: unknown ALSParams field (valid: "
                + ", ".join(sorted(fields)) + ")")
        else:
            base_kw[canon] = value
    if problems:
        raise GridConfigError("grid rejected:\n  " + "\n  ".join(problems))
    try:
        base = ALSParams(**base_kw)
    except (TypeError, ValueError) as e:
        raise GridConfigError(f"grid rejected:\n  base: {e}") from e
    overrides = spec.get("configs")
    if not isinstance(overrides, (list, tuple)) or not overrides:
        raise GridConfigError(
            "grid rejected:\n  configs: expected a non-empty list of "
            "override objects")
    return make_grid(base, overrides)


# ---------------------------------------------------------------------------
# training


@dataclasses.dataclass
class GridTrainResult:
    """Host-side result of one vmapped grid training: fp32 factors
    stacked ``[k, N, R_max]`` / ``[k, M, R_max]`` (rank-padded columns
    are exact zeros), the grid, and the per-config ``alive`` mask
    (False = diverged and masked out mid-run; its factors are zeros)."""

    user_factors: np.ndarray
    item_factors: np.ndarray
    grid: ConfigGrid
    alive: np.ndarray
    #: per-chunk objective samples ({"step", "fit", "l2", "total"} with
    #: [k]-vectors holding None for dead configs) when training-plane
    #: telemetry was on; None under PIO_TRAIN_TELEMETRY=0
    loss_history: Optional[List[dict]] = None

    def factors_for(self, i: int) -> Tuple[np.ndarray, np.ndarray]:
        """Config ``i``'s factors at its TRUE rank — what the serial
        ``train_als_bucketed`` run of that config returns."""
        r = int(self.grid.configs[i].rank)
        return (self.user_factors[i][:, :r],
                self.item_factors[i][:, :r])


def init_grid_factors(n_users: int, n_items: int, grid: ConfigGrid,
                      dtype, precision: str):
    """Stacked factor init ``[k, N, R_max]``: each config draws at its
    TRUE rank with the shared seed (bit-identical to its serial run's
    init, including the 1/sqrt(rank) scale) and zero-pads the column
    tail. The pad zeros + the unit pad ridge are what make the grid ==
    serial differential exact."""
    import jax.numpy as jnp

    r_max = grid.max_rank
    xs, ys = [], []
    for c in grid.configs:
        X, Y = _als.init_policy_factors(n_users, n_items, int(c.rank),
                                        c.seed, dtype, precision)
        pad = r_max - int(c.rank)
        if pad:
            X = jnp.pad(X, ((0, 0), (0, pad)))
            Y = jnp.pad(Y, ((0, 0), (0, pad)))
        xs.append(X)
        ys.append(Y)
    return jnp.stack(xs), jnp.stack(ys)


def grid_checkpoint_layout(user_side: BucketedRatings,
                           item_side: BucketedRatings, grid: ConfigGrid):
    """Layout half of the grid checkpoint fingerprint: the bucketed
    layout plus every config's sweep coordinates — a manifest written
    by a different grid must NOT resume this one."""
    return ("grid",
            _als.checkpoint_layout_bucketed(user_side, item_side),
            tuple((int(c.rank), float(c.lambda_), float(c.alpha))
                  for c in grid.configs))


def train_als_grid_bucketed(user_side: BucketedRatings,
                            item_side: BucketedRatings,
                            grid: ConfigGrid,
                            dtype=None) -> GridTrainResult:
    """Train all k configs in ONE device program against the shared
    bucketed tables (see the module docstring for the contract). Same
    lifecycle as :func:`~predictionio_tpu.ops.als.train_als_bucketed`:
    AOT warm-up via the grid-aware ``warmup_train_als_bucketed``,
    crash-safe chunking when ``PIO_CHECKPOINT_DIR`` is set (with the
    per-config divergence mask carried in the manifest), host fp32
    factors out."""
    import jax.numpy as jnp

    assert user_side.n_rows >= item_side.n_cols
    assert item_side.n_rows >= user_side.n_cols
    base = grid.base
    precision = _als._als_precision_mode(base)  # resolved per call
    X, Y = init_grid_factors(user_side.n_rows, item_side.n_rows, grid,
                             dtype, precision)
    (_, _, lam, alpha, ridge, u_t, i_t), kw = _als._grid_call_args(
        user_side, item_side, grid.configs, precision)
    ckpt = _als._maybe_checkpointer(
        grid_checkpoint_layout(user_side, item_side, grid), base,
        kw["solver"], precision, dtype)
    fdt = X.dtype

    def run_iters(Xc, Yc, n):
        return _als._als_iterations_grid(
            Xc, Yc, lam, alpha, ridge, u_t, i_t,
            **dict(kw, num_iterations=int(n)))

    objective = history = None
    if _als._train_telemetry_enabled():
        implicit = bool(base.implicit_prefs)
        history = []

        def objective(Xc, Yc):
            return _als._objective_pack_grid(Xc, Yc, lam, alpha, u_t,
                                             implicit=implicit)

    # both branches go through the checkpoint module's grid loop — it
    # owns the per-config finite guard + masking either way (ckpt=None
    # is the single-dispatch fast path)
    from predictionio_tpu.workflow import checkpoint as _checkpoint

    X, Y, alive = _checkpoint.run_chunked_grid(
        run_iters, X, Y, int(base.num_iterations), ckpt,
        to_host=lambda a: np.asarray(a, dtype=np.float32),
        from_host=lambda a: jnp.asarray(a, dtype=fdt),
        objective=objective, history=history)
    return GridTrainResult(
        user_factors=np.asarray(X, dtype=np.float32),
        item_factors=np.asarray(Y, dtype=np.float32),
        grid=grid, alive=np.asarray(alive, dtype=bool),
        loss_history=history)


# ---------------------------------------------------------------------------
# on-device grid evaluation (rides the batchpredict idiom: one einsum +
# top_k per user chunk, all k configs at once)

_grid_topk_jit = None


def _get_grid_topk_jit():
    global _grid_topk_jit
    if _grid_topk_jit is None:
        import jax
        import jax.numpy as jnp

        def impl(Xu, Y, seen, *, topk):
            # Xu [k, B, R], Y [k, M, R], seen [B, M] (train interactions,
            # config-independent — the grid shares one train set)
            scores = jnp.einsum("kbr,kmr->kbm", Xu, Y,
                                precision=jax.lax.Precision.HIGHEST)
            scores = jnp.where(seen[None, :, :], -jnp.inf, scores)
            _, idx = jax.lax.top_k(scores, topk)
            return idx                             # [k, B, topk]

        _grid_topk_jit = jax.jit(impl, static_argnames=("topk",))
    return _grid_topk_jit


def grid_topk(result: GridTrainResult, user_ids: Sequence[int],
              train_rows: np.ndarray, train_cols: np.ndarray,
              topk: int, chunk: int = 512) -> np.ndarray:
    """Top-``topk`` unseen items for ``user_ids`` under EVERY config at
    once: ``[k, U, topk]`` item indices. Users are processed in fixed
    chunks (padded, so at most two compiled shapes) to bound the
    ``[k, B, M]`` score block."""
    import jax.numpy as jnp

    k, _, _ = result.user_factors.shape
    n_items = result.item_factors.shape[1]
    users = np.asarray(list(user_ids), dtype=np.int64)
    X = jnp.asarray(result.user_factors)
    Y = jnp.asarray(result.item_factors)
    jitted = _get_grid_topk_jit()

    # host seen-lookup: user -> train item rows (config-independent)
    order = np.argsort(train_rows, kind="stable")
    srows, scols = np.asarray(train_rows)[order], \
        np.asarray(train_cols)[order]
    bounds = np.searchsorted(srows, [users, users + 1])

    out = np.empty((k, len(users), int(topk)), dtype=np.int64)
    chunk = max(1, int(chunk))
    for start in range(0, len(users), chunk):
        u = users[start:start + chunk]
        b = len(u)
        pad = chunk - b
        seen = np.zeros((chunk, n_items), dtype=bool)
        for j in range(b):
            lo, hi = bounds[0][start + j], bounds[1][start + j]
            seen[j, scols[lo:hi]] = True
        Xu = result.user_factors[:, u, :]
        if pad:
            Xu = np.pad(Xu, ((0, 0), (0, pad), (0, 0)))
        idx = jitted(jnp.asarray(Xu), Y, jnp.asarray(seen),
                     topk=int(topk))
        out[:, start:start + b, :] = np.asarray(idx)[:, :b, :]
    return out


def grid_leaderboard(result: GridTrainResult, train_rows: np.ndarray,
                     train_cols: np.ndarray, held: Mapping[int, set],
                     topk: int = 10) -> Dict:
    """Score every config on the held-out interactions (Precision@k +
    NDCG@k over the on-device top-k) and rank them. Returns the
    leaderboard artifact body: ``rows`` best-first (diverged configs
    sink to the bottom with ``metric: None``) and ``winner``."""
    from predictionio_tpu.data import sliding

    users = sorted(int(u) for u in held if held[u])
    rows: List[Dict] = []
    if users:
        idx = grid_topk(result, users, train_rows, train_cols, topk)
    for i in range(result.grid.k):
        entry = {"config": i,
                 "params": result.grid.describe()[i],
                 "diverged": not bool(result.alive[i]),
                 # per-config objective curve (why the winner won):
                 # one point per telemetry sample this config survived
                 "lossTrajectory": [
                     {"step": e["step"], "fit": e["fit"][i],
                      "l2": e["l2"][i], "total": e["total"][i]}
                     for e in (result.loss_history or [])
                     if i < len(e["total"])
                     and e["total"][i] is not None]}
        if entry["diverged"] or not users:
            entry["metric"] = None
            entry["precisionAtK"] = None
            entry["ndcgAtK"] = None
        else:
            prec, ndcg = [], []
            for j, u in enumerate(users):
                rel = held[u]
                ranked = [int(t) for t in idx[i, j]]
                hits = sum(1 for t in ranked if t in rel)
                prec.append(hits / float(topk))
                ndcg.append(sliding.ndcg_at_k(ranked, rel, topk))
            entry["precisionAtK"] = float(np.mean(prec))
            entry["ndcgAtK"] = float(np.mean(ndcg))
            entry["metric"] = entry["precisionAtK"]
        rows.append(entry)
    rows.sort(key=lambda r: (r["metric"] is None, -(r["metric"] or 0.0),
                             r["config"]))
    winner = next((r for r in rows if r["metric"] is not None), None)
    return {"metricName": f"precision@{int(topk)}", "k": int(topk),
            "nTestUsers": len(users), "rows": rows,
            "winner": dict(winner) if winner else None}
