"""Alternating least squares on TPU — the north-star kernel.

Capability parity with MLlib ``ALS.trainImplicit``/``ALS.train`` as invoked
by the recommendation template
(``examples/scala-parallel-recommendation/custom-query/src/main/scala/
ALSAlgorithm.scala:64-71``: rank, iterations, lambda, alpha=1.0, seed).

The design follows the ALX layout (PAPERS.md: "ALX: Large Scale Matrix
Factorization on TPUs") rather than MLlib's block-partitioned shuffle:

- Ratings are grouped by row length into a few dense ``[B, L]``
  index/weight tables (:class:`BucketedRatings`: each row pads to its
  own length class, so power-law raggedness costs < 2x and no pair is
  dropped). Static shapes keep XLA on the MXU.
- One alternating half-step solves ALL rows in a single program, one
  batched solve a bucket: gather the fixed side's factors
  ``[B, L, R]``, form normal equations with two einsums (never
  materializing ``[B, L, R, R]``), add the shared Gram matrix for the
  implicit term, and batch-solve via Cholesky
  (``jax.scipy.linalg.cho_solve``).
- Multi-chip: rows are sharded over the mesh's data axis (each device
  solves its slice); the fixed factor matrix is replicated and the shared
  Gram matrix is computed once — XLA inserts the collectives when the
  caller runs this under ``shard_map``/``jit`` with shardings (see
  ``predictionio_tpu.parallel.als_sharding``).

Implicit-feedback objective (Hu-Koren-Volinsky, as in MLlib): confidence
``c = 1 + alpha * r``, preference ``p = 1`` for observed pairs; per-row
normal equations ``(YtY + Yt (C - I) Y + lambda*I) x = Yt C p``.
Explicit: ``(Yt_u Y_u + lambda * n_u * I) x = Yt_u r_u`` (MLlib's ALS-WR
lambda scaling by per-row rating count).
"""

from __future__ import annotations

import dataclasses
import time as _time
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.core.base import Params


@dataclasses.dataclass(frozen=True)
class ALSParams(Params):
    """Mirror of ALSAlgorithmParams (custom-query ALSAlgorithm.scala:13-14)
    plus the implicit/explicit switch MLlib exposes as two entry points."""

    rank: int = 10
    num_iterations: int = 10
    lambda_: float = 0.01
    alpha: float = 1.0
    implicit_prefs: bool = True
    seed: Optional[int] = None
    # max rows*L padded slots per solve dispatch: a bucket whose table
    # exceeds this runs as sequential row blocks (lax.map), bounding
    # the [rows, L, R] factor gather that dominates HBM at scale (10M+
    # ratings). None = solve each bucket in one dispatch.
    bucket_slot_budget: Optional[int] = None
    # precision policy for the training loop: "fp32" (default —
    # byte-identical to the historical all-fp32 path) or "bf16" (factor
    # matrices stored and gathered as bfloat16, halving the dominant
    # [B, L, R] HBM stream; the normal-equation einsums and shared Gram
    # matrix accumulate in fp32 via preferred_element_type and the
    # batched Cholesky solve stays fp32 — the ALX §4 storage/compute
    # split). PIO_ALS_PRECISION overrides; resolved once per train_als*
    # call (never at trace time) and unknown values raise.
    precision: str = "fp32"
    # one fp32 iterative-refinement pass on each normal-equation solve
    # (x += solve(A, b - A x)): tightens the solve residual when the
    # assembled A/b carry bf16 rounding, at ~2x solve cost. Off by
    # default; meaningful mainly under precision="bf16".
    solve_refine: bool = False
    # crash-safe training (workflow/checkpoint.py): run the iteration
    # scan in chunks of this many iterations per device program so the
    # host can snapshot an atomic checkpoint, honor SIGTERM/SIGINT and
    # guard divergence between chunks. None/0 = off (today's
    # single-scan path, untouched). Chunked training is byte-identical
    # to unchunked — the per-iteration program and every reduction
    # order are unchanged (differential-gated) — so this is an
    # execution knob, excluded from the checkpoint fingerprint.
    # PIO_CHECKPOINT_EVERY overrides; checkpoints only land when
    # PIO_CHECKPOINT_DIR is also set (pio train --checkpoint-dir).
    checkpoint_every: Optional[int] = None


# rows pad to a multiple of this in every solve-table builder
# (the bucketed grouper and the fold-in padder, whose EFFECTIVE
# max_len cap must match training exactly)
PAD_MULTIPLE = 8


def dedup_sum_ratings(rows: np.ndarray, cols: np.ndarray,
                      values: np.ndarray, n_cols: int):
    """Sum duplicate (row, col) pairs — the template's
    ``reduceByKey(_ + _)`` aggregation (custom-query
    ALSAlgorithm.scala:50). Returns unique (rows, cols, summed values),
    sorted by (row, col) — downstream bucketing relies on the row
    grouping to skip its own sort.

    One integer radix argsort + contiguous ``add.reduceat`` — several
    times faster at 10M rows than the previous
    ``np.unique(return_inverse)`` + ``np.add.at`` (scattered atomics).
    """
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    values = np.asarray(values, dtype=np.float32)
    if not len(rows):
        return rows, cols, values
    key = rows * n_cols + cols
    order = np.argsort(key, kind="stable")
    return dedup_sum_sorted(key[order], rows[order], cols[order],
                            values[order])


def dedup_sum_sorted(key: np.ndarray, rows: np.ndarray, cols: np.ndarray,
                     values: np.ndarray):
    """The dedup-sum tail over triples ALREADY stably sorted by the
    (row, col) key: segment starts + one ``np.add.reduceat`` per run.
    Shared by :func:`dedup_sum_ratings` (which sorts first) and the
    pipelined ingest's k-way merge finalize (whose merge produces the
    identical stable order without the global sort) — one summation
    code path, so both lanes are byte-identical by construction."""
    if not len(rows):
        return (np.asarray(rows, dtype=np.int64),
                np.asarray(cols, dtype=np.int64),
                np.asarray(values, dtype=np.float32))
    from predictionio_tpu.native import codec as _native

    starts = _native.segment_starts(key)
    if starts is None:
        starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    sums = np.add.reduceat(values, starts).astype(np.float32)
    return (rows[starts].astype(np.int64),
            cols[starts].astype(np.int64), sums)


# ---------------------------------------------------------------------------
# Length-bucketed ratings (SURVEY hard part #1: padding/bucketing to keep
# MXU utilization on power-law-ragged data)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RatingsBucket:
    """Rows of one length class, padded to the bucket's own ``L``.

    ``row_ids[i]`` is the true row index of table row ``i``; padding rows
    (added to round the row count up) carry the sentinel ``n_rows`` and a
    zero mask, and the device scatter drops them (``mode="drop"``)."""

    row_ids: np.ndarray   # int32 [B]
    cols: np.ndarray      # int32 [B, L]
    weights: np.ndarray   # float32 [B, L]
    mask: np.ndarray      # float32 [B, L]

    @property
    def max_len(self) -> int:
        return int(self.cols.shape[1])


@dataclasses.dataclass
class BucketedRatings:
    """One solve side's ratings grouped into row-length buckets.

    Versus one ``[N, L_max]`` table padded to the longest (power-law)
    row, each bucket pads only to its own length class, so padded-slot
    occupancy — and with it the share of MXU work that multiplies real
    data — rises several-fold. The half-step solves each bucket as its
    own batched program sharing one Gram matrix (padding contributes
    exact zeros to every row's normal equations).
    """

    buckets: List["RatingsBucket"]
    n_rows: int
    n_cols: int

    @property
    def padded_slots(self) -> int:
        return sum(b.cols.size for b in self.buckets)

    @property
    def nnz(self) -> int:
        # integer sums, bucket by bucket: the masks are float32, and a
        # float32 running total stops counting exactly at 2^24 (at the
        # ML-20M shape it came out one pair short of 17,506,609)
        return sum(int(b.mask.sum(dtype=np.int32)) for b in self.buckets)

    @property
    def occupancy(self) -> float:
        slots = self.padded_slots
        return self.nnz / slots if slots else 0.0

    def to_device(self) -> "BucketedRatings":
        """New BucketedRatings whose tables live in HBM (the numpy
        original stays untouched); transfer once, train many. Blocks
        until every table has landed — :meth:`to_device_async` is the
        overlapped flavor the pipelined ingest uses."""
        return self.to_device_async().block_until_staged()

    def to_device_async(self, device=None) -> "BucketedRatings":
        """Start every bucket table's H2D transfer WITHOUT waiting for
        completion: ``jax.device_put`` dispatches asynchronously, so the
        caller keeps bucketizing the next table (or the other solve
        side) on host while these bytes stream — the double-buffering
        half of the ingest pipeline. Call :meth:`block_until_staged`
        (or just train) when the overlap window closes."""
        import jax

        def put(a):
            return jax.device_put(a, device)

        return dataclasses.replace(self, buckets=[
            dataclasses.replace(
                b, row_ids=put(b.row_ids), cols=put(b.cols),
                weights=put(b.weights), mask=put(b.mask))
            for b in self.buckets])

    def block_until_staged(self) -> "BucketedRatings":
        """Wait for all in-flight :meth:`to_device_async` transfers of
        this instance's tables; returns self (host-numpy tables are a
        no-op)."""
        for b in self.buckets:
            for a in (b.row_ids, b.cols, b.weights, b.mask):
                wait = getattr(a, "block_until_ready", None)
                if wait is not None:
                    wait()
        return self


def bucket_ratings(rows: np.ndarray, cols: np.ndarray, values: np.ndarray,
                   n_rows: int, n_cols: int,
                   bucket_lengths: Optional[Sequence[int]] = None,
                   max_len: Optional[int] = None,
                   pad_multiple: int = PAD_MULTIPLE,
                   row_multiple: int = 8) -> BucketedRatings:
    """Group rows by rating-count into geometric length buckets.

    Duplicates are summed first (``reduceByKey`` semantics,
    :func:`dedup_sum_ratings`). With ``max_len=None`` (the default) NOTHING is
    truncated: the top bucket's length is the true longest row, so
    coverage of unique pairs is 100% — the full-RDD semantics of MLlib's
    ``ALS.trainImplicit`` (custom-query ALSAlgorithm.scala:64-71).
    ``bucket_lengths=None`` builds a ×2 ladder from 16 up to the longest
    row; an explicit ladder is clipped/extended to cover it.
    """
    rows, cols, values = dedup_sum_ratings(rows, cols, values, n_cols)
    return _bucket_grouped(rows, cols, values, n_rows, n_cols,
                           bucket_lengths, max_len, pad_multiple,
                           row_multiple)


def bucket_ratings_pair(
        rows: np.ndarray, cols: np.ndarray, values: np.ndarray,
        n_rows: int, n_cols: int,
        bucket_lengths: Optional[Sequence[int]] = None,
        max_len: Optional[int] = None, pad_multiple: int = PAD_MULTIPLE,
        row_multiple: int = 8) -> Tuple[BucketedRatings, BucketedRatings]:
    """Both solve sides from one pass: dedup-sum once, bucket the row
    side from the (already row-grouped) result, and the column side
    after a single radix re-sort — half the host work of calling
    :func:`bucket_ratings` twice. Returns ``(row_side, col_side)``."""
    rows, cols, values = dedup_sum_ratings(rows, cols, values, n_cols)
    row_side = _bucket_grouped(rows, cols, values, n_rows, n_cols,
                               bucket_lengths, max_len, pad_multiple,
                               row_multiple)
    o = np.argsort(cols, kind="stable")
    col_side = _bucket_grouped(cols[o], rows[o], values[o], n_cols,
                               n_rows, bucket_lengths, max_len,
                               pad_multiple, row_multiple)
    return row_side, col_side


def _bucket_grouped(rows, cols, values, n_rows: int, n_cols: int,
                    bucket_lengths, max_len, pad_multiple: int,
                    row_multiple: int) -> BucketedRatings:
    """Bucketing core over DEDUPED triples sorted by row (the
    dedup_sum_ratings contract). Without truncation the incoming order
    is used as-is; only a live ``max_len`` cut pays a lexsort to keep
    each row's strongest-magnitude ratings."""
    counts = np.bincount(rows, minlength=n_rows)
    true_top = int(counts.max()) if counts.size and counts.max() > 0 else 1
    L_top = true_top
    if max_len is not None:
        L_top = min(L_top, int(max_len))
    L_top = max(1, -(-L_top // pad_multiple) * pad_multiple)
    if bucket_lengths is None:
        # x2 ladder from 16: short rows dominate power-law count
        # distributions, so the bottom rungs carry most of the rows and
        # set the occupancy; each row wastes < 2x its own length
        lengths = []
        L = min(16, L_top)
        while L < L_top:
            lengths.append(L)
            L *= 2
        lengths.append(L_top)
    else:
        lengths = sorted({min(int(x), L_top) for x in bucket_lengths})
        if not lengths or lengths[-1] < L_top:
            lengths.append(L_top)
    lengths = [max(1, -(-x // pad_multiple) * pad_multiple)
               for x in lengths]
    lengths = sorted(set(lengths))

    if true_top > L_top:
        # truncation active: order each row strongest-magnitude first
        # so the cut keeps the heaviest ratings
        order = np.lexsort((-np.abs(values), rows))
        rows, cols, values = rows[order], cols[order], values[order]
    row_starts = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(counts, out=row_starts[1:])
    pos = np.arange(len(rows)) - row_starts[rows]
    if true_top > L_top:
        keep = pos < L_top
        rows, cols, values, pos = \
            rows[keep], cols[keep], values[keep], pos[keep]

    eff = np.minimum(counts, L_top)
    b_of_row = np.searchsorted(lengths, eff, side="left")
    rank = np.empty(n_rows, dtype=np.int64)  # valid only at member rows
    # allocate every bucket's zeroed tables first, then fill — either in
    # ONE native pass over all entries (pio_bucket_fill: pure data
    # movement, byte-identical) or with the per-bucket numpy scatter
    # (one boolean pass over all entries PER bucket) as fallback
    tables: List[tuple] = []
    id_lists: List[np.ndarray] = []
    table_of_bucket = np.full(len(lengths), -1, dtype=np.int32)
    for b, L in enumerate(lengths):
        members = np.nonzero((b_of_row == b) & (eff > 0))[0]
        if members.size == 0:
            continue
        B = int(members.size)
        Bp = -(-B // row_multiple) * row_multiple
        rank[members] = np.arange(B)
        oc = np.zeros((Bp, L), dtype=np.int32)
        ow = np.zeros((Bp, L), dtype=np.float32)
        om = np.zeros((Bp, L), dtype=np.float32)
        row_ids = np.full(Bp, n_rows, dtype=np.int32)  # pad sentinel
        row_ids[:B] = members
        table_of_bucket[b] = len(tables)
        tables.append((oc, ow, om))
        id_lists.append(row_ids)
    if tables:
        from predictionio_tpu.native import codec as _native

        if not _native.bucket_fill(rows, cols, values, pos,
                                   table_of_bucket[b_of_row], rank,
                                   tables):
            b_of_entry = b_of_row[rows]
            for b in range(len(lengths)):
                ti = int(table_of_bucket[b])
                if ti < 0:
                    continue
                oc, ow, om = tables[ti]
                sel = b_of_entry == b
                r, c, v, p = rows[sel], cols[sel], values[sel], pos[sel]
                oc[rank[r], p] = c
                ow[rank[r], p] = v
                om[rank[r], p] = 1.0
    out = [RatingsBucket(ids, *tbl) for ids, tbl in zip(id_lists, tables)]
    return BucketedRatings(out, n_rows, n_cols)


# ---------------------------------------------------------------------------
# Device kernels
# ---------------------------------------------------------------------------

def implicit_weights(w, alpha: float):
    """Hu-Koren-Volinsky confidence/preference weights: A-matrix
    weights ``alpha*|r|`` and b-vector weights ``pref*(1+alpha*|r|)``
    with ``pref = 1 iff r > 0``."""
    import jax.numpy as jnp

    aw = alpha * jnp.abs(w)
    bw = (w > 0).astype(w.dtype) * (1.0 + aw)
    return aw, bw


def zero_empty_rows(X, mask):
    """Rows with no ratings keep a zero factor (matches MLlib dropping
    them)."""
    import jax.numpy as jnp

    has_any = (jnp.sum(mask, axis=1) > 0).astype(X.dtype)
    return X * has_any[:, None]


PRECISION_MODES = ("fp32", "bf16")


def normalize_precision(value: str, source: str,
                        allowed: tuple = PRECISION_MODES) -> str:
    """Canonicalize a precision string (accepting the ``float32``/
    ``bfloat16``/``int8``-family aliases) or raise naming ``source`` —
    the ONE canonicalization shared by the training
    (``PIO_ALS_PRECISION``) and serving (``PIO_SERVE_PRECISION``)
    resolvers. ``allowed`` is each resolver's whitelist: training
    accepts only :data:`PRECISION_MODES`; serving extends it with
    ``int8`` (a storage-only mode that makes no sense as a training
    accumulate policy, so it must NOT leak into this default)."""
    mode = {"float32": "fp32", "bfloat16": "bf16",
            "i8": "int8"}.get(value, value)
    if mode not in allowed:
        raise ValueError(
            f"{source}={mode!r} is not a known precision mode "
            f"(expected one of: {', '.join(allowed)})")
    return mode


def _als_precision_mode(params: Optional[ALSParams] = None) -> str:
    """``fp32`` (the historical all-fp32 pipeline, byte-identical
    default) or ``bf16`` (bf16 factor storage/gather, fp32 accumulation
    and solve — ALX §4). ``PIO_ALS_PRECISION`` overrides
    ``ALSParams.precision``; an unknown value raises instead of being
    silently ignored. Resolved ONCE per ``train_als*`` call and passed
    down as a static jit argument — never read at trace time, so
    changing the env var between trainings always takes effect (same
    contract as ``_spd_solver_mode``)."""
    import os

    forced = os.environ.get("PIO_ALS_PRECISION", "").strip().lower()
    if forced:
        return normalize_precision(forced, "PIO_ALS_PRECISION")
    mode = str(getattr(params, "precision", None)
               or "fp32").strip().lower()
    return normalize_precision(mode, "ALSParams.precision")


def factor_dtype(precision: str):
    """The on-device factor storage dtype for a resolved precision mode."""
    import jax.numpy as jnp

    return jnp.bfloat16 if precision == "bf16" else jnp.float32


def init_policy_factors(n_rows: int, n_cols: int, rank: int,
                        seed: Optional[int], dtype,
                        precision: str) -> Tuple:
    """:func:`init_factors` under the precision policy: the random draw
    always happens in the caller's ``dtype`` (fp32 by default), and
    only THEN casts to the bf16 factor store — both precision lanes
    start from (near-)identical factors, so differential suites isolate
    the solve numerics, not the RNG's dtype behavior. Shared by every
    ``train_als*`` entry point."""
    X, Y = init_factors(n_rows, n_cols, rank, seed, dtype)
    if precision == "bf16" and dtype is None:
        X, Y = X.astype(factor_dtype(precision)), \
            Y.astype(factor_dtype(precision))
    return X, Y


def _refine_solve(A, b, X, solver: Optional[str]):
    """One fp32 iterative-refinement pass: x += solve(A, b - A x).
    Tightens the residual left by bf16-rounded A/b assembly (the solve
    itself is already fp32 either way)."""
    import jax
    import jax.numpy as jnp

    r = b - jnp.einsum("brs,bs->br", A, X,
                       precision=jax.lax.Precision.HIGHEST)
    return X + _spd_solve(A, r, solver)


def _solve_rows(Y, cols, weights, mask, lam: float, alpha: float,
                implicit: bool, gram=None, solver: Optional[str] = None,
                precision: str = "fp32", refine: bool = False,
                extra_ridge=None, shared=None):
    """Normal-equation solve for one batch of rows: given fixed factors
    ``Y [M, R]`` and padded ratings ``[B, L]`` (+ validity mask), return
    new factors ``[B, R]``. ``gram`` (``Y^T Y``, implicit term) may be
    precomputed by the caller so bucketed solves share one.

    jit-friendly: static shapes, two einsums + batched Cholesky; runs on
    the MXU. Written to be shard_map-compatible: only ``cols``/``weights``/
    ``mask`` carry the batch dimension.

    What runs where: with the Pallas solver resolved (one TPU device,
    rank <= ``SPD_MAX_RANK``, or ``PIO_ALS_SOLVER=pallas``) and fp32
    precision, :func:`_solve_rows_kernel`: the gather of 128-lane rows,
    ``als_pallas.assemble_normal_equations`` (one kernel reads the
    gathered block where it lies and writes ``A`` and ``b``
    batch-minor) and ``als_pallas.spd_solve_batch_minor``; ``shared``
    is :func:`_kernel_operands`' pair, made once a half-step by
    :func:`_solve_side_bucketed` and here when absent. Everywhere else
    (``lanes``: the sharded trainers, rank > 96; ``cho``: CPU, GPU; the
    bf16 lane) the path below: ``jnp.take``, :func:`_assemble_fp32` or
    :func:`_assemble_bf16`, :func:`_spd_solve`.

    ``lam``/``alpha`` may be python floats (the serial paths, where they
    are static jit args) or traced scalars (the vmapped config-grid
    path, where one compiled program serves every hyperparameter
    value). ``extra_ridge`` is an optional ``[R]`` diagonal addition the
    grid path uses to keep rank-padded columns solvable: a config of
    rank r < R carries zero factor columns beyond r, which zero the
    corresponding rows/cols of A and of b, so with a positive ridge on
    those diagonal entries the padded coordinates solve to EXACT zeros
    (block-diagonal system, zero rhs) and the leading r coordinates are
    untouched — even at lambda = 0.

    ``precision="bf16"``: ``Y`` is stored bfloat16, so the dominant
    ``[B, L, R]`` gather moves half the HBM bytes; the confidence
    weights are computed in fp32 then cast to bf16 so the MXU multiplies
    native bf16 operands while ``preferred_element_type`` keeps the
    normal-equation accumulators fp32; the batched Cholesky solve stays
    fp32 and the new factors cast back to bf16 (ALX §4's
    storage/compute split). ``"fp32"`` is byte-identical to the
    historical path.
    """
    import jax
    import jax.numpy as jnp

    if assembles_in_kernel(solver, precision):
        if shared is None:
            shared = _kernel_operands(Y, lam, implicit, gram, extra_ridge)
        return _solve_rows_kernel(cols, weights, mask, lam, alpha,
                                  implicit, refine, *shared)
    with jax.named_scope("gather"):
        Yg = jnp.take(Y, cols, axis=0)        # [B, L, R] gather
    if precision == "bf16":
        X = _solve_rows_bf16(Y, Yg, weights, mask, lam, alpha, implicit,
                             gram, solver, refine, extra_ridge)
        return zero_empty_rows(X, mask.astype(X.dtype))
    with jax.named_scope("assemble"):
        A, b, mask = _assemble_fp32(Y, Yg, weights, mask, lam, alpha,
                                    implicit, gram, extra_ridge)
    with jax.named_scope("solve"):
        X = _spd_solve(A, b, solver)
        if refine:
            X = _refine_solve(A, b, X, solver)
        return zero_empty_rows(X, mask)


def _assemble_fp32(Y, Yg, weights, mask, lam, alpha, implicit: bool,
                   gram, extra_ridge):
    """The fp32 normal equations of :func:`_solve_rows`: ``A [B, R, R]``,
    ``b [B, R]`` and the mask cast to the factor dtype."""
    import jax
    import jax.numpy as jnp

    R = Y.shape[1]
    mask = mask.astype(Y.dtype)
    w = weights.astype(Y.dtype) * mask        # zero out padded slots
    # Normal equations are precision-sensitive: force full fp32 MXU passes
    # instead of TPU's default bf16 matmul decomposition (cf. ALX §4).
    hi = jax.lax.Precision.HIGHEST

    if implicit:
        # MLlib trainImplicit semantics: confidence c = 1 + alpha*|r|,
        # preference p = 1 iff r > 0. |r| keeps A positive-definite when
        # ratings carry negative signal (e.g. dislikes).
        # A_b = YtY + alpha * sum_j |r_j| y_j y_j^T + lam I
        # b_b = sum_j p_j (1 + alpha |r_j|) y_j
        aw, bw = implicit_weights(w, alpha)
        if gram is None:
            gram = jnp.matmul(Y.T, Y, precision=hi)              # [R, R]
        corr = jnp.einsum("bl,blr,bls->brs", aw, Yg, Yg,
                          precision=hi)                          # [B, R, R]
        A = gram[None, :, :] + corr
        A += lam * jnp.eye(R, dtype=Y.dtype)[None, :, :]
        b = jnp.einsum("bl,blr->br", bw, Yg, precision=hi)       # [B, R]
    else:
        # explicit ALS-WR: A_b = sum_j y_j y_j^T + lam n_b I; b = sum r y
        A = jnp.einsum("bl,blr,bls->brs", mask, Yg, Yg, precision=hi)
        n_b = jnp.sum(mask, axis=1)                              # [B]
        A += (lam * jnp.maximum(n_b, 1.0))[:, None, None] \
            * jnp.eye(R, dtype=Y.dtype)[None, :, :]
        b = jnp.einsum("bl,blr->br", w, Yg, precision=hi)

    if extra_ridge is not None:
        A += extra_ridge.astype(A.dtype)[None, None, :] \
            * jnp.eye(R, dtype=A.dtype)
    return A, b, mask


def assembles_in_kernel(solver: Optional[str], precision: str) -> bool:
    """Whether :func:`_solve_rows` hands gather and assembly to the
    Pallas kernel: wherever the Pallas solver was resolved (one TPU
    device, rank <= ``SPD_MAX_RANK``, or ``PIO_ALS_SOLVER=pallas``), in
    the fp32 lane."""
    return solver == "pallas" and precision != "bf16"


def _kernel_operands(Y, lam, implicit: bool, gram, extra_ridge):
    """What every batch of rows solved against ``Y`` hands the assembly
    kernel, made once a half-step: ``Y`` as the 128-lane table the
    kernel's gather reads, and the part of ``A`` every row shares
    (implicit: Gram + ridge; ``extra_ridge`` on the diagonal), which the
    kernel sums the weighted outer products onto."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import als_pallas

    R = Y.shape[1]
    eye = jnp.eye(R, dtype=Y.dtype)
    if implicit:
        if gram is None:
            gram = jnp.matmul(Y.T, Y, precision=jax.lax.Precision.HIGHEST)
        start = gram + lam * eye
    else:
        start = jnp.zeros((R, R), Y.dtype)
    if extra_ridge is not None:
        start = start + extra_ridge.astype(Y.dtype)[None, :] * eye
    return als_pallas.widen_table(Y), als_pallas.widen_start(start)


def _solve_rows_kernel(cols, weights, mask, lam, alpha, implicit: bool,
                       refine: bool, wide, start):
    """:func:`_solve_rows` where the Pallas kernels run: gather and
    :func:`_assemble_fp32`'s equations come from
    ``als_pallas.assemble_normal_equations`` batch-minor (``At [R, R,
    Bq]``, ``bt [R, Bq]``), which is how ``spd_solve``'s kernel reads
    them, so nothing passes over ``A`` between the two. ``wide`` and
    ``start`` are :func:`_kernel_operands`'; ALS-WR's ridge, which
    differs by row, is added behind the kernel."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import als_pallas

    B, R = cols.shape[0], start.shape[0]
    dtype = wide.dtype
    mask = mask.astype(dtype)
    w = weights.astype(dtype) * mask          # zero out padded slots
    aw, bw = implicit_weights(w, alpha) if implicit else (mask, w)
    At, bt = als_pallas.assemble_normal_equations(wide, cols, aw, bw,
                                                  start)
    if not implicit:
        with jax.named_scope("assemble"):
            n_b = jnp.pad(jnp.sum(mask, axis=1), (0, bt.shape[1] - B))
            At += jnp.eye(R, dtype=dtype)[:, :, None] \
                * (lam * jnp.maximum(n_b, 1.0))
    with jax.named_scope("solve"):
        X = als_pallas.spd_solve_batch_minor(At, bt)[:, :B].T
        if refine:
            A, b = jnp.transpose(At[:, :, :B], (2, 0, 1)), bt[:, :B].T
            X = _refine_solve(A, b, X, "pallas")
        return zero_empty_rows(X, mask)


def _solve_rows_bf16(Y, Yg, weights, mask, lam: float, alpha: float,
                     implicit: bool, gram, solver: Optional[str],
                     refine: bool, extra_ridge=None):
    """The bf16 lane of :func:`_solve_rows`: bf16 operands into every
    MXU pass, fp32 accumulators out (``preferred_element_type``), fp32
    solve, result cast back to bf16 factor storage."""
    import jax

    with jax.named_scope("assemble"):
        A, b = _assemble_bf16(Y, Yg, weights, mask, lam, alpha, implicit,
                              gram, extra_ridge)
    with jax.named_scope("solve"):
        X = _spd_solve(A, b, solver)
        if refine:
            X = _refine_solve(A, b, X, solver)
        return X.astype(Y.dtype)


def _assemble_bf16(Y, Yg, weights, mask, lam, alpha, implicit: bool,
                   gram, extra_ridge):
    """The bf16 lane's normal equations: bf16 operands, fp32
    accumulators (``A [B, R, R]``, ``b [B, R]``)."""
    import jax.numpy as jnp

    f32, bf16 = jnp.float32, jnp.bfloat16
    R = Y.shape[1]
    mask32 = mask.astype(f32)
    w32 = weights.astype(f32) * mask32        # zero out padded slots
    if implicit:
        aw, bw = implicit_weights(w32, alpha)
        if gram is None:
            gram = jnp.matmul(Y.T, Y, preferred_element_type=f32)
        corr = jnp.einsum("bl,blr,bls->brs", aw.astype(bf16), Yg, Yg,
                          preferred_element_type=f32)            # [B, R, R]
        A = gram[None, :, :].astype(f32) + corr
        A += lam * jnp.eye(R, dtype=f32)[None, :, :]
        b = jnp.einsum("bl,blr->br", bw.astype(bf16), Yg,
                       preferred_element_type=f32)               # [B, R]
    else:
        A = jnp.einsum("bl,blr,bls->brs", mask32.astype(bf16), Yg, Yg,
                       preferred_element_type=f32)
        n_b = jnp.sum(mask32, axis=1)                            # [B]
        A += (lam * jnp.maximum(n_b, 1.0))[:, None, None] \
            * jnp.eye(R, dtype=f32)[None, :, :]
        b = jnp.einsum("bl,blr->br", w32.astype(bf16), Yg,
                       preferred_element_type=f32)
    if extra_ridge is not None:
        A += extra_ridge.astype(f32)[None, None, :] * jnp.eye(R, dtype=f32)
    return A, b


SOLVER_MODES = ("lanes", "cho", "xla", "pallas")


class SolverChoice(NamedTuple):
    """What :func:`_resolve_spd_solver` decided: the ``name`` of the
    solver that runs, and whether that is ``lanes`` only because the
    Pallas kernel could not take the systems (``fell_back``)."""

    name: str
    fell_back: bool


def _devices_spanned(operands) -> int:
    """The most devices any array in the ``operands`` pytree lives on
    (sharded or replicated alike): more than one makes the jit that
    reads it a partitioned program. Host arrays and abstract shapes
    count as one."""
    import jax

    n = 1
    for a in jax.tree_util.tree_leaves(operands):
        sharding = getattr(a, "sharding", None)
        if sharding is not None:
            n = max(n, len(sharding.device_set))
    return n


def _resolve_spd_solver(rank: int, operands) -> SolverChoice:
    """The solver that RUNS for systems of this ``rank`` in the program
    that reads ``operands`` (the arrays the caller is about to hand its
    jit): ``pallas`` (``ops/als_pallas.py::spd_solve``), ``lanes``
    (:func:`spd_solve_lanes`) or ``cho`` (LAPACK-backed ``cho_solve``).

    ``PIO_ALS_SOLVER`` asks (``lanes``, ``cho`` or its alias ``xla``,
    ``pallas``; an unknown value raises instead of being silently
    ignored), else the platform does: the Pallas kernel on a TPU,
    ``cho`` on CPU/GPU. The kernel yields to ``lanes`` where it cannot
    take the systems: above ``als_pallas.SPD_MAX_RANK`` (its three
    ``[R, R, 128]`` VMEM buffers no longer fit), and where any operand
    spans more than one device, because the jit is then a partitioned
    program and the TPU compiler refuses a Mosaic call there ("cannot
    be automatically partitioned. Please wrap the call in a
    shard_map"): the sharded trainers' tables, and fold-in against a
    serving store sharded or replicated over a mesh. The device count
    is read off the operands here so that no caller has to remember it.

    Resolved ONCE per ``train_als*`` / ``fold_in_users`` call and passed
    down as a static jit argument — never read at trace time, so
    changing the env var between trainings always takes effect — and
    the same name goes into the checkpoint fingerprint, the run log and
    the ``als.iterations`` span."""
    import os

    mode = os.environ.get("PIO_ALS_SOLVER", "").strip().lower()
    if mode:
        if mode not in SOLVER_MODES:
            raise ValueError(
                f"PIO_ALS_SOLVER={mode!r} is not a known solver mode "
                f"(expected one of: {', '.join(SOLVER_MODES)})")
        if mode == "xla":
            mode = "cho"
    else:
        import jax

        mode = "pallas" if jax.default_backend() == "tpu" else "cho"
    if mode == "pallas":
        from predictionio_tpu.ops import als_pallas

        if int(rank) > als_pallas.SPD_MAX_RANK \
                or _devices_spanned(operands) > 1:
            return SolverChoice("lanes", True)
    return SolverChoice(mode, False)


def _spd_solver_mode(rank: int, operands) -> str:
    """:func:`_resolve_spd_solver`'s name alone, for callers with no
    span to tell of a fallback."""
    return _resolve_spd_solver(rank, operands).name


def solve_span_attributes(choice: SolverChoice, systems: int,
                          precision: str = "fp32") -> dict:
    """What a span round a batch of solves says of them: the resolved
    ``solver``, the ``solve_systems`` it was handed (padded rows
    included), how many of those took ``lanes`` only because the
    Pallas kernel could not (``solve_systems_fallback``), and how many
    had their equations assembled by the Pallas kernel
    (``assemble_systems_kernel``: all where :func:`assembles_in_kernel`
    says so, else none)."""
    return {"solver": choice.name, "solve_systems": int(systems),
            "solve_systems_fallback":
                int(systems) if choice.fell_back else 0,
            "assemble_systems_kernel":
                int(systems) if assembles_in_kernel(choice.name, precision)
                else 0}


def _spd_solve(A, b, mode: str):
    """Batched SPD solve of ``A [B, R, R] x = b [B, R]`` by the solver
    :func:`_resolve_spd_solver` named (this runs under the callers'
    jits, where nothing can be resolved any more).

    On a TPU XLA's batched ``cho_factor``/``cho_solve`` round-trips the
    whole matrix batch through HBM on every column (129 ms per 16,384
    rank-64 systems on a v5e) and :func:`spd_solve_lanes` once a panel
    (29.6 ms); the Pallas kernel keeps 128 systems in VMEM for all R
    steps (1.9 ms since PR 49, 3.7 before), so it is the default there
    up to
    ``als_pallas.SPD_MAX_RANK``. CPU/GPU keep LAPACK-backed cho_solve."""
    import jax

    R = b.shape[-1]
    if mode == "pallas":
        from predictionio_tpu.ops import als_pallas

        if R > als_pallas.SPD_MAX_RANK:
            raise ValueError(
                f"the Pallas SPD kernel takes rank <= "
                f"{als_pallas.SPD_MAX_RANK}, got {R}: resolve the solver "
                f"with _resolve_spd_solver")
        return als_pallas.spd_solve(A, b).astype(b.dtype)
    if mode == "lanes":
        return spd_solve_lanes(A, b).astype(b.dtype)
    chol = jax.scipy.linalg.cho_factor(A)
    return jax.scipy.linalg.cho_solve(chol, b)


def spd_solve_lanes(A, b, panel: int = 8):
    """Batched SPD solve with the batch on the minor (lane) dimension —
    TPU-shaped replacement for ``cho_solve(cho_factor(A), b)``.

    Layout: ``A`` is transposed to ``[R, R, B]`` so each scalar of the
    factorization (pivot, reciprocal sqrt, substitution coefficient) is
    a ``[B]``-wide vector op across all systems at once. The
    factorization is blocked into ``panel``-column panels: the
    panel-internal masked column steps touch only ``[R, panel, B]``
    slices, and each panel issues ONE full-matrix rank-``panel`` update
    (a batched matmul on the MXU) — versus XLA's cholesky expansion
    whose per-column while-loop reads and writes the entire ``[B, R,
    R]`` batch every step. HBM traffic drops from ``O(R)`` full-matrix
    round-trips to ``O(R/panel)``.

    Same math as non-pivoted Cholesky + forward/backward substitution;
    fp32; agreement with scipy asserted in tests on every backend.
    """
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    B, R = b.shape
    if R % panel:
        pad = panel - R % panel
        eye_tail = jnp.zeros((B, R, pad), f32)
        A = jnp.concatenate([A.astype(f32), eye_tail], axis=2)
        tail_rows = jnp.concatenate(
            [jnp.zeros((B, pad, R), f32),
             jnp.broadcast_to(jnp.eye(pad, dtype=f32)[None], (B, pad, pad))],
            axis=2)
        A = jnp.concatenate([A, tail_rows], axis=1)
        b = jnp.concatenate([b.astype(f32), jnp.zeros((B, pad), f32)],
                            axis=1)
        Rp = R + pad
    else:
        Rp = R
    At = jnp.transpose(A.astype(f32), (1, 2, 0))          # [Rp, Rp, B]
    bt = jnp.transpose(b.astype(f32), (1, 0))             # [Rp, B]
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (Rp, 1, 1), 0)
    n_panels = Rp // panel

    def panel_step(p, carry):
        A, L = carry
        k0 = p * panel
        pan = jax.lax.dynamic_slice(A, (0, k0, 0), (Rp, panel, B))

        def col_step(j, pan):
            k = k0 + j
            c = jax.lax.dynamic_slice(pan, (0, j, 0), (Rp, 1, B))
            d = jnp.maximum(
                jax.lax.dynamic_slice(c, (k, 0, 0), (1, 1, B)), 1e-30)
            lcol = c / jnp.sqrt(d) * (iota_r >= k).astype(f32)
            # pivot-row values of lcol for the panel's columns
            lrow = jax.lax.dynamic_slice(lcol, (k0, 0, 0), (panel, 1, B))
            # update columns jj > j of the panel; write lcol into col j
            jj = jax.lax.broadcasted_iota(jnp.int32, (1, panel, 1), 1)
            upd = lcol * jnp.transpose(lrow, (1, 0, 2))   # [Rp, panel, B]
            pan = pan - upd * (jj > j).astype(f32)
            return jnp.where(jj == j, lcol, pan)

        pan = jax.lax.fori_loop(0, panel, col_step, pan)
        L = jax.lax.dynamic_update_slice(L, pan, (0, k0, 0))
        # one rank-`panel` trailing update on the MXU, masked to the
        # not-yet-factored columns (rows need no mask: lcol's >= masks
        # already zero everything above each column's pivot)
        upd = jnp.einsum("rpb,spb->rsb", pan, pan,
                         precision=jax.lax.Precision.HIGHEST)
        col_gt = (jax.lax.broadcasted_iota(jnp.int32, (1, Rp, 1), 1)
                  >= k0 + panel).astype(f32)
        A = A - upd * col_gt
        return A, L

    with jax.named_scope("factor"):
        _, L = jax.lax.fori_loop(0, n_panels, panel_step,
                                 (At, jnp.zeros_like(At)))

    def fwd_step(k, carry):
        y, bw = carry
        lc = jax.lax.dynamic_slice(L, (0, k, 0), (Rp, 1, B))[:, 0, :]
        d = jax.lax.dynamic_slice(lc, (k, 0), (1, B))
        yk = jax.lax.dynamic_slice(bw, (k, 0), (1, B)) / d
        y = jax.lax.dynamic_update_slice(y, yk, (k, 0))
        bw = bw - lc * yk                     # rows < k of lc are zero
        return y, bw

    with jax.named_scope("forward"):
        y, _ = jax.lax.fori_loop(0, Rp, fwd_step,
                                 (jnp.zeros_like(bt), bt))

    def bwd_step(i, x):
        k = Rp - 1 - i
        lc = jax.lax.dynamic_slice(L, (0, k, 0), (Rp, 1, B))[:, 0, :]
        d = jax.lax.dynamic_slice(lc, (k, 0), (1, B))
        s = jnp.sum(lc * x, axis=0, keepdims=True)        # x[k] still 0
        xk = (jax.lax.dynamic_slice(y, (k, 0), (1, B)) - s) / d
        return jax.lax.dynamic_update_slice(x, xk, (k, 0))

    with jax.named_scope("backward"):
        x = jax.lax.fori_loop(0, Rp, bwd_step, jnp.zeros_like(bt))
    return jnp.transpose(x, (1, 0))[:, :R]


def _solve_side_bucketed(Y, buckets, n_rows_out: int, lam: float,
                         alpha: float, implicit: bool,
                         slot_budget: Optional[int],
                         solver: Optional[str] = None,
                         precision: str = "fp32", refine: bool = False,
                         extra_ridge=None):
    """One alternating half-step over length buckets: each bucket is a
    batched solve at its own ``L`` (one Gram matrix shared by all), and
    the results scatter into the full factor matrix, all buckets in one
    scatter. Rows in no bucket (no ratings) keep zero factors — same as
    ``zero_empty_rows``.

    ``buckets`` is a sequence of ``(row_ids, cols, weights, mask)``
    array tuples (a pytree — this function runs under jit). A bucket
    whose padded table exceeds ``slot_budget`` rows*L slots is solved in
    sequential row blocks (lax.map) to bound the [rows, L, R] gather."""
    import jax
    import jax.numpy as jnp

    R = Y.shape[1]
    with jax.named_scope("gram"):
        if precision == "bf16":
            # one shared fp32-accumulated Gram from the bf16 factor store
            gram = jnp.matmul(Y.T, Y, preferred_element_type=jnp.float32) \
                if implicit else None
        else:
            gram = jnp.matmul(Y.T, Y,
                              precision=jax.lax.Precision.HIGHEST) \
                if implicit else None
    shared = None
    if assembles_in_kernel(solver, precision):
        shared = _kernel_operands(Y, lam, implicit, gram, extra_ridge)
    ids, solved = [], []
    for row_ids, cols, w, m in buckets:
        B, L = cols.shape
        if slot_budget and B * L > slot_budget:
            block = max(8, (slot_budget // L) // 8 * 8)
            pad = (-B) % block
            if pad:
                cols = jnp.pad(cols, ((0, pad), (0, 0)))
                w = jnp.pad(w, ((0, pad), (0, 0)))
                m = jnp.pad(m, ((0, pad), (0, 0)))
                row_ids = jnp.pad(row_ids, (0, pad),
                                  constant_values=n_rows_out)
            nb = (B + pad) // block

            def one(args, _gram=gram):
                c_, w_, m_ = args
                return _solve_rows(Y, c_, w_, m_, lam, alpha, implicit,
                                   _gram, solver, precision, refine,
                                   extra_ridge, shared)

            Xb = jax.lax.map(one, (cols.reshape(nb, block, L),
                                   w.reshape(nb, block, L),
                                   m.reshape(nb, block, L)))
            Xb = Xb.reshape(B + pad, R)
        else:
            Xb = _solve_rows(Y, cols, w, m, lam, alpha, implicit, gram,
                             solver, precision, refine, extra_ridge,
                             shared)
        ids.append(row_ids)
        solved.append(Xb)
    # ONE scatter a half-step, after the last solve: the new factor
    # matrix is then born after the last Mosaic call, and the compiler
    # keeps it in VMEM for the next half-step's gathers (scattered into
    # bucket by bucket its buffer lives across the kernels, stays in
    # HBM, and the item step's gathers run at a seventh of the speed:
    # PERF.md section 6, PR 26). Pad rows carry the sentinel row_id ==
    # n_rows_out -> dropped
    X = jnp.zeros((n_rows_out, R), Y.dtype)
    if not buckets:  # a side with no ratings at all
        return X
    with jax.named_scope("scatter"):
        return X.at[jnp.concatenate(ids)].set(jnp.concatenate(solved),
                                              mode="drop")


def _als_iterations_bucketed_impl(X, Y, u_buckets, i_buckets, *, lam,
                                  alpha, implicit, num_iterations,
                                  slot_budget, solver=None,
                                  precision="fp32", refine=False):
    """Bucketed training loop as one compiled program (lax.scan over
    iterations; the per-bucket solves are unrolled in the trace — a
    handful of static shapes, not data-dependent control flow)."""
    import jax

    n_u, n_i = X.shape[0], Y.shape[0]

    def body(carry, _):
        X, Y = carry
        with jax.named_scope("user_step"):
            X = _solve_side_bucketed(Y, u_buckets, n_u, lam, alpha,
                                     implicit, slot_budget, solver,
                                     precision, refine)
        with jax.named_scope("item_step"):
            Y = _solve_side_bucketed(X, i_buckets, n_i, lam, alpha,
                                     implicit, slot_budget, solver,
                                     precision, refine)
        return (X, Y), None

    (X, Y), _ = jax.lax.scan(body, (X, Y), None, length=num_iterations)
    return X, Y


_als_iterations_bucketed_jit = None

# AOT-compiled bucketed executables: abstract-signature key ->
# jax Compiled. Populated by warmup_train_als_bucketed (typically on a
# background thread overlapping H2D transfers); consulted by
# _als_iterations_bucketed so the warmed first train skips its compile
# wait entirely. The bounded-FIFO/best-effort machinery is the shared
# ops/aot.py cache — the same pattern DeviceTopK's serve-time bucket
# ladder precompiles through.
from predictionio_tpu.ops.aot import AOTCache as _AOTCache

_AOT_BUCKETED_MAX = 8
_aot_bucketed = _AOTCache(_AOT_BUCKETED_MAX, name="train-bucketed")


def _bucketed_aot_key(args, kw) -> tuple:
    """Abstract signature of one bucketed training call: every leaf's
    (shape, dtype, device ids) plus the static kwargs — what XLA would
    key its compilation on. Device identity matters: the warm-up
    lowers for the DEFAULT device (ShapeDtypeStructs carry none), so a
    call whose tables were committed elsewhere must miss the cache and
    take the jit path (which compiles for the right device) instead of
    crashing the default-device executable."""
    import jax

    default_ids = (jax.devices()[0].id,)

    def leaf_sig(a):
        devs = getattr(a, "devices", None)
        ids = (tuple(sorted(d.id for d in devs()))
               if callable(devs) else default_ids)
        return (tuple(a.shape), str(a.dtype), ids)

    leaves = jax.tree_util.tree_leaves(args)
    return (tuple(leaf_sig(a) for a in leaves),
            tuple(sorted(kw.items())))


def _get_bucketed_jit():
    global _als_iterations_bucketed_jit
    if _als_iterations_bucketed_jit is None:
        import jax

        _als_iterations_bucketed_jit = jax.jit(
            _als_iterations_bucketed_impl,
            static_argnames=("lam", "alpha", "implicit", "num_iterations",
                             "slot_budget", "solver", "precision",
                             "refine"),
            donate_argnums=(0, 1))
    return _als_iterations_bucketed_jit


def _als_iterations_bucketed(*args, **kw):
    """Jitted bucketed loop. The X/Y carries are DONATED: steady-state
    iterations write the new factors into the input buffers' HBM
    instead of copying two ``[N, R]`` matrices per dispatch, so callers
    must treat the factor arrays they pass in as consumed.
    ``solver``/``precision`` arrive resolved as STATIC args: an env-var
    change retriggers compilation instead of being baked in at first
    trace. A
    matching AOT executable from :func:`warmup_train_als_bucketed`
    (statics baked at lower time) is used when present."""
    jitted = _get_bucketed_jit()
    if len(_aot_bucketed):
        compiled = _aot_bucketed.get(_bucketed_aot_key(args, kw))
        if compiled is not None:
            return compiled(*args)
    return jitted(*args, **kw)


def _als_iterations_grid_impl(X, Y, lam, alpha, ridge, u_buckets,
                              i_buckets, *, implicit, num_iterations,
                              slot_budget, solver=None,
                              precision="fp32", refine=False):
    """Multi-config bucketed training loop: the per-iteration half-steps
    vmapped over a leading CONFIG axis (DrJAX's map-over-leading-axis
    idiom), so ONE compiled program advances all k hyperparameter
    configs per iteration.

    ``X [k, N, R]`` / ``Y [k, M, R]`` carry one factor set per config;
    ``lam [k]`` / ``alpha [k]`` are TRACED fp32 vectors (in the serial
    path they are static jit args — k distinct lambdas there mean k XLA
    compiles; here one program serves any values at fixed k);
    ``ridge [k, R]`` is ``1.0`` on each config's rank-padded columns
    (see :func:`_solve_rows` — pads solve to exact zeros, so a rank-r
    config's leading r columns match its serial rank-r run). The bucket
    tables are closed over WITHOUT a config axis: vmap broadcasts them,
    so the device holds k factor sets but only ONE copy of the ratings —
    ingest and HBM for the tables are paid once for the whole grid.
    """
    import jax

    n_u, n_i = X.shape[1], Y.shape[1]

    def half_steps(Xk, Yk, lamk, alphak, ridgek):
        Xk = _solve_side_bucketed(Yk, u_buckets, n_u, lamk, alphak,
                                  implicit, slot_budget, solver,
                                  precision, refine, ridgek)
        Yk = _solve_side_bucketed(Xk, i_buckets, n_i, lamk, alphak,
                                  implicit, slot_budget, solver,
                                  precision, refine, ridgek)
        return Xk, Yk

    vstep = jax.vmap(half_steps, in_axes=(0, 0, 0, 0, 0))

    def body(carry, _):
        Xc, Yc = carry
        Xc, Yc = vstep(Xc, Yc, lam, alpha, ridge)
        return (Xc, Yc), None

    (X, Y), _ = jax.lax.scan(body, (X, Y), None, length=num_iterations)
    return X, Y


_als_iterations_grid_jit = None

_AOT_GRID_MAX = 8
_aot_grid = _AOTCache(_AOT_GRID_MAX, name="train-grid")


def _get_grid_jit():
    global _als_iterations_grid_jit
    if _als_iterations_grid_jit is None:
        import jax

        _als_iterations_grid_jit = jax.jit(
            _als_iterations_grid_impl,
            static_argnames=("implicit", "num_iterations", "slot_budget",
                             "solver", "precision", "refine"),
            donate_argnums=(0, 1))
    return _als_iterations_grid_jit


def _als_iterations_grid(*args, **kw):
    """Jitted grid loop (X/Y donated, lam/alpha/ridge traced); a
    matching AOT executable from the grid-aware
    :func:`warmup_train_als_bucketed` is used when present — the same
    zero-steady-state-compile contract as the serial bucketed lane."""
    jitted = _get_grid_jit()
    if len(_aot_grid):
        compiled = _aot_grid.get(_bucketed_aot_key(args, kw))
        if compiled is not None:
            return compiled(*args)
    return jitted(*args, **kw)


def _bucket_tables(*sides: BucketedRatings) -> tuple:
    """Each side's buckets as the ``(row_ids, cols, weights, mask)``
    tuples the jitted loops take: one tuple of tuples a side."""
    return tuple(tuple((b.row_ids, b.cols, b.weights, b.mask)
                       for b in s.buckets) for s in sides)


def _grid_call_args(user_side: BucketedRatings,
                    item_side: BucketedRatings, configs,
                    precision: str, abstract: bool = False,
                    num_iterations: Optional[int] = None):
    """The exact (args, static kwargs) grid training passes to
    :func:`_als_iterations_grid` — shared with the AOT warm-up so a
    warmed grid signature is guaranteed to match the real call. The
    solver is resolved here, at the widest rank (the narrower configs
    are padded to it).
    ``configs`` is the ConfigGrid's resolved ALSParams sequence; shared
    statics (implicit/precision/iterations/...) come from ``configs[0]``
    (the ConfigGrid constructor enforces they are uniform)."""
    import jax
    import jax.numpy as jnp

    base = configs[0]
    k = len(configs)
    r_max = max(int(c.rank) for c in configs)
    lam = np.asarray([float(c.lambda_) for c in configs], np.float32)
    alpha = np.asarray([float(c.alpha) for c in configs], np.float32)
    # 1.0 exactly on rank-padded columns, 0.0 on real ones
    ridge = (np.arange(r_max)[None, :]
             >= np.asarray([int(c.rank) for c in configs])[:, None]
             ).astype(np.float32)

    def leaf(a):
        return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype) \
            if abstract else a

    tables = _bucket_tables(user_side, item_side)
    u_t, i_t = jax.tree_util.tree_map(leaf, tables)
    if abstract:
        dt = factor_dtype(precision)
        X = jax.ShapeDtypeStruct((k, user_side.n_rows, r_max), dt)
        Y = jax.ShapeDtypeStruct((k, item_side.n_rows, r_max), dt)
        f32 = np.dtype(np.float32)
        lam = jax.ShapeDtypeStruct((k,), f32)
        alpha = jax.ShapeDtypeStruct((k,), f32)
        ridge = jax.ShapeDtypeStruct((k, r_max), f32)
    else:
        X = Y = None  # caller inits real factors
        lam, alpha = jnp.asarray(lam), jnp.asarray(alpha)
        ridge = jnp.asarray(ridge)
    args = (X, Y, lam, alpha, ridge, u_t, i_t)
    kw = dict(
        implicit=bool(base.implicit_prefs),
        num_iterations=int(base.num_iterations
                           if num_iterations is None
                           else num_iterations),
        slot_budget=None if not base.bucket_slot_budget
        else int(base.bucket_slot_budget),
        solver=_spd_solver_mode(r_max, tables), precision=precision,
        refine=bool(base.solve_refine))
    return args, kw


# ---------------------------------------------------------------------------
# Training-objective telemetry: a fused on-device reduction of the loss
# each train_als* flavor actually optimizes, evaluated once per
# checkpoint chunk against the already-resident solve tables. Pure
# observer: it reads the post-chunk factor carries (never donated), one
# scalar-pack D2H per sample, and the whole plane dies with
# PIO_TRAIN_TELEMETRY=0 (workflow/runlog.py::telemetry_enabled).
# ---------------------------------------------------------------------------


def _objective_pack_impl(X, Y, u_buckets, *, lam, alpha, implicit):
    """``[fit, l2, finite]`` float32 pack of the training objective.

    Implicit (Hu-Koren-Volinsky — what :func:`_solve_rows` minimizes):
    ``L = sum_{u,i} c_ui (p_ui - x_u.y_i)^2 + lam (|X|^2 + |Y|^2)``
    with confidence ``c = 1 + alpha|r|`` on observed pairs (1
    elsewhere) and preference ``p = 1`` iff ``r > 0``. The quadratic
    over ALL (u, i) pairs collapses through the Gram matrix —
    ``sum_u x_u^T (Y^T Y) x_u`` — plus a correction over just the
    observed entries: ``c(p-s)^2 - s^2 = bw - 2 bw s + aw s^2`` with
    ``s = x_u.y_i`` and ``(aw, bw)`` exactly :func:`implicit_weights`,
    so the objective shares the solver's weighting to the letter.

    Explicit (ALS-WR): ``L = sum_obs (r - s)^2 + lam (sum_u n_u|x_u|^2
    + sum_i n_i|y_i|^2)``; both item-side terms come off the USER-side
    tables (``sum_i n_i|y_i|^2`` equals the table-entry sum of
    ``mask * |Y[col]|^2``), so one solve side feeds the whole pack.

    Truncated tables (``max_len`` caps) contribute exactly the pairs
    the solver sees — the objective tracks what training optimizes,
    not a hypothetical untruncated loss. ``finite`` fuses the
    divergence guard (``isfinite`` over both carries) into the same
    program, so the chunk loop pays ONE D2H for guard + loss, and the
    guard stays exact even when a huge-but-finite loss overflows.
    fp32 accumulation throughout (bf16 factor stores cast up once).
    """
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    finite = (jnp.isfinite(X).all() & jnp.isfinite(Y).all()).astype(f32)
    Xf = X.astype(f32)
    Yf = Y.astype(f32)
    fit = jnp.zeros((), f32)
    l2n = jnp.zeros((), f32)  # explicit ALS-WR count-weighted norms
    if implicit:
        G = jnp.matmul(Yf.T, Yf, precision=hi)
        fit = fit + jnp.einsum("nr,rs,ns->", Xf, G, Xf, precision=hi)
    for row_ids, cols, w, m in u_buckets:
        # sentinel pad ids sit one past the end: clip (the fill-mode
        # default would turn w=0 pad slots into 0*NaN poison)
        Xb = jnp.take(Xf, row_ids, axis=0, mode="clip")   # [B, R]
        Yg = jnp.take(Yf, cols, axis=0, mode="clip")
        s = jnp.einsum("blr,br->bl", Yg, Xb, precision=hi)
        m32 = m.astype(f32)
        wm = w.astype(f32) * m32               # pads -> aw = bw = 0
        if implicit:
            aw, bw = implicit_weights(wm, alpha)
            fit = fit + jnp.sum(bw - 2.0 * bw * s + aw * s * s)
        else:
            fit = fit + jnp.sum(m32 * (wm - s) ** 2)
            l2n = l2n + jnp.sum(jnp.sum(m32, axis=1)
                                * jnp.sum(Xb * Xb, axis=1))
            l2n = l2n + jnp.einsum("bl,blr->", m32, Yg * Yg,
                                   precision=hi)
    if implicit:
        l2 = lam * (jnp.sum(Xf * Xf) + jnp.sum(Yf * Yf))
    else:
        l2 = lam * l2n
    return jnp.stack([fit, l2, finite])


def _objective_pack_grid_impl(X, Y, lam, alpha, u_buckets, *, implicit):
    """Per-config ``[k, 3]`` packs: :func:`_objective_pack_impl`
    vmapped over the stacked config axis with traced lam/alpha vectors
    and the bucket tables broadcast — the same structure as the grid
    training program (rank-padded factor columns are exact zeros, so
    they add nothing to either term)."""
    import jax

    def one(Xk, Yk, lamk, alphak):
        return _objective_pack_impl(Xk, Yk, u_buckets, lam=lamk,
                                    alpha=alphak, implicit=implicit)

    return jax.vmap(one, in_axes=(0, 0, 0, 0))(X, Y, lam, alpha)


_objective_jit = None
_objective_grid_jit = None

_AOT_OBJECTIVE_MAX = 8
_aot_objective = _AOTCache(_AOT_OBJECTIVE_MAX, name="train-objective")
_aot_objective_grid = _AOTCache(_AOT_OBJECTIVE_MAX,
                                name="train-objective-grid")


def _get_objective_jit():
    global _objective_jit
    if _objective_jit is None:
        import jax

        _objective_jit = jax.jit(
            _objective_pack_impl,
            static_argnames=("lam", "alpha", "implicit"))
    return _objective_jit


def _get_objective_grid_jit():
    global _objective_grid_jit
    if _objective_grid_jit is None:
        import jax

        _objective_grid_jit = jax.jit(
            _objective_pack_grid_impl, static_argnames=("implicit",))
    return _objective_grid_jit


def _objective_pack(*args, **kw):
    """Jitted objective (X/Y NOT donated — the pack observes carries
    the next chunk still trains from); a matching AOT executable from
    the warm-up is used when present, so the per-chunk sample keeps
    the zero-steady-state-compile contract."""
    jitted = _get_objective_jit()
    if len(_aot_objective):
        compiled = _aot_objective.get(_bucketed_aot_key(args, kw))
        if compiled is not None:
            return compiled(*args)
    return jitted(*args, **kw)


def _objective_pack_grid(*args, **kw):
    jitted = _get_objective_grid_jit()
    if len(_aot_objective_grid):
        compiled = _aot_objective_grid.get(_bucketed_aot_key(args, kw))
        if compiled is not None:
            return compiled(*args)
    return jitted(*args, **kw)


def _objective_statics(params) -> dict:
    """The objective program's static kwargs for one config — shared
    by the real per-chunk call and the AOT warm-up, so a warmed
    signature is guaranteed to match."""
    return dict(lam=float(params.lambda_), alpha=float(params.alpha),
                implicit=bool(params.implicit_prefs))


def _train_telemetry_enabled() -> bool:
    from predictionio_tpu.workflow import runlog as _runlog

    return _runlog.telemetry_enabled()


def _objective_call_args(user_side: BucketedRatings,
                         item_side: BucketedRatings, params,
                         precision: str, configs=None):
    """Abstract (args, statics) of the objective program matching the
    chunk loop's real call — lowered by the warm-up next to the
    iteration signatures. ``configs`` switches to the vmapped grid
    signature."""
    import jax

    def leaf(a):
        return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)

    u_t = tuple((leaf(b.row_ids), leaf(b.cols), leaf(b.weights),
                 leaf(b.mask)) for b in user_side.buckets)
    dt = factor_dtype(precision)
    if configs is not None:
        k = len(configs)
        r_max = max(int(c.rank) for c in configs)
        f32 = np.dtype(np.float32)
        X = jax.ShapeDtypeStruct((k, user_side.n_rows, r_max), dt)
        Y = jax.ShapeDtypeStruct((k, item_side.n_rows, r_max), dt)
        lam = jax.ShapeDtypeStruct((k,), f32)
        alpha = jax.ShapeDtypeStruct((k,), f32)
        return ((X, Y, lam, alpha, u_t),
                dict(implicit=bool(configs[0].implicit_prefs)))
    X = jax.ShapeDtypeStruct((user_side.n_rows, int(params.rank)), dt)
    Y = jax.ShapeDtypeStruct((item_side.n_rows, int(params.rank)), dt)
    return (X, Y, u_t), _objective_statics(params)


def training_objective(X, Y, user_side, params: ALSParams) -> dict:
    """One objective sample for a factor pair against the USER-side
    solve tables: ``{"fit", "l2", "total", "finite"}``.

    ``user_side`` is the :class:`BucketedRatings` whose rows align with
    ``X``. This is the public one-shot form of the fused per-chunk
    reduction the crash-safe loop samples; factors may be host numpy or
    live device arrays."""
    import jax.numpy as jnp

    u_t, = _bucket_tables(user_side)
    pack = np.asarray(_objective_pack(
        jnp.asarray(X), jnp.asarray(Y), u_t,
        **_objective_statics(params)), dtype=np.float64)
    return {"fit": float(pack[0]), "l2": float(pack[1]),
            "total": float(pack[0] + pack[1]),
            "finite": bool(pack[2] == 1.0)}


def checkpoint_layout_bucketed(user_side: BucketedRatings,
                               item_side: BucketedRatings):
    """Layout half of the checkpoint fingerprint for bucketed sides:
    row/col spaces + every bucket's padded table shape."""
    def side(s):
        return (int(s.n_rows), int(s.n_cols),
                tuple(tuple(int(d) for d in b.cols.shape)
                      for b in s.buckets))

    return ("bucketed", side(user_side), side(item_side))


def _maybe_checkpointer(layout, params: ALSParams, solver: str,
                        precision: str, dtype=None):
    """The active TrainCheckpointer for this call, or None. Gated on
    the env var BEFORE importing the checkpoint module so the
    (production-default) inactive path costs one dict lookup and never
    pulls the workflow package into a pure ops call."""
    import os

    if not os.environ.get("PIO_CHECKPOINT_DIR", "").strip():
        return None
    from predictionio_tpu.workflow import checkpoint as _checkpoint

    return _checkpoint.checkpointer_for(layout, params, solver,
                                        precision, dtype)


def _checkpoint_chunk_lengths(params: ALSParams) -> tuple:
    """The distinct static trip counts the chunked loop will dispatch
    (at most two: the chunk length and a remainder) — what the AOT
    warm-up must cover so chunked training keeps the zero-recompile
    contract. Falls back to the single scan when checkpointing is off
    or misconfigured (warm-up is best-effort by contract)."""
    import os

    total = int(params.num_iterations)
    if not os.environ.get("PIO_CHECKPOINT_DIR", "").strip():
        return (total,)
    try:
        from predictionio_tpu.workflow import checkpoint as _checkpoint

        return tuple(sorted(set(
            _checkpoint.chunk_schedule(
                total, _checkpoint.resolve_every(params)))))
    except Exception:
        return (total,)


def _bucketed_call_args(user_side: BucketedRatings,
                        item_side: BucketedRatings, params: ALSParams,
                        precision: str, abstract: bool = False,
                        num_iterations: Optional[int] = None,
                        solver: Optional[str] = None):
    """The exact (args, static kwargs) train_als_bucketed passes to the
    jitted loop — shared with the AOT warm-up so a warmed signature is
    guaranteed to match the real call. The solver is resolved here from
    ``params.rank`` and these sides' tables, unless the caller already
    holds :func:`_resolve_spd_solver`'s answer for them (``solver``).
    ``abstract=True`` replaces every
    array with its ShapeDtypeStruct. ``num_iterations`` overrides the
    params value — the chunked checkpoint loop dispatches
    chunk-length scans, and the warm-up lowers the same lengths."""
    import jax

    def leaf(a):
        return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype) \
            if abstract else a

    tables = _bucket_tables(user_side, item_side)
    u_t, i_t = jax.tree_util.tree_map(leaf, tables)
    if abstract:
        dt = factor_dtype(precision)
        X = jax.ShapeDtypeStruct((user_side.n_rows, int(params.rank)), dt)
        Y = jax.ShapeDtypeStruct((item_side.n_rows, int(params.rank)), dt)
    else:
        X = Y = None  # caller inits real factors
    args = (X, Y, u_t, i_t)
    kw = dict(
        lam=float(params.lambda_), alpha=float(params.alpha),
        implicit=bool(params.implicit_prefs),
        num_iterations=int(params.num_iterations
                           if num_iterations is None
                           else num_iterations),
        slot_budget=None if not params.bucket_slot_budget
        else int(params.bucket_slot_budget),
        solver=solver or _spd_solver_mode(params.rank, tables),
        precision=precision, refine=bool(params.solve_refine))
    return args, kw


def warmup_train_als_bucketed(user_side: BucketedRatings,
                              item_side: BucketedRatings,
                              params) -> bool:
    """AOT-compile the bucketed training program for these exact bucket
    shapes/statics so the next :func:`train_als_bucketed` call starts
    computing immediately instead of paying its jit wait. The pipelined
    ingest runs this on a background thread WHILE the bucket tables'
    H2D transfers stream — compile time hides inside the transfer
    window. Returns True once every program of the run is cached; a
    program the device compiler refuses raises with the compiler's
    message (the train call that follows would hit the same error).

    ``params`` may also be an :class:`~predictionio_tpu.ops.tuning.
    ConfigGrid` — then the VMAPPED multi-config signature is lowered
    instead, so grid training (``train_als_grid_bucketed``) keeps the
    same zero-steady-state-compile contract as serial training."""
    import os

    from predictionio_tpu.ops import aot

    def warm(cache, jitted, args, kw) -> None:
        key = _bucketed_aot_key(args, kw)
        if key not in cache:
            cache.put(key, aot.lower_compile(jitted, *args, **kw))

    configs = getattr(params, "configs", None)
    if configs is not None:
        base = configs[0]
        precision = _als_precision_mode(base)
        for n in _checkpoint_chunk_lengths(base):
            warm(_aot_grid, _get_grid_jit(),
                 *_grid_call_args(user_side, item_side, configs,
                                  precision, abstract=True,
                                  num_iterations=n))
        if _train_telemetry_enabled():
            # the per-chunk objective sample joins the ladder so the
            # telemetry plane keeps the zero-steady-state-compile
            # contract (grid samples run even without checkpointing:
            # the end-of-run divergence grading needs one)
            warm(_aot_objective_grid, _get_objective_grid_jit(),
                 *_objective_call_args(user_side, item_side, base,
                                       precision, configs=configs))
        return True

    precision = _als_precision_mode(params)
    # with checkpointing active the chunked loop dispatches
    # chunk-length scans (at most two distinct trip counts) —
    # lower each so the warmed first train stays compile-free
    # under the crash-safe lifecycle too
    for n in _checkpoint_chunk_lengths(params):
        warm(_aot_bucketed, _get_bucketed_jit(),
             *_bucketed_call_args(user_side, item_side, params,
                                  precision, abstract=True,
                                  num_iterations=n))
    if _train_telemetry_enabled() and os.environ.get(
            "PIO_CHECKPOINT_DIR", "").strip():
        # serial objective samples only run inside the chunked
        # checkpoint loop — lower the program alongside the
        # chunk-length scans it will interleave with
        warm(_aot_objective, _get_objective_jit(),
             *_objective_call_args(user_side, item_side, params,
                                   precision))
    return True


def train_als_bucketed(user_side: BucketedRatings,
                       item_side: BucketedRatings, params: ALSParams,
                       dtype=None) -> Tuple[np.ndarray, np.ndarray]:
    """Train on length-bucketed tables and return host numpy
    ``(user_factors [N, R], item_factors [M, R])``.

    The padded-slot count — and with it the MXU work — is set by each
    bucket's own length, not the global longest row. Build the sides
    with :func:`bucket_ratings`; call ``.to_device()`` on them first to
    stage the tables into HBM once when training repeatedly."""
    assert user_side.n_rows >= item_side.n_cols
    assert item_side.n_rows >= user_side.n_cols
    import jax

    from predictionio_tpu.utils import metrics as _metrics
    from predictionio_tpu.utils import tracing as _tracing

    # one local root per call (a child span inside `pio train`'s root):
    # stage / iterations / fetch, so a call's time outside the training
    # program is a span and not a subtraction
    with _tracing.trace_scope("als.train", slow_exempt=True):
        with _tracing.span("als.stage"):
            precision = _als_precision_mode(params)  # resolved per call
            X, Y = init_policy_factors(user_side.n_rows, item_side.n_rows,
                                       params.rank, params.seed, dtype,
                                       precision)
            # args/statics built by the SAME helper the AOT warm-up
            # lowers with, so a warmed executable always matches this
            # call's signature; the tables go up here (a no-op for sides
            # already staged with .to_device()) and are waited for, so
            # the upload is this span's and not the first iteration's
            u_dev, i_dev = user_side.to_device(), item_side.to_device()
            choice = _resolve_spd_solver(params.rank,
                                         _bucket_tables(u_dev, i_dev))
            (_, _, u_t, i_t), kw = _bucketed_call_args(
                u_dev, i_dev, params, precision, solver=choice.name)
            jax.block_until_ready((X, Y))
            ckpt = _maybe_checkpointer(
                checkpoint_layout_bucketed(user_side, item_side), params,
                kw["solver"], precision, dtype)
        # every padded bucket row is one system a half-step (two with
        # the refinement pass)
        systems = sum(int(t[1].shape[0]) for t in u_t + i_t) \
            * kw["num_iterations"] * (2 if kw["refine"] else 1)
        with _tracing.span("als.iterations", attributes=(
                solve_span_attributes(choice, systems, precision))):
            compile_s0 = _metrics.JIT_COMPILE_SECONDS.value()
            t0 = _tracing.span_now()
            if ckpt is None:
                X, Y = _als_iterations_bucketed(X, Y, u_t, i_t, **kw)
            else:
                X, Y = _run_checkpointed_bucketed(
                    X, Y, u_t, i_t, kw, ckpt, params, precision,
                    user_side.nnz)
            compile_s = _metrics.JIT_COMPILE_SECONDS.value() - compile_s0
            if compile_s > 0:
                # a first call: the compile pipeline (trace, lower,
                # compile or cache load) ran before the program did
                # (its phases are summed, so never past now)
                _tracing.record_completed_span(
                    "als.compile", t0,
                    min(t0 + compile_s, _tracing.span_now()))
            jax.block_until_ready((X, Y))
        with _tracing.span("als.fetch"):
            # host factors always land fp32: persistence, serving and
            # the eval stack stay byte-compatible regardless of the
            # training policy
            return (np.asarray(X, dtype=np.float32),
                    np.asarray(Y, dtype=np.float32))


def _run_checkpointed_bucketed(X, Y, u_t, i_t, kw: dict, ckpt,
                               params: ALSParams, precision: str,
                               trained_pairs: int):
    """The crash-safe lane of :func:`train_als_bucketed`: chunk-length
    scans with atomic checkpoints, preemption and the finite guard
    between them (byte-identical to the single scan —
    differential-gated)."""
    import jax.numpy as jnp

    from predictionio_tpu.workflow import checkpoint as _checkpoint
    from predictionio_tpu.workflow import runlog as _runlog

    fdt = X.dtype

    def run_iters(Xc, Yc, n):
        return _als_iterations_bucketed(
            Xc, Yc, u_t, i_t, **dict(kw, num_iterations=int(n)))

    objective = None
    if _train_telemetry_enabled():
        obj_kw = _objective_statics(params)

        def objective(Xc, Yc):
            return _objective_pack(Xc, Yc, u_t, **obj_kw)

    # the run-log header names what the platform resolved (solver,
    # precision), how many pairs train and over how many devices
    with _runlog.run_context_scope(
            solver=kw["solver"], precision=precision,
            trainedPairs=trained_pairs, devices=1):
        return _checkpoint.run_chunked(
            run_iters, X, Y, int(params.num_iterations), ckpt,
            to_host=lambda a: np.asarray(a, dtype=np.float32),
            from_host=lambda a: jnp.asarray(a, dtype=fdt),
            objective=objective)


def init_factors(n_rows: int, n_cols: int, rank: int,
                 seed: Optional[int], dtype=None) -> Tuple:
    """MLlib-style init: small random factors scaled by 1/sqrt(rank)."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    key = jax.random.PRNGKey(0 if seed is None else int(seed))
    ku, ki = jax.random.split(key)
    scale = 1.0 / np.sqrt(rank)
    X = jax.random.normal(ku, (n_rows, rank), dtype=dtype) * scale
    Y = jax.random.normal(ki, (n_cols, rank), dtype=dtype) * scale
    return X, Y


# ---------------------------------------------------------------------------
# Online fold-in (ROADMAP item 3): the normal-equations half-step reused at
# batch size 1..k against FIXED item factors, so a deployed server can solve
# fresh user rows seconds after their events arrive — no retrain, no reload.
# ---------------------------------------------------------------------------

_fold_in_jit = None


def _get_fold_in_jit():
    """Jitted batch-k fold-in solve — exactly :func:`_solve_rows` (the
    training half-step) with the item side held fixed. ``solver`` /
    ``precision`` / the scalar hyperparameters are static, so each
    (B, L, R, statics) signature compiles once and every later fold at
    the same bucketed shape reuses the executable."""
    global _fold_in_jit
    if _fold_in_jit is None:
        import jax

        def fold_in_solve(Y, cols, weights, mask, *, lam, alpha, implicit,
                          solver, precision, refine):
            with jax.named_scope("fold_in"):
                return _solve_rows(Y, cols, weights, mask, lam, alpha,
                                   implicit, None, solver, precision,
                                   refine)

        _fold_in_jit = jax.jit(
            fold_in_solve,
            static_argnames=("lam", "alpha", "implicit", "solver",
                             "precision", "refine"))
    return _fold_in_jit


def pad_fold_in_batch(cols_list: Sequence[np.ndarray],
                      vals_list: Sequence[np.ndarray],
                      row_bucket: int = 8, len_bucket: int = 8,
                      max_len: Optional[int] = None):
    """Pad k ragged per-user rating sets into one ``[B, L]`` solve table.

    Both dimensions round up the power-of-two ladder (``B`` from
    ``row_bucket``, ``L`` from ``len_bucket``) so a long-lived server's
    repeated folds hit a handful of compiled programs instead of one
    per distinct (k, longest-row) pair. Duplicate (user, item) pairs
    are summed first — the same ``reduceByKey`` aggregation training
    applies (:func:`dedup_sum_ratings`). ``max_len`` applies the SAME
    per-row truncation training applies (``_bucket_grouped``: keep the
    largest-magnitude ratings) — an engine trained with truncation must
    fold truncated, or the fold solves a different objective than the
    trained rows for exactly the long-history users the cap exists for
    (it also bounds the ``L`` bucket, so one pathological user cannot
    force a giant fresh compile inside the live server). Padding
    rows/slots carry a zero mask, so they solve to exact zero rows and
    slice off."""
    # lazy: serving imports from this module the same way
    from predictionio_tpu.ops.serving import bucket_size

    k = len(cols_list)
    # the EFFECTIVE training cap: _bucket_grouped rounds
    # max_len up to PAD_MULTIPLE and only cut rows beyond that —
    # truncating at the raw max_len here would solve a smaller problem
    # than training did for rows in the rounding gap
    cap = None if max_len is None else max(
        1, -(-int(max_len) // PAD_MULTIPLE) * PAD_MULTIPLE)
    deduped = []
    longest = 1
    for c, v in zip(cols_list, vals_list):
        c = np.asarray(c, dtype=np.int64)
        v = np.asarray(v, dtype=np.float32)
        if len(c):
            order = np.argsort(c, kind="stable")
            _, cc, vv = dedup_sum_sorted(c[order], c[order], c[order],
                                         v[order])
            if cap is not None and len(cc) > cap:
                sel = np.argsort(-np.abs(vv), kind="stable")[:cap]
                cc, vv = cc[sel], vv[sel]
            deduped.append((cc, vv))
            longest = max(longest, len(cc))
        else:
            deduped.append((c, v))
    B = bucket_size(max(k, 1), row_bucket)
    L = bucket_size(longest, len_bucket)
    cols = np.zeros((B, L), dtype=np.int32)
    weights = np.zeros((B, L), dtype=np.float32)
    mask = np.zeros((B, L), dtype=np.float32)
    for i, (c, v) in enumerate(deduped):
        m = len(c)
        cols[i, :m] = c
        weights[i, :m] = v
        mask[i, :m] = 1.0
    return cols, weights, mask


def fold_in_users(item_factors, cols_list: Sequence[np.ndarray],
                  vals_list: Sequence[np.ndarray],
                  params: ALSParams,
                  max_len: Optional[int] = None) -> np.ndarray:
    """Solve ``k`` user rows against FIXED item factors (the ALX
    normal-equations machinery at batch size 1..k — ROADMAP item 3).

    ``cols_list[i]`` / ``vals_list[i]`` are user ``i``'s FULL rating set
    (item indices + values, duplicates summed here); the returned
    ``[k, R]`` float32 rows are exactly what one training half-step
    (:func:`_solve_rows`) would produce for those users given these
    item factors — the differential contract the fold-in suite gates.

    The precision policy is the training one (``ALSParams.precision`` /
    ``PIO_ALS_PRECISION``, resolved per call): under ``bf16`` the item
    factors are gathered bfloat16 with fp32 accumulation and solve,
    matching the trainers' storage/compute split. ``item_factors``
    may be host numpy or a live device array (e.g. the serving store's
    HBM-resident ``Y``, possibly already bf16)."""
    import jax.numpy as jnp

    precision = _als_precision_mode(params)
    Y = jnp.asarray(item_factors)
    want = factor_dtype(precision)
    if Y.dtype != want:
        # cast through fp32 so a bf16 serving store folds identically
        # under an fp32 training policy (and vice versa)
        Y = Y.astype(jnp.float32).astype(want) if want != jnp.float32 \
            else Y.astype(jnp.float32)
    k = len(cols_list)
    if k == 0:
        return np.zeros((0, Y.shape[1]), dtype=np.float32)
    cols, weights, mask = pad_fold_in_batch(cols_list, vals_list,
                                            max_len=max_len)
    # a serving store's Y may live on a whole mesh: the resolver sees it
    choice = _resolve_spd_solver(Y.shape[1], (Y, cols, weights, mask))
    fold_kwargs = dict(
        lam=float(params.lambda_), alpha=float(params.alpha),
        implicit=bool(params.implicit_prefs),
        solver=choice.name, precision=precision,
        refine=bool(params.solve_refine))
    from predictionio_tpu.utils import device_telemetry as _dtel

    if not _dtel.enabled():
        # killed-lane fast path (PIO_DEVICE_TELEMETRY=0): no clocks
        out = _get_fold_in_jit()(Y, cols, weights, mask, **fold_kwargs)
    else:
        # the fold-in solve is a device dispatch like any serving
        # top-k: record its dispatch->block window in the flight ring
        # (lane "foldin"; kBucket carries the padded history length L,
        # bucket the padded user batch B) and emit the device.execute
        # span under the ambient foldin.solve span (for the profiler,
        # live annotations round the call and the block)
        from predictionio_tpu.utils import tracing as _tracing

        t0e = _tracing.span_now()
        with _tracing.annotation("dispatch.enqueue"):
            t0m = _time.monotonic()
            out = _get_fold_in_jit()(Y, cols, weights, mask, **fold_kwargs)
            t1m = _time.monotonic()
        with _tracing.annotation("dispatch.wait"):
            out.block_until_ready()
            t2m = _time.monotonic()
        rec = _dtel.record_dispatch(
            lane="foldin", kernel="xla", precision=precision,
            aot="jit", k_bucket=int(cols.shape[1]), batch=k,
            bucket=int(cols.shape[0]),
            host_us=(t2m - t0m) * 1e6, device_us=(t2m - t1m) * 1e6)
        _tracing.record_completed_span(
            "device.execute", start=t0e, end=t0e + (t2m - t0m),
            attributes=dict(
                rec or {}, **solve_span_attributes(
                    choice, int(cols.shape[0])
                    * (2 if fold_kwargs["refine"] else 1), precision)))
    return np.asarray(out[:k], dtype=np.float32)


def item_interaction_counts(item_side) -> np.ndarray:
    """Per-item interaction counts from an ITEM-side table (rows are
    items) — the density signal the ALX-style bin-pack shards by
    (``parallel.als_sharding.density_aware_item_layout``). Sentinel pad
    rows contribute nothing."""
    counts = np.zeros(item_side.n_rows, dtype=np.int64)
    for b in item_side.buckets:
        ids = np.asarray(b.row_ids, dtype=np.int64)
        # reduce BEFORE np.asarray: device-staged tables (the 1B
        # lane) transfer one [rows] vector, not the padded mask
        per_row = np.asarray(
            b.mask.sum(axis=1)).astype(np.int64)
        real = ids < item_side.n_rows
        np.add.at(counts, ids[real], per_row[real])
    return counts


# ---------------------------------------------------------------------------
# Scoring / prediction helpers
# ---------------------------------------------------------------------------

def top_k_items(scores: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side top-k (indices, scores) descending."""
    k = min(k, scores.shape[-1])
    idx = np.argpartition(-scores, k - 1, axis=-1)[..., :k]
    top = np.take_along_axis(scores, idx, axis=-1)
    order = np.argsort(-top, axis=-1)
    return np.take_along_axis(idx, order, axis=-1), \
        np.take_along_axis(top, order, axis=-1)


def cosine_scores(query_features: np.ndarray,
                  item_factors: np.ndarray) -> np.ndarray:
    """Summed cosine similarity of each item against every query feature
    row — the template's predict scoring (custom-query
    ALSAlgorithm.scala:77-103, cosine at :121-135)."""
    q = np.atleast_2d(query_features)
    qn = q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
    inorm = np.maximum(np.linalg.norm(item_factors, axis=1, keepdims=True),
                       1e-12)
    yn = item_factors / inorm
    return (yn @ qn.T).sum(axis=1)


def predict_scores_for_user(user_factor: np.ndarray,
                            item_factors: np.ndarray) -> np.ndarray:
    """Dot-product recommendation scores for one user (MLlib
    recommendProducts semantics)."""
    return item_factors @ user_factor
