"""Plain-numpy ALS over (row, col, value) triples: the reference the
trainers in ``ops/als.py`` are held to. It shares nothing with them but
the seeded start (``init_factors``): no padding, no buckets, float64,
one ``np.linalg.solve`` a row. And the recurrence the Pallas SPD kernel
is held to, to the bit (:func:`spd_solve_whole_block`)."""

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops.als import (
    ALSParams,
    bucket_ratings_pair,
    init_factors,
    train_als_bucketed,
)


def sum_duplicates(rows, cols, vals):
    """Unique (row, col) pairs with their values summed (the template's
    ``reduceByKey(_ + _)``)."""
    agg = {}
    for r, c, v in zip(rows, cols, vals):
        agg[(int(r), int(c))] = agg.get((int(r), int(c)), 0.0) + float(v)
    keys = np.asarray(sorted(agg), dtype=np.int64).reshape(-1, 2)
    return keys[:, 0], keys[:, 1], \
        np.asarray([agg[tuple(k)] for k in keys], dtype=np.float64)


def numpy_half_step(Y, rows, cols, vals, n_rows, lam, alpha,
                    implicit=True):
    """One half-step from unique triples: per-row dense normal
    equations. Implicit (Hu-Koren-Volinsky as MLlib reads it):
    confidence from ``|r|``, preference ``r > 0``; explicit: ALS-WR's
    ``lambda * n_row`` ridge. A row with no rating keeps zeros."""
    Y = np.asarray(Y, dtype=np.float64)
    R = Y.shape[1]
    gram = Y.T @ Y
    X = np.zeros((n_rows, R), dtype=np.float64)
    for u in np.unique(rows):
        sel = rows == u
        y = Y[cols[sel]]                      # [nnz, R]
        r = np.asarray(vals[sel], dtype=np.float64)
        if implicit:
            aw = alpha * np.abs(r)
            A = gram + (y.T * aw) @ y + lam * np.eye(R)
            b = (((r > 0) * (1.0 + aw))[:, None] * y).sum(axis=0)
        else:
            A = y.T @ y + lam * len(r) * np.eye(R)
            b = (r[:, None] * y).sum(axis=0)
        X[u] = np.linalg.solve(A, b)
    return X


def numpy_train_als(rows, cols, vals, n_users, n_items,
                    params: ALSParams):
    """``params.num_iterations`` alternating half-steps from the
    trainers' own seeded start."""
    rows, cols, vals = sum_duplicates(rows, cols, vals)
    X, Y = (np.asarray(a, dtype=np.float64) for a in init_factors(
        n_users, n_items, params.rank, params.seed))
    kw = dict(lam=params.lambda_, alpha=params.alpha,
              implicit=params.implicit_prefs)
    for _ in range(params.num_iterations):
        X = numpy_half_step(Y, rows, cols, vals, n_users, **kw)
        Y = numpy_half_step(X, cols, rows, vals, n_items, **kw)
    return X, Y


def train_from_triples(rows, cols, vals, n_users, n_items,
                       params: ALSParams):
    """The one-device trainer on both sides of the same triples."""
    return train_als_bucketed(
        *bucket_ratings_pair(rows, cols, vals, n_users, n_items), params)


@jax.jit
def spd_solve_whole_block(A, b):
    """``x: A @ x = b`` for ``A [B, R, R]``, ``b [B, R]`` by the
    recurrence ``als_pallas.spd_solve`` ran until PR 49, in plain
    ``jax.numpy``: batch-minor, float32, every one of the R steps
    subtracting ``u u^T`` from the WHOLE ``[R, R]`` block. The kernel
    updates only what a later step reads; each entry it reads has seen
    these operations in this order, so its ``x`` equals this one to the
    bit, for a symmetric ``A`` or not (nothing below the diagonal is
    read, here or there)."""
    R = b.shape[1]
    a = jnp.transpose(A.astype(jnp.float32), (1, 2, 0))    # [R, R, B]
    rhs = b.astype(jnp.float32).T                          # [R, B]
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)

    def fact_step(k, carry):
        a, lt = carry
        d = jnp.maximum(a[k, k], 1e-30)
        inv = 1.0 / jnp.sqrt(d)
        lcol = a[k] * inv[None, :] * (iota_r >= k).astype(jnp.float32)
        u = lcol * (iota_r > k).astype(jnp.float32)
        return a - u[None, :, :] * u[:, None, :], lt.at[k].set(lcol)

    _, lt = jax.lax.fori_loop(0, R, fact_step, (a, jnp.zeros_like(a)))

    def fwd_step(k, carry):
        rhs, y = carry
        yk = rhs[k] / lt[k, k]
        return rhs - lt[k] * yk[None, :], y.at[k].set(yk)

    _, y = jax.lax.fori_loop(0, R, fwd_step, (rhs, jnp.zeros_like(rhs)))

    def bwd_step(i, x):
        k = R - 1 - i
        s = jnp.sum(lt[k] * x, axis=0)                     # x[k] still 0
        return x.at[k].set((y[k] - s) / lt[k, k])

    return jax.lax.fori_loop(0, R, bwd_step, jnp.zeros_like(rhs)).T
