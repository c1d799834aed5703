"""Plain-numpy ALS over (row, col, value) triples: the reference the
trainers in ``ops/als.py`` are held to. It shares nothing with them but
the seeded start (``init_factors``): no padding, no buckets, float64,
one ``np.linalg.solve`` a row."""

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
