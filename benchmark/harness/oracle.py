"""The plain references that decide ``correct``.

Copied, not imported: the benchmark's verdict must not move with the
program. Sources: ``chip_smoke.py`` (the numpy float32 top-N oracle and
its bf16 tolerance) and ``bench_quality.py`` (the numpy ALS trainer).
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence, Tuple

import numpy as np

# The serving configurations state a bf16 factor store with fp32
# accumulation. The oracle therefore rounds the seeded tables to bf16
# first (round to nearest even, as the store's cast does) and then
# computes in plain numpy float32: what is left between it and the
# program is the order of a 64-term fp32 sum, ~1e-6 relative. The seeded
# tables are i.i.d. normal, so the 10th and 11th best of 41,140 scores
# often lie within 1e-3 of each other, and "k of 10 shared" would turn
# on such ties; the check is instead that every returned item DESERVES
# its place (its oracle score is within the tolerance of the oracle's
# n-th best) and carries the oracle's score. 2e-3 of the top score is
# 1000x the fp32 summation error, and an int8 store (per-row absmax:
# about three times bf16's rounding) fails it for 197 of 200 users at
# this shape (test_oracle.py); tables of HIGHER precision than stated
# differ by about 1e-3 and mostly pass, which is no fault.
SCORE_RTOL = 2e-3
# item-similarity queries: the program normalizes the bf16 rows and
# keeps the normalized table in the store's precision again, a second
# rounding this oracle does not repeat; the smoke's bf16-against-fp32
# tolerance (2e-2) covers it.
SIMILAR_RTOL = 2e-2
# fp32 gather + assembly at Precision.HIGHEST + the fp32 `lanes` SPD
# solve of a rank-64 system whose condition number reaches ~1e4 here:
# eps(fp32) x condition = 6e-8 x 1e4 ~ 6e-4, and the first two chip runs
# read 2e-5 and 9.6e-4. bf16 anywhere in the half-step (eps 4e-3 x the
# same condition) lands at order 1. 1e-2 leaves a decade on each side.
HALF_STEP_RTOL = 1e-2


def bf16_round(a: np.ndarray) -> np.ndarray:
    """float32 -> nearest-even bfloat16 -> float32, in numpy."""
    u = np.ascontiguousarray(a, dtype=np.float32).view(np.uint32)
    bias = ((u >> 16) & 1) + np.uint32(0x7FFF)
    return ((u + bias) & np.uint32(0xFFFF0000)).view(np.float32)


def scores_single(user_vec: np.ndarray, item_factors: np.ndarray,
                  seen: np.ndarray) -> np.ndarray:
    """Plain numpy float32: scores = Y @ x, seen masked."""
    scores = item_factors @ user_vec.astype(np.float32)
    scores[seen] = -np.inf
    return scores


def scores_two_stage(user_vec, item_factors, user_vec2, item_factors2,
                     seen: np.ndarray, candidates: int, tol: float
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Brute force two-stage scores, with the candidate cut taken both
    ways round a tie: ``(allowed, certain)``. ``allowed`` holds the
    stage-2 score of every unseen item whose stage-1 score (unmasked,
    as the program retrieves) is within ``tol`` UNDER the
    ``candidates``-th best, -inf elsewhere: what may be returned.
    ``certain`` keeps only items at least ``tol`` OVER the cut: what
    the program holds whichever way it broke ties, so the n-th best of
    it is a floor under every returned score."""
    s1 = item_factors @ user_vec.astype(np.float32)
    cut = np.partition(s1, -candidates)[-candidates]
    s2 = item_factors2 @ user_vec2.astype(np.float32)
    s2[seen] = -np.inf
    allowed, certain = s2.copy(), s2.copy()
    allowed[s1 < cut - tol * abs(cut)] = -np.inf
    certain[s1 < cut + tol * abs(cut)] = -np.inf
    return allowed, certain


def scores_similar(query_idx: Sequence[int], item_factors: np.ndarray
                   ) -> np.ndarray:
    """Cosine to the summed normalized query rows, query items out."""
    Y = item_factors.astype(np.float32)
    Yn = Y / np.maximum(np.linalg.norm(Y, axis=1, keepdims=True), 1e-12)
    q = np.asarray(query_idx, dtype=np.int64)
    scores = Yn @ Yn[q].sum(axis=0)
    scores[q] = -np.inf
    return scores


def check_answer(got: Sequence[Mapping[str, Any]], want: np.ndarray,
                 n: int, rtol: float,
                 certain: Optional[np.ndarray] = None) -> Optional[str]:
    """None when the served list agrees with the oracle's score vector
    ``want`` (indexed by item, -inf where an item may not be returned),
    else why not. ``certain`` (default ``want``) is the score vector
    whose n-th best every returned score must reach, within the
    tolerance. Items are named ``i<index>``."""
    try:
        idx = np.asarray([int(g["item"][1:]) for g in got], dtype=np.int64)
        scores = np.asarray([g["score"] for g in got], dtype=np.float64)
    except (KeyError, TypeError, ValueError):
        return f"malformed answer {got!r}"
    if len(idx) != n or len(set(idx.tolist())) != n:
        return f"expected {n} distinct items, got {len(idx)}"
    if not (np.isfinite(scores).all() and (np.diff(scores) <= 1e-6).all()):
        return f"scores not finite descending: {scores.tolist()}"
    theirs = want[idx]
    if not np.isfinite(theirs).all():
        return ("seen, blacklisted, query or non-candidate items "
                f"returned: {idx[~np.isfinite(theirs)].tolist()}")
    finite = want[np.isfinite(want)]
    sure = finite if certain is None else certain[np.isfinite(certain)]
    if len(sure) < n:
        return f"the oracle has only {len(sure)} certain candidates"
    nth = np.partition(sure, -n)[-n]
    tol = rtol * float(np.abs(finite).max())
    if (theirs < nth - tol).any():
        worst = float((nth - theirs).max())
        return (f"an item {worst:.3g} under the oracle's {n}-th best "
                f"score was returned (tolerance {tol:.3g})")
    if (np.abs(scores - theirs) > tol).any():
        worst = float(np.abs(scores - theirs).max())
        return f"scores differ from the oracle by {worst:.3g} (> {tol:.3g})"
    return None


def well_formed(body: Any, num: int) -> bool:
    """Status-and-shape check of an in-window answer: a list of at most
    ``num`` ``{item, score}`` records. Empty is NOT well formed: every
    query the generator sends names a known user or item."""
    if not isinstance(body, dict):
        return False
    scores = body.get("itemScores")
    if not isinstance(scores, list) or not 0 < len(scores) <= num:
        return False
    return all(isinstance(s, dict) and isinstance(s.get("item"), str)
               and isinstance(s.get("score"), (int, float))
               for s in scores)


# -- training --------------------------------------------------------------

def half_step_items(item_ids: np.ndarray, rows: np.ndarray,
                    cols: np.ndarray, vals: np.ndarray,
                    user_factors: np.ndarray, lam: float, alpha: float
                    ) -> np.ndarray:
    """The last half-step of implicit ALS re-solved in float64 for
    ``item_ids``: normal equations from the final user factors and
    those items' ratings (duplicate events summed, as the trainer's
    tables hold them), Hu-Koren-Volinsky weights as ``ops/als.py``
    defines them (A += alpha*|r| x x^T; b += [r>0]*(1+alpha*|r|) x)."""
    X = user_factors.astype(np.float64)
    R = X.shape[1]
    gram = X.T @ X + lam * np.eye(R)
    pick = np.isin(cols, item_ids)
    r_, c_, v_ = rows[pick], cols[pick], vals[pick].astype(np.float64)
    out = np.zeros((len(item_ids), R))
    for j, item in enumerate(item_ids.tolist()):
        m = c_ == item
        users, inv = np.unique(r_[m], return_inverse=True)
        if not len(users):
            continue
        w = np.bincount(inv, weights=v_[m])
        aw = alpha * np.abs(w)
        bw = (w > 0) * (1.0 + aw)
        Xu = X[users]
        A = gram + (Xu * aw[:, None]).T @ Xu
        out[j] = np.linalg.solve(A, Xu.T @ bw)
    return out


def _numpy_solve_side(Y, cols, weights, mask, lam: float, alpha: float):
    R = Y.shape[1]
    w = weights * mask
    aw = alpha * np.abs(w)
    bw = (w > 0).astype(np.float32) * (1.0 + aw)
    Yg = Y[cols]
    gram = Y.T @ Y
    corr = np.einsum("bl,blr,bls->brs", aw, Yg, Yg, optimize=True)
    A = gram[None] + corr + lam * np.eye(R, dtype=np.float32)[None]
    b = np.einsum("bl,blr->br", bw, Yg, optimize=True)
    X = np.linalg.solve(A, b[..., None])[..., 0].astype(np.float32)
    return X * (mask.sum(axis=1) > 0).astype(np.float32)[:, None]


def padded_side(rows, cols, vals, n_rows: int):
    """Dense ``[n_rows, longest]`` cols / weights / mask with duplicate
    events summed: the toy-size layout the numpy trainer walks."""
    key = rows.astype(np.int64) * (int(cols.max()) + 1) + cols
    uniq, inv = np.unique(key, return_inverse=True)
    w = np.bincount(inv, weights=vals).astype(np.float32)
    r = (uniq // (int(cols.max()) + 1)).astype(np.int64)
    c = (uniq % (int(cols.max()) + 1)).astype(np.int64)
    counts = np.bincount(r, minlength=n_rows)
    L = max(1, int(counts.max()))
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(len(r)) - starts[r]
    C = np.zeros((n_rows, L), dtype=np.int64)
    W = np.zeros((n_rows, L), dtype=np.float32)
    M = np.zeros((n_rows, L), dtype=np.float32)
    C[r, slot], W[r, slot], M[r, slot] = c, w, 1.0
    return C, W, M


def train_als_numpy(rows, cols, vals, n_users: int, n_items: int,
                    X0: np.ndarray, Y0: np.ndarray, iterations: int,
                    lam: float, alpha: float
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Full implicit-ALS trajectory in numpy from the given init: the
    toy-size reference of the rehearsal (at 20M events it cannot run
    inside a set-up; the half-step check stands in there)."""
    u = padded_side(rows, cols, vals, n_users)
    i = padded_side(cols, rows, vals, n_items)
    X, Y = np.asarray(X0, np.float32), np.asarray(Y0, np.float32)
    for _ in range(iterations):
        X = _numpy_solve_side(Y, *u, lam, alpha)
        Y = _numpy_solve_side(X, *i, lam, alpha)
    return X, Y
