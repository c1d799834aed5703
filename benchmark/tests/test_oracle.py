"""The comparison that decides ``correct``: it accepts what the stated
precision produces, near-ties included, and refuses a wrong mask, a
wrong item, a wrong score and a lower precision than stated."""

import numpy as np
import pytest

from benchmark.harness import oracle


def _answer(idx, scores):
    return [{"item": f"i{i}", "score": float(s)} for i, s in zip(idx, scores)]


@pytest.fixture(scope="module")
def tables():
    rng = np.random.default_rng(0)
    X = (rng.normal(size=(50, 64)) / 8).astype(np.float32)
    Y = (rng.normal(size=(5000, 64)) / 8).astype(np.float32)
    return oracle.bf16_round(X), oracle.bf16_round(Y), X, Y


def test_bf16_round_is_nearest_even_on_the_grid():
    a = np.asarray([1.0, 1.0 + 2 ** -9, 1.0 + 3 * 2 ** -9, -0.3, 0.0],
                   dtype=np.float32)
    r = oracle.bf16_round(a)
    assert r[0] == 1.0 and r[1] == 1.0          # tie -> even mantissa
    assert r[2] == np.float32(1.0 + 2 ** -7)    # tie -> even, upwards
    assert (r.view(np.uint32) & 0xFFFF == 0).all()
    assert abs(r[3] + 0.3) < 0.3 * 2 ** -8


def test_exact_answer_and_near_tie_swap_pass(tables):
    Xb, Yb, _, _ = tables
    seen = np.asarray([3, 4, 5])
    want = oracle.scores_single(Xb[0], Yb, seen)
    top = np.argsort(-want, kind="stable")[:10]
    assert oracle.check_answer(_answer(top, want[top]), want, 10,
                               oracle.SCORE_RTOL) is None
    # the 11th best within the tolerance of the 10th may take its place
    order = np.argsort(-want, kind="stable")
    want2 = want.copy()
    want2[order[10]] = want2[order[9]] - 1e-5
    swapped = np.concatenate([order[:9], order[10:11]])
    assert oracle.check_answer(_answer(swapped, want2[swapped]), want2, 10,
                               oracle.SCORE_RTOL) is None


def test_wrong_answers_fail(tables):
    Xb, Yb, X, Y = tables
    seen = np.asarray([3, 4, 5])
    want = oracle.scores_single(Xb[0], Yb, seen)
    order = np.argsort(-want, kind="stable")
    top = order[:10]
    ok = _answer(top, want[top])
    assert "distinct" in oracle.check_answer(ok[:9], want, 10, 2e-3)
    # a seen item returned (the mask is missing)
    unmasked = Yb @ Xb[0]
    bad = np.concatenate([[3], top[:9]])
    assert "seen" in oracle.check_answer(
        _answer(bad, np.sort(unmasked[bad])[::-1]), want, 10, 2e-3)
    # an item far down the ranking
    low = np.concatenate([top[:9], order[2000:2001]])
    assert "under the oracle" in oracle.check_answer(
        _answer(low, want[low]), want, 10, 2e-3)
    # right items, scores off by 1%
    assert "scores differ" in oracle.check_answer(
        _answer(top, want[top] * 1.01), want, 10, 2e-3)
    # an int8 store (per-row absmax) where bf16 is stated is caught
    from benchmark.stores import int8

    X8, Y8 = int8.round_table(X), int8.round_table(Y)
    fails = 0
    for u in range(20):
        w = oracle.scores_single(Xb[u], Yb, seen)
        s8 = oracle.scores_single(X8[u], Y8, seen)
        t = np.argsort(-s8, kind="stable")[:10]
        fails += oracle.check_answer(_answer(t, s8[t]), w, 10,
                                     oracle.SCORE_RTOL) is not None
    assert fails >= 15


def test_two_stage_candidates_mask_and_cut_ties(tables):
    Xb, Yb, _, _ = tables
    rng = np.random.default_rng(1)
    X2 = oracle.bf16_round((rng.normal(size=(50, 64)) / 8).astype(np.float32))
    Y2 = oracle.bf16_round(
        (rng.normal(size=(5000, 64)) / 8).astype(np.float32))
    s1 = Yb @ Xb[1]
    cand = np.argsort(-s1, kind="stable")[:128]
    seen = cand[:3]
    want, certain = oracle.scores_two_stage(Xb[1], Yb, X2[1], Y2, seen,
                                            128, 2e-3)
    assert np.isfinite(certain).sum() <= 125 <= np.isfinite(want).sum() + 3
    assert not np.isfinite(want[seen]).any()
    s2 = Y2[cand] @ X2[1]
    s2[:3] = -np.inf
    best = np.argsort(-s2, kind="stable")[:10]
    assert oracle.check_answer(_answer(cand[best], s2[best]), want, 10,
                               2e-3, certain) is None
    # an item a hair under the stage-1 cut whose stage-2 score is high:
    # a program that broke the tie the other way never saw it, and its
    # honest top-10 must still pass
    full2 = Y2 @ X2[1]
    order1 = np.argsort(-s1, kind="stable")
    cut = s1[order1[127]]
    Yb2 = Yb.copy()
    extra = order1[128]
    Yb2[extra] = Yb[order1[127]]       # same stage-1 score as the cut
    Y2b = Y2.copy()
    Y2b[extra] = X2[1] * 10            # a very high stage-2 score
    w2, c2 = oracle.scores_two_stage(Xb[1], Yb2, X2[1], Y2b, seen, 128,
                                     2e-3)
    assert np.isfinite(w2[extra]) and not np.isfinite(c2[extra])
    assert oracle.check_answer(_answer(cand[best], s2[best]), w2, 10,
                               2e-3, c2) is None
    # the brute-force top-10 of the WHOLE catalog is not the answer
    t = np.argsort(-full2, kind="stable")[:10]
    assert oracle.check_answer(_answer(t, full2[t]), want, 10, 2e-3,
                               certain)


def test_half_step_matches_a_direct_solve():
    rng = np.random.default_rng(2)
    n_u, n_i, R = 40, 12, 4
    X = rng.normal(size=(n_u, R))
    rows = rng.integers(0, n_u, 300)
    cols = rng.integers(0, n_i, 300)
    vals = rng.integers(1, 6, 300).astype(np.float32)
    got = oracle.half_step_items(np.arange(n_i), rows, cols, vals, X,
                                 0.01, 1.0)
    for j in range(n_i):
        A = X.T @ X + 0.01 * np.eye(R)
        b = np.zeros(R)
        w = {}
        for r, c, v in zip(rows, cols, vals):
            if c == j:
                w[r] = w.get(r, 0.0) + float(v)
        for r, v in w.items():
            A += v * np.outer(X[r], X[r])
            b += (1 + v) * X[r]
        assert np.allclose(got[j], np.linalg.solve(A, b), rtol=1e-9)


def test_well_formed():
    assert oracle.well_formed({"itemScores": [{"item": "i1", "score": 1}]}, 3)
    assert not oracle.well_formed({"itemScores": []}, 3)
    assert not oracle.well_formed({"itemScores": [{"item": 1}]}, 3)
    assert not oracle.well_formed({"message": "x"}, 3)
