"""The two CPU quality gates of the precision and two-stage lanes.

A correctness check, not a speed: the slow-marked ``TestQualityGate``
classes of ``test_als_precision.py`` and ``test_twostage.py`` train on
a small structured split and compare ranking quality between lanes.

Protocol (leave-last-out, the recommendation template's ``read_eval``
shape):
- synthetic MovieLens-100K-shaped ratings with latent co-preference
  structure (power-law user/item activity);
- per user with >= 5 distinct items, the 2 last-drawn items are held out;
- train on the rest; predict top-10 unseen items; Precision@10 =
  |top10 ∩ held| / 10 averaged over users with holdouts (users without
  holdouts are skipped, matching OptionAverageMetric's None semantics).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

# the MovieLens-100K shape
N_USERS, N_ITEMS, NNZ = 943, 1682, 100_000
RANK = 32
ITERATIONS = 10
LAMBDA = 0.01
ALPHA = 1.0
K = 10


def structured_ratings(n_users: int, n_items: int, nnz: int, seed: int,
                       latent_rank: int = 8):
    """MovieLens-like synthetic ratings WITH latent co-preference
    structure: each user's item choices are drawn from
    softmax(U_u . V_i + log popularity), so taste clusters exist for a
    factor model to recover. (A generator that draws user and item
    independently leaves popularity Bayes-optimal: NO recommender can
    beat the popularity floor on it, which is why a quality check
    needs this one.)"""
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(n_users, latent_rank)) / np.sqrt(latent_rank)
    V = rng.normal(size=(n_items, latent_rank)) / np.sqrt(latent_rank)
    log_pop = -0.5 * np.log(np.arange(1, n_items + 1))
    user_p = 1.0 / np.arange(1, n_users + 1) ** 0.6
    user_p /= user_p.sum()
    counts = np.bincount(rng.choice(n_users, size=nnz, p=user_p),
                         minlength=n_users)
    rows = np.empty(nnz, dtype=np.int64)
    cols = np.empty(nnz, dtype=np.int64)
    vals = np.empty(nnz, dtype=np.float32)
    pos = 0
    # taste scale 6 vs popularity exponent 0.5: ALS recovers ~4-5x the
    # popularity baseline's Precision@10 here, a MovieLens-like regime
    affinity_all = U @ V.T * 6.0 + log_pop[None, :]   # [N, M] logits
    for u in range(n_users):
        c = int(counts[u])
        if c == 0:
            continue
        logits = affinity_all[u]
        p = np.exp(logits - logits.max())
        p /= p.sum()
        picked = rng.choice(n_items, size=c, p=p)
        rows[pos:pos + c] = u
        cols[pos:pos + c] = picked
        # rating tracks affinity: top-quintile affinity -> 5, etc.
        aff = affinity_all[u][picked]
        qs = np.quantile(affinity_all[u], [0.2, 0.4, 0.6, 0.8])
        vals[pos:pos + c] = 1.0 + np.searchsorted(qs, aff)
        pos += c
    return rows[:pos], cols[:pos], vals[:pos]


def build_split(n_users: int, n_items: int, nnz: int, seed: int,
                holdout_per_user: int = 2, min_ratings: int = 5):
    """Dedup (user, item) pairs, hold out the last-drawn items per
    qualifying user. Returns (train_rows, train_cols, train_vals, held)
    with ``held: user -> set(item)`` disjoint from the train pairs."""
    rows, cols, vals = structured_ratings(n_users, n_items, nnz, seed)
    # dedup keeping the first occurrence (draw order)
    key = rows.astype(np.int64) * n_items + cols
    _, first_idx = np.unique(key, return_index=True)
    first_idx.sort()
    rows, cols, vals = rows[first_idx], cols[first_idx], vals[first_idx]

    held: Dict[int, set] = {}
    held_mask = np.zeros(len(rows), dtype=bool)
    for u in range(n_users):
        idx = np.flatnonzero(rows == u)
        if len(idx) >= min_ratings:
            out = idx[-holdout_per_user:]
            held[u] = set(cols[out].tolist())
            held_mask[out] = True
    keep = ~held_mask
    return rows[keep], cols[keep], vals[keep], held


def precision_at_k(user_factors: np.ndarray, item_factors: np.ndarray,
                   train_rows: np.ndarray, train_cols: np.ndarray,
                   held: Dict[int, set], k: int = K) -> float:
    """Mean over holdout users of |top-k unseen| ∩ held| / k — the
    template's PrecisionAtK on the model's own top-N serving logic."""
    if not held:
        raise ValueError(
            "no holdout users — the (n_users, n_items, nnz) shape is too "
            "sparse for the leave-last-out protocol (need >=5 distinct "
            "items per user)")
    scores = np.asarray(user_factors) @ np.asarray(item_factors).T
    scores[train_rows, train_cols] = -np.inf  # never recommend seen items
    users = np.fromiter(held.keys(), dtype=np.int64, count=len(held))
    top = np.argpartition(-scores[users], k, axis=1)[:, :k]
    hits = np.fromiter(
        (len(set(top[i].tolist()) & held[u]) for i, u in enumerate(users)),
        dtype=np.float64, count=len(users))
    return float(hits.mean() / k)


def run_precision_check(n_users: int = N_USERS, n_items: int = N_ITEMS,
                        nnz: int = NNZ, seed: int = 7,
                        iterations: int = ITERATIONS) -> dict:
    """Quality gate for the precision policies (ops/als.py
    ``ALSParams.precision`` + the ops/serving.py int8 store): train the
    SAME ml100k-shaped leave-last-out split under fp32 and bf16 from
    the same seed and report both Precision@10, then score the fp32
    factors through the int8 SERVING transform (symmetric per-row
    absmax quantize -> dequantize — exactly what ``DeviceTopK`` holds
    under ``PIO_SERVE_PRECISION=int8``; int8 is storage-only, so the
    serving-side round-trip IS its quality exposure). The slow-marked
    test in tests/test_als_precision.py asserts both drops stay within
    0.02 absolute — the hard gate each lane ships behind."""
    import dataclasses as _dc

    from predictionio_tpu.ops.als import (
        ALSParams,
        bucket_ratings_pair,
        train_als_bucketed,
    )
    from predictionio_tpu.ops.quantize import (
        dequantize_rows_np,
        quantize_rows_int8_np,
    )

    rows, cols, vals, held = build_split(n_users, n_items, nnz, seed)
    user_side, item_side = bucket_ratings_pair(rows, cols, vals, n_users,
                                               n_items)
    params = ALSParams(rank=RANK, num_iterations=iterations,
                       lambda_=LAMBDA, alpha=ALPHA, implicit_prefs=True,
                       seed=3)

    X32, Y32 = train_als_bucketed(user_side, item_side, params)
    p32 = precision_at_k(X32, Y32, rows, cols, held)
    X16, Y16 = train_als_bucketed(user_side, item_side,
                         _dc.replace(params, precision="bf16"))
    p16 = precision_at_k(X16, Y16, rows, cols, held)
    X8 = dequantize_rows_np(quantize_rows_int8_np(np.asarray(X32)))
    Y8 = dequantize_rows_np(quantize_rows_int8_np(np.asarray(Y32)))
    p8 = precision_at_k(X8, Y8, rows, cols, held)
    return {
        "check": "precision_policy_quality_gate",
        "fp32_precision_at_10": round(p32, 4),
        "bf16_precision_at_10": round(p16, 4),
        "bf16_drop_abs": round(p32 - p16, 4),
        "int8_serving_precision_at_10": round(p8, 4),
        "int8_serving_drop_abs": round(p32 - p8, 4),
        "gate_max_drop_abs": 0.02,
        "holdout_users": len(held),
        "rank": RANK, "iterations": iterations,
        "protocol": "leave-last-2-out per user>=5, top-10 unseen",
    }


def run_twostage_check(n_users: int = 200, n_items: int = 100,
                       min_len: int = 4, max_len: int = 24,
                       num_steps: int = 400, rank_retrieval: int = 32,
                       rank_rerank: int = 32, candidates: int = None,
                       seed: int = 11, k: int = K) -> dict:
    """Quality gate for fused two-stage serving (ISSUE 20 acceptance):
    on the seqrec gate's Markov chain stream, the two-stage combination
    (ALS retrieval -> seqrec re-rank through the REAL
    :class:`~predictionio_tpu.ops.twostage.TwoStageTopK` device store)
    must reach NDCG@10 >= max(ALS alone, seqrec alone).

    Why this holds and what it proves: ALS sees only the SET of items
    per user (the marginal item distribution of the stride walks is
    near uniform, so ALS retrieval is weak on its own but its top-N
    still covers the catalog well at N >= |catalog|/2); seqrec learns
    the transition structure. Re-ranking the retrieval candidates by
    the sequence model recovers (at full recall, equals) the sequence
    model's ranking — fusing the two stages into one device program
    must not cost quality. The default candidate budget is the FULL
    catalog, where stage 1 has recall 1.0 and the fused program is
    bit-exact to brute-force re-ranking (tests/test_twostage.py), so
    the gate is deterministic; ``als_recall_at_half_catalog`` reports
    how much of that recall a halved budget would keep. The two-stage
    list itself comes from ``TwoStageTopK.twos_topk`` so the gate
    exercises the served kernel, not a host reimplementation."""
    from predictionio_tpu.data.sliding import ndcg_at_k
    from predictionio_tpu.ops.als import (
        ALSParams,
        bucket_ratings_pair,
        train_als_bucketed,
    )
    from predictionio_tpu.ops.seqrec import (
        SeqRecParams,
        bucket_sequences,
        encode_users,
        train_seqrec,
    )
    from predictionio_tpu.ops.twostage import TwoStageTopK

    if candidates is None:
        candidates = n_items

    rng = np.random.default_rng(seed)
    strides = (1, 3, 7)
    seqs, next_item = [], []
    for _ in range(n_users):
        start = int(rng.integers(0, n_items))
        stride = int(strides[rng.integers(0, len(strides))])
        n = int(rng.integers(min_len, max_len))
        walk = (start + stride * np.arange(n + 1)) % n_items
        seqs.append(walk[:-1].astype(np.int64))
        next_item.append(int(walk[-1]))
    seen = {u: np.unique(seqs[u]) for u in range(n_users)}

    # --- stage-1 model: implicit ALS on the walks' (user, item) set
    rows = np.concatenate([np.full(len(s), u, dtype=np.int64)
                           for u, s in enumerate(seqs)])
    cols = np.concatenate(seqs)
    key = rows * n_items + cols
    uniq = np.unique(key)
    rows, cols = uniq // n_items, uniq % n_items
    vals = np.ones(len(rows), dtype=np.float32)
    als_params = ALSParams(rank=rank_retrieval, num_iterations=ITERATIONS,
                           lambda_=LAMBDA, alpha=ALPHA,
                           implicit_prefs=True, seed=3)
    X_als, Y_als = train_als_bucketed(
        *bucket_ratings_pair(rows, cols, vals, n_users, n_items),
        als_params)
    X_als, Y_als = np.asarray(X_als), np.asarray(Y_als)

    # --- stage-2 model: seqrec on the same walks
    seq_params = SeqRecParams(rank=rank_rerank, n_layers=2, n_heads=2,
                              max_seq_len=max_len, num_steps=num_steps,
                              batch_size=64, n_negatives=64,
                              learning_rate=0.005, seed=seed)
    buckets = bucket_sequences(seqs, max_len=max_len)
    theta, _ = train_seqrec(buckets, n_items, seq_params)
    U_seq = np.asarray(encode_users(theta, buckets, n_users, seq_params))
    E_seq = np.asarray(theta["item_emb"])

    def _single_stage_ndcg(U, E):
        total = 0.0
        for u in range(n_users):
            scores = E @ U[u]
            scores[seen[u]] = -np.inf
            top = np.argpartition(-scores, k)[:k]
            top = top[np.argsort(-scores[top], kind="stable")]
            total += ndcg_at_k(top.tolist(), {next_item[u]}, k)
        return total / n_users

    ndcg_als = _single_stage_ndcg(X_als, Y_als)
    ndcg_seq = _single_stage_ndcg(U_seq, E_seq)

    # --- the fused path: the SERVED device store, not a host re-derivation
    store = TwoStageTopK(X_als, Y_als, U_seq, E_seq, seen=seen,
                         candidates=candidates)
    try:
        ids, _ = store.twos_topk(np.arange(n_users, dtype=np.int64), k)
        ids = np.asarray(ids)
    finally:
        store.close()
    ndcg_two = sum(
        ndcg_at_k(ids[u].tolist(), {next_item[u]}, k)
        for u in range(n_users)) / n_users

    # stage-1 recall of the held-out item inside a HALVED budget — the
    # quality headroom a tighter serving configuration would trade away
    half = max(1, n_items // 2)
    recall = 0
    for u in range(n_users):
        s1 = Y_als @ X_als[u]           # unmasked, matching stage 1
        top_n = np.argpartition(-s1, half - 1)[:half]
        recall += next_item[u] in set(top_n.tolist())

    best_single = max(ndcg_als, ndcg_seq)
    return {
        "check": "twostage_vs_single_stage_quality_gate",
        "ndcg_two_stage": round(ndcg_two, 4),
        "ndcg_als_alone": round(ndcg_als, 4),
        "ndcg_seqrec_alone": round(ndcg_seq, 4),
        "gate_ndcg_not_worse": bool(ndcg_two >= best_single - 1e-9),
        "als_recall_at_half_catalog": round(recall / n_users, 4),
        "candidates": int(candidates),
        "k": k, "n_users": n_users, "n_items": n_items,
        "num_steps": num_steps,
        "rank_retrieval": rank_retrieval, "rank_rerank": rank_rerank,
        "protocol": ("per-user Markov walks (strides 1/3/7); held-out true "
                     "next item; two-stage list served by "
                     "TwoStageTopK.twos_topk"),
    }
