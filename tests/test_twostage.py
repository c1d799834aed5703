"""ISSUE 20 — two-stage serving differential suite.

Exactness: at N = catalog the fused retrieval + re-rank program is
BIT-level identical to a brute-force full-catalog re-rank (same ids,
same order, same ``lax.top_k`` tie-break) on every precision lane,
single-chip AND mesh-sharded — integer-valued fixtures make every dot
product an exact integer, so equality is independent of reduction
order. Plus: candidate handoff across shard boundaries, fold-in growth
through both stages, the zero-steady-state-compile gate, the
one-dispatch-per-batch flight-recorder gate, the serve-during-patch
hammer, the table-driven serving policy matrix, the host-compose
``TwoStageServing`` combinator, composite fold-in attach, the deployed
two-stage engine, and the multi-algorithm ensemble live path
(LFirst / LAverage — satellite, independent of TwoStageServing).
"""

import dataclasses
import datetime as dt
import http.client
import itertools
import json
import threading
import types

import numpy as np
import pytest

from predictionio_tpu.controller import (
    ComputeContext,
    EmptyParams,
    Engine,
    EngineParams,
    Params,
)
from predictionio_tpu.controller.controllers import (
    LAverageServing,
    LFirstServing,
    TwoStageServing,
)
from predictionio_tpu.ops.als import ALSParams
from predictionio_tpu.ops.serving import (
    DeviceTopK,
    _score_einsum,
    validate_serving_policy,
)
from predictionio_tpu.ops.twostage import (
    DEFAULT_CANDIDATES,
    TwoStageTopK,
    build_two_stage_store,
)
from predictionio_tpu.parallel.als_sharding import (
    density_aware_item_layout,
)

UTC = dt.timezone.utc
CTX = ComputeContext()


# ---------------------------------------------------------------------------
# Integer-exact fixtures + the brute-force oracle
# ---------------------------------------------------------------------------

def _int_problem(seed=0, n=12, m=19, r1=6, r2=5):
    """Integer-valued factor tables: every score is an exact integer in
    fp32/bf16 (values small enough for bf16's mantissa) and in int8
    with unit scales, so two-stage == brute-force is a BIT-level
    assertion, not a tolerance."""
    rng = np.random.default_rng(seed)
    X = rng.integers(-3, 4, size=(n, r1)).astype(np.float32)
    Y = rng.integers(-3, 4, size=(m, r1)).astype(np.float32)
    U = rng.integers(-3, 4, size=(n, r2)).astype(np.float32)
    E = rng.integers(-3, 4, size=(m, r2)).astype(np.float32)
    seen = {u: np.unique(rng.choice(m, size=4, replace=False))
            for u in range(0, n, 2)}
    return X, Y, U, E, seen


def _oracle(E, U, seen, uids, k):
    """Brute-force full-catalog re-rank: stage-2 scores over EVERY
    item, seen masked, ``lax.top_k`` — the tie-break rule (lowest item
    id wins among equals) is the device programs' contract."""
    import jax.numpy as jnp
    from jax import lax

    s2 = np.array(_score_einsum("mr,br->bm", jnp.asarray(E),
                                jnp.asarray(U), mode="fp32"))
    for u, items in (seen or {}).items():
        s2[int(u), np.asarray(items)] = -np.inf
    vals, idx = lax.top_k(jnp.asarray(s2[np.asarray(uids)]), k)
    return np.array(idx), np.array(vals)


def _quant(a):
    import jax.numpy as jnp

    from predictionio_tpu.ops.quantize import QuantFactors

    return QuantFactors(jnp.asarray(a.astype(np.int8)),
                        jnp.ones((a.shape[0],), jnp.float32))


def _layout(seen, m, shards=4):
    counts = np.zeros(m, np.int64)
    for v in seen.values():
        np.add.at(counts, v, 1)
    return density_aware_item_layout(counts, shards)


def _assert_exact(store, E, U, seen, k=7):
    n = U.shape[0]
    uids = np.arange(n)
    want_idx, want_vals = _oracle(E, U, seen, uids, k)
    got_idx, got_vals = store.twos_topk(uids, k)
    np.testing.assert_array_equal(got_idx, want_idx)
    np.testing.assert_array_equal(got_vals, want_vals)
    # single-uid lane agrees with its batch row (finite prefix)
    idx1, vals1 = store.two_topk(3, k)
    keep = np.isfinite(want_vals[3])
    np.testing.assert_array_equal(idx1, want_idx[3][keep])
    np.testing.assert_array_equal(vals1, want_vals[3][keep])


# ---------------------------------------------------------------------------
# N < catalog on the fused kernel: the stage-1 cut is the bounded merge's
# (ISSUE 29), so where the 128th candidate ties, who rises with the item
# id and who falls is decided there
# ---------------------------------------------------------------------------

CUT_M, CUT_N = 700, 128          # six kernel tiles, the last ragged


def _cut_problem(order, seed):
    """Stage-1 tables whose scores are exact integers laid out against
    the item id (``tests/test_fused_serving.py::_merge_problem``'s
    orders: rising, falling, blocks of 37 duplicated rows so that the
    cut at 128 falls inside a block, random), under random stage-2
    tables; user 5 has seen every item, the even users a few."""
    rng = np.random.default_rng(seed)
    n, i = 12, np.arange(CUT_M)
    if order == "random":
        Y = rng.integers(-3, 4, (CUT_M, 2))
        X = rng.integers(-3, 4, (n, 2))
    else:
        Y = np.stack([i // 16, i % 16], axis=1)
        c = rng.integers(1, 4, (n, 1))
        X = c * np.asarray([[16, 1]])
        if order == "falling":
            X = -X
        elif order == "blocks":
            Y = np.stack([i // 37, np.zeros_like(i)], axis=1)
            X = -c * np.asarray([[1, 0]])
    U = rng.integers(-3, 4, size=(n, 5)).astype(np.float32)
    E = rng.integers(-3, 4, size=(CUT_M, 5)).astype(np.float32)
    seen = {u: np.unique(rng.choice(CUT_M, size=40, replace=False))
            for u in range(0, n, 2)}
    seen[5] = np.arange(CUT_M)
    return X.astype(np.float32), Y.astype(np.float32), U, E, seen


def _cut_oracle(X, Y, U, E, seen, k):
    """Numpy: the 128 best stage-1 scores of a user (lowest item id
    among equals), re-ranked by stage 2 with the seen items out."""
    from jax import lax
    import jax.numpy as jnp

    s1 = X @ Y.T
    cand = np.argsort(-s1, axis=1, kind="stable")[:, :CUT_N]
    s2 = np.full(s1.shape, -np.inf, dtype=np.float32)
    rows = np.arange(len(X))[:, None]
    s2[rows, cand] = (U @ E.T)[rows, cand]
    for u, items in seen.items():
        s2[u, items] = -np.inf
    vals, idx = lax.top_k(jnp.asarray(s2), k)
    return np.asarray(idx), np.asarray(vals), s1


class TestFusedStage1Merge:
    @pytest.mark.parametrize("precision", ["fp32", "bf16", "int8"])
    @pytest.mark.parametrize("order", ["random", "rising", "falling",
                                       "blocks"])
    def test_cut_matches_oracle_and_counts_rounds(self, order, precision,
                                                  monkeypatch):
        from predictionio_tpu.ops.als_pallas import TOPK_TILE_M
        from predictionio_tpu.utils import device_telemetry
        from test_fused_serving import _rule_rounds

        monkeypatch.setenv("PIO_SERVE_KERNEL", "fused")
        if precision == "bf16":
            monkeypatch.setenv("PIO_SERVE_PRECISION", "bf16")
        X, Y, U, E, seen = _cut_problem(order, seed=len(order))
        tables = (X, Y, U, E)
        if precision == "int8":
            tables = tuple(_quant(t) for t in tables)
        store = TwoStageTopK(*tables, seen=seen, candidates=CUT_N,
                             microbatch=False, n_users=X.shape[0],
                             n_items=CUT_M)
        rec = device_telemetry.recorder()
        was = device_telemetry.enabled()
        device_telemetry.set_enabled(True)
        try:
            assert store._kernel == "fused"
            want_idx, want_vals, s1 = _cut_oracle(X, Y, U, E, seen, 7)
            rec.reset()
            got_idx, got_vals = store.twos_topk(np.arange(12), 7)
            np.testing.assert_array_equal(got_vals, want_vals)
            fin = np.isfinite(want_vals)
            np.testing.assert_array_equal(got_idx[fin], want_idx[fin])
            assert not fin[5].any()            # saw it all: nothing left
            # the one dispatch's selectRounds: the 12 users in a bucket
            # of 16 (padded with user 0's row), top-128 over two tiles
            record, = rec.snapshot(10)
            assert record["lane"] == "two" and record["bucket"] == 16
            block = np.concatenate([s1, s1[:1].repeat(4, axis=0)]).T
            assert record["selectRounds"] == _rule_rounds(
                block.astype(np.float32), CUT_N, TOPK_TILE_M)
            if order == "rising":              # the worst case, bounded
                assert record["selectRounds"] == sum(
                    min(CUT_N, TOPK_TILE_M, CUT_M - t)
                    for t in range(0, CUT_M, TOPK_TILE_M))
            elif order == "falling":           # the first tile fills it
                assert record["selectRounds"] == CUT_N
            idx1, vals1 = store.two_topk(3, 7)
            np.testing.assert_array_equal(idx1, want_idx[3][fin[3]])
            np.testing.assert_array_equal(vals1, want_vals[3][fin[3]])
        finally:
            device_telemetry.set_enabled(was)
            rec.reset()
            store.close()


# ---------------------------------------------------------------------------
# N = catalog exactness, every precision lane
# ---------------------------------------------------------------------------

class TestExactAtCatalog:
    def test_fp32(self):
        X, Y, U, E, seen = _int_problem()
        store = TwoStageTopK(X, Y, U, E, seen=seen,
                             candidates=Y.shape[0], microbatch=False)
        try:
            _assert_exact(store, E, U, seen)
        finally:
            store.close()

    def test_bf16(self, monkeypatch):
        monkeypatch.setenv("PIO_SERVE_PRECISION", "bf16")
        X, Y, U, E, seen = _int_problem(seed=1)
        store = TwoStageTopK(X, Y, U, E, seen=seen,
                             candidates=Y.shape[0], microbatch=False)
        try:
            _assert_exact(store, E, U, seen)
        finally:
            store.close()

    def test_int8(self):
        X, Y, U, E, seen = _int_problem(seed=2)
        store = TwoStageTopK(_quant(X), _quant(Y), _quant(U),
                             _quant(E), seen=seen,
                             candidates=Y.shape[0], microbatch=False,
                             n_users=X.shape[0], n_items=Y.shape[0])
        try:
            _assert_exact(store, E, U, seen)
        finally:
            store.close()

    def test_fused_kernel(self, monkeypatch):
        monkeypatch.setenv("PIO_SERVE_KERNEL", "fused")
        X, Y, U, E, seen = _int_problem(seed=3)
        store = TwoStageTopK(X, Y, U, E, seen=seen,
                             candidates=Y.shape[0], microbatch=False)
        try:
            _assert_exact(store, E, U, seen)
        finally:
            store.close()

    def test_mask_applied_exactly_once(self):
        """Stage 1 retrieves UNMASKED (at N = catalog a fully-seen user
        still has candidates); the one stage-2 mask drops them all."""
        X, Y, U, E, _ = _int_problem(seed=4)
        seen = {5: np.arange(Y.shape[0])}  # user 5 has seen everything
        store = TwoStageTopK(X, Y, U, E, seen=seen,
                             candidates=Y.shape[0], microbatch=False)
        try:
            idx, vals = store.two_topk(5, 7)
            assert len(idx) == 0 and len(vals) == 0
            _assert_exact(store, E, U, seen, k=7)
        finally:
            store.close()


@pytest.mark.multichip
class TestExactSharded:
    """The density-permuted mesh store: positions != item ids, so these
    lanes prove the pos->id tie-break table (candidates sorted by ITEM
    id, not store position, before re-rank)."""

    def test_fp32_sharded(self, multichip_devices):
        X, Y, U, E, seen = _int_problem(seed=5)
        store = TwoStageTopK(X, Y, U, E, seen=seen,
                             candidates=Y.shape[0], microbatch=False,
                             item_layout=_layout(seen, Y.shape[0]))
        try:
            assert store.shard_count == 4
            _assert_exact(store, E, U, seen)
        finally:
            store.close()

    def test_int8_sharded(self, multichip_devices):
        X, Y, U, E, seen = _int_problem(seed=6)
        store = TwoStageTopK(_quant(X), _quant(Y), _quant(U),
                             _quant(E), seen=seen,
                             candidates=Y.shape[0], microbatch=False,
                             n_users=X.shape[0], n_items=Y.shape[0],
                             item_layout=_layout(seen, Y.shape[0]))
        try:
            _assert_exact(store, E, U, seen)
        finally:
            store.close()

    def test_candidate_gather_across_shards(self, multichip_devices):
        """N < catalog: the stage-1 run spans shard boundaries (the
        density layout scatters the catalog over 4 shards) and the
        HBM gather must pick candidates from all of them — asserted as
        a differential against the single-chip store, which shares the
        same candidate-run semantics."""
        rng = np.random.default_rng(7)
        n, m = 16, 41
        X = rng.normal(size=(n, 6)).astype(np.float32)
        Y = rng.normal(size=(m, 6)).astype(np.float32)
        U = rng.normal(size=(n, 5)).astype(np.float32)
        E = rng.normal(size=(m, 5)).astype(np.float32)
        seen = {u: rng.choice(m, size=5, replace=False)
                for u in range(n)}
        layout = _layout(seen, m)
        single = TwoStageTopK(X, Y, U, E,
                              seen={u: v.copy() for u, v in seen.items()},
                              candidates=8, microbatch=False)
        sharded = TwoStageTopK(X, Y, U, E,
                               seen={u: v.copy() for u, v in seen.items()},
                               candidates=8, microbatch=False,
                               item_layout=layout)
        try:
            i1, s1 = single.twos_topk(np.arange(n), 6)
            i2, s2 = sharded.twos_topk(np.arange(n), 6)
            np.testing.assert_array_equal(i1, i2)
            np.testing.assert_allclose(s1, s2, atol=1e-5)
            # the winning candidates really straddle shards
            winners = np.unique(i2[np.isfinite(s2)])
            shards_hit = {int(layout.inv[it]) // layout.cap
                          for it in winners}
            assert len(shards_hit) > 1, \
                "top-k candidates all landed on one shard — the gather " \
                "across shard boundaries is untested by this layout"
        finally:
            single.close()
            sharded.close()

    def test_foldin_growth_sharded(self, multichip_devices):
        """A new user grows/reshards the mesh store through BOTH stage
        tables; the grown row serves exactly."""
        X, Y, U, E, seen = _int_problem(seed=8)
        store = TwoStageTopK(X, Y, U, E, seen=seen,
                             candidates=Y.shape[0], microbatch=False,
                             item_layout=_layout(seen, Y.shape[0]))
        try:
            new_uid = store.user_capacity + 3
            rng = np.random.default_rng(9)
            row2 = rng.integers(-3, 4, size=(1, U.shape[1])
                                ).astype(np.float32)
            store.patch_seq_users([new_uid], row2,
                                  seen_items={new_uid: np.asarray([0, 2])})
            store.patch_users([new_uid], np.zeros((1, X.shape[1]),
                                                  np.float32))
            U2 = np.zeros((new_uid + 1, U.shape[1]), np.float32)
            U2[:U.shape[0]] = U
            U2[new_uid] = row2[0]
            seen2 = dict(seen)
            seen2[new_uid] = np.asarray([0, 2])
            want_idx, want_vals = _oracle(E, U2, seen2, [new_uid], 6)
            got_idx, got_vals = store.twos_topk([new_uid], 6)
            np.testing.assert_array_equal(got_idx, want_idx)
            np.testing.assert_array_equal(got_vals, want_vals)
        finally:
            store.close()


# ---------------------------------------------------------------------------
# Fold-in growth, both stages (single chip)
# ---------------------------------------------------------------------------

class TestFoldInBothStages:
    def test_patch_seq_users_updates_ranking(self):
        X, Y, U, E, seen = _int_problem(seed=10)
        store = TwoStageTopK(X, Y, U, E, seen=seen,
                             candidates=Y.shape[0], microbatch=False)
        try:
            rng = np.random.default_rng(11)
            U2 = U.copy()
            U2[4] = rng.integers(-3, 4, size=U.shape[1])
            store.patch_seq_users([4], U2[4:5])
            _assert_exact(store, E, U2, seen)
        finally:
            store.close()

    def test_growth_via_stage2_probe(self):
        """patch_seq_users for an out-of-capacity uid grows BOTH stores
        through the stage-1 ladder; the stage-1 row stays zero until
        its own fold lands, and the grown user is servable at once."""
        X, Y, U, E, seen = _int_problem(seed=12)
        store = TwoStageTopK(X, Y, U, E, seen=seen,
                             candidates=Y.shape[0], microbatch=False)
        try:
            cap0 = store.user_capacity
            new_uid = cap0 + 5
            row2 = np.arange(U.shape[1], dtype=np.float32)[None, :]
            store.patch_seq_users([new_uid], row2)
            assert store.user_capacity > cap0
            assert store.n_users == new_uid + 1
            U2 = np.zeros((new_uid + 1, U.shape[1]), np.float32)
            U2[:U.shape[0]] = U
            U2[new_uid] = row2[0]
            want_idx, want_vals = _oracle(E, U2, seen, [new_uid], 5)
            got_idx, got_vals = store.twos_topk([new_uid], 5)
            np.testing.assert_array_equal(got_idx, want_idx)
            np.testing.assert_array_equal(got_vals, want_vals)
            # stage-1 fold for the same user rides the normal path
            store.patch_users([new_uid],
                              np.ones((1, X.shape[1]), np.float32))
            got_idx2, _ = store.twos_topk([new_uid], 5)
            np.testing.assert_array_equal(got_idx2, want_idx)
        finally:
            store.close()

    def test_seen_update_through_stage2_patch(self):
        X, Y, U, E, seen = _int_problem(seed=13)
        store = TwoStageTopK(X, Y, U, E, seen=seen,
                             candidates=Y.shape[0], microbatch=False)
        try:
            idx0, _ = store.two_topk(1, 3)
            newly_seen = np.asarray([int(idx0[0])])
            store.patch_seq_users([1], U[1:2],
                                  seen_items={1: newly_seen})
            seen2 = {k: v.copy() for k, v in seen.items()}
            seen2[1] = np.union1d(seen2.get(1, np.asarray([], np.int64)),
                                  newly_seen)
            _assert_exact(store, E, U, seen2)
        finally:
            store.close()


# ---------------------------------------------------------------------------
# Zero-steady-state-compile + single-dispatch gates
# ---------------------------------------------------------------------------

class TestZeroCompileSteadyState:
    def test_two_stage_traffic_compiles_nothing_after_warmup(self):
        from predictionio_tpu.utils import metrics

        X, Y, U, E, seen = _int_problem(seed=14, n=24, m=33)
        store = TwoStageTopK(X, Y, U, E, seen=seen, microbatch=False)
        try:
            assert metrics.install_jit_compile_listener()
            stats = store.warmup(max_k=16, batch_sizes=(16,))
            assert stats["compiled"] > 0
            c0 = metrics.JIT_COMPILES.value()
            rng = np.random.default_rng(15)
            for uid in range(12):
                store.two_topk(uid, 3 + (uid % 12))
            for n in (3, 9, 16):
                store.twos_topk(rng.integers(0, 24, size=n), 10)
            assert metrics.JIT_COMPILES.value() - c0 == 0, \
                "a steady-state two-stage query paid an XLA compile"
        finally:
            store.close()

    def test_aot_plan_includes_two_lane(self):
        X, Y, U, E, seen = _int_problem(seed=16)
        store = TwoStageTopK(X, Y, U, E, seen=seen, microbatch=False)
        try:
            plan = store.aot_plan(max_k=32, batch_sizes=(16,))
            kinds = {e[0] for e in plan}
            assert kinds == {"user", "users", "items", "two"}
            twos = [e for e in plan if e[0] == "two"]
            # every k bucket has a (k, N, batch) two-stage program
            ks = sorted({e[1] for e in twos})
            assert ks == sorted({e[1] for e in plan if e[0] == "user"})
            assert all(e[2] >= e[1] for e in twos), \
                "the N bucket must cover the k bucket"
        finally:
            store.close()


class TestSingleDispatchPerBatch:
    def test_flight_recorder_sees_one_two_lane_dispatch(self):
        """The no-host-round-trip gate: one batched two-stage query is
        ONE device dispatch on the \"two\" lane — retrieval and re-rank
        never surface as separate stage dispatches."""
        from predictionio_tpu.utils import device_telemetry

        X, Y, U, E, seen = _int_problem(seed=17)
        store = TwoStageTopK(X, Y, U, E, seen=seen,
                             candidates=Y.shape[0], microbatch=False)
        rec = device_telemetry.recorder()
        was = device_telemetry.enabled()
        device_telemetry.set_enabled(True)
        try:
            store.warmup(max_k=16, batch_sizes=(8,))
            rec.reset()
            store.twos_topk(np.arange(8), 6)
            recs = rec.snapshot(100)
            assert len(recs) == 1, \
                f"expected ONE dispatch, saw lanes " \
                f"{[r['lane'] for r in recs]}"
            assert recs[0]["lane"] == "two"
            rec.reset()
            store.two_topk(2, 5)
            recs = rec.snapshot(100)
            assert [r["lane"] for r in recs] == ["two"]
        finally:
            device_telemetry.set_enabled(was)
            rec.reset()
            store.close()


# ---------------------------------------------------------------------------
# Serve-during-patch hammer: queries race live fold-in on BOTH stores
# ---------------------------------------------------------------------------

class TestServeDuringPatch:
    def test_hammer_both_stores(self):
        X, Y, U, E, seen = _int_problem(seed=18, n=16, m=23)
        store = TwoStageTopK(X, Y, U, E, seen=seen,
                             candidates=Y.shape[0])
        errors = []
        stop = threading.Event()

        def query_loop(tid):
            rng = np.random.default_rng(tid)
            try:
                while not stop.is_set():
                    if rng.integers(2):
                        idx, vals = store.two_topk(
                            int(rng.integers(0, 16)), 5)
                        assert np.isfinite(vals).all()
                    else:
                        idx, vals = store.twos_topk(
                            rng.integers(0, 16, size=4), 5)
                        assert idx.shape == (4, 5)
            except Exception as e:  # pragma: no cover - failure path
                errors.append(e)

        threads = [threading.Thread(target=query_loop, args=(t,))
                   for t in range(4)]
        try:
            store.warmup(max_k=8, batch_sizes=(8,))
            for t in threads:
                t.start()
            rng = np.random.default_rng(99)
            U_final = U.copy()
            for step in range(30):
                uid = int(rng.integers(0, 16))
                if step % 2:
                    row = rng.integers(-3, 4, size=(1, U.shape[1])
                                       ).astype(np.float32)
                    store.patch_seq_users([uid], row)
                    U_final[uid] = row[0]
                else:
                    store.patch_users(
                        [uid], rng.integers(-3, 4, size=(1, X.shape[1])
                                            ).astype(np.float32))
            stop.set()
            for t in threads:
                t.join(timeout=30)
            assert not errors, errors[:3]
            # the store converged to exactly the final patched state
            _assert_exact(store, E, U_final, seen, k=5)
        finally:
            stop.set()
            store.close()


# ---------------------------------------------------------------------------
# Satellite: the table-driven serving policy matrix, fully enumerated
# ---------------------------------------------------------------------------

class TestServingPolicyMatrix:
    FRAGMENT = {
        "resident": "device-resident",
        "precision": "PIO_SERVE_PRECISION",
        "foldin": "PIO_FOLDIN",
        "sharded": "PIO_SERVE_SHARDS",
        "two_stage": "two-stage serving",
    }

    @staticmethod
    def _active(host_capable, precision, foldin, sharded, two_stage):
        """The policy matrix restated independently of the production
        table: the historical raise order of choose_server."""
        names = []
        if not host_capable:
            names.append("resident")
        if precision in ("bf16", "int8"):
            names.append("precision")
        if foldin:
            names.append("foldin")
        if sharded:
            names.append("sharded")
        if two_stage:
            names.append("two_stage")
        return names

    def test_full_matrix(self):
        cases = itertools.product(
            ("host", "device", "auto", ""),
            (True, False),                      # host_capable
            (None, "fp32", "bf16", "int8"),     # explicit precision
            (False, True),                      # foldin
            (False, True),                      # sharded
            (False, True),                      # two_stage
        )
        for backend, cap, prec, fold, shard, two in cases:
            active = self._active(cap, prec, fold, shard, two)
            kw = dict(host_capable=cap, explicit_precision=prec,
                      foldin=fold, sharded=shard, two_stage=two)
            if backend == "host":
                if active:
                    with pytest.raises(ValueError) as ei:
                        validate_serving_policy(backend, **kw)
                    assert self.FRAGMENT[active[0]] in str(ei.value), \
                        (backend, kw, active)
                else:
                    assert validate_serving_policy(backend,
                                                   **kw) == "host"
            elif backend == "device":
                assert validate_serving_policy(backend, **kw) == "device"
            else:  # auto / unknown fall through alike
                want = "device" if active else "auto"
                assert validate_serving_policy(backend, **kw) == want, \
                    (backend, kw, active)

    def test_choose_server_delegates_to_matrix(self, monkeypatch):
        """The refactor satellite's non-regression: choose_server's
        behavior is the matrix's, not a parallel if-chain."""
        from predictionio_tpu.ops.serving import HostTopK, choose_server

        rng = np.random.default_rng(0)
        X = rng.normal(size=(6, 4)).astype(np.float32)
        Y = rng.normal(size=(9, 4)).astype(np.float32)
        assert isinstance(choose_server(X, Y, {}), HostTopK)
        monkeypatch.setenv("PIO_FOLDIN", "on")
        srv = choose_server(X, Y, {})
        assert isinstance(srv, DeviceTopK)
        srv.close()
        monkeypatch.setenv("PIO_SERVING_BACKEND", "host")
        with pytest.raises(ValueError, match="PIO_FOLDIN"):
            choose_server(X, Y, {})


# ---------------------------------------------------------------------------
# build_two_stage_store validation + TwoStageServing host compose
# ---------------------------------------------------------------------------

def _fake_models(n=6, m=9, r1=4, r2=3, users=None, items=None):
    rng = np.random.default_rng(3)
    retrieval = types.SimpleNamespace(
        user_factors=rng.normal(size=(n, r1)).astype(np.float32),
        item_factors=rng.normal(size=(m, r1)).astype(np.float32),
        user_map=list(range(users if users is not None else n)),
        item_map=list(range(items if items is not None else m)),
        seen=None)
    rerank = types.SimpleNamespace(
        user_vectors=rng.normal(size=(n, r2)).astype(np.float32),
        item_vectors=rng.normal(size=(m, r2)).astype(np.float32),
        user_map=list(range(n)), item_map=list(range(m)))
    return retrieval, rerank


class TestBuildStoreValidation:
    def test_builds_and_serves(self):
        retrieval, rerank = _fake_models()
        store = build_two_stage_store(retrieval, rerank, candidates=9)
        try:
            assert isinstance(store, TwoStageTopK)
            idx, vals = store.twos_topk([0, 1], 4)
            assert idx.shape == (2, 4)
        finally:
            store.close()

    def test_default_candidates_env(self, monkeypatch):
        monkeypatch.setenv("PIO_TWOSTAGE_N", "7")
        retrieval, rerank = _fake_models()
        store = build_two_stage_store(retrieval, rerank)
        try:
            assert store._candidates == 7
        finally:
            store.close()
        monkeypatch.delenv("PIO_TWOSTAGE_N")
        store = build_two_stage_store(retrieval, rerank)
        try:
            assert store._candidates == DEFAULT_CANDIDATES
        finally:
            store.close()

    def test_retrieval_shape_required(self):
        retrieval, rerank = _fake_models()
        with pytest.raises(ValueError, match="FIRST algorithm"):
            build_two_stage_store(rerank, rerank)
        with pytest.raises(ValueError, match="LAST algorithm"):
            build_two_stage_store(retrieval, retrieval)

    def test_shared_item_map_required(self):
        retrieval, rerank = _fake_models()
        rerank.item_map = list(range(5))
        with pytest.raises(ValueError, match="one shared item map"):
            build_two_stage_store(retrieval, rerank)

    def test_host_backend_refused(self, monkeypatch):
        monkeypatch.setenv("PIO_SERVING_BACKEND", "host")
        retrieval, rerank = _fake_models()
        with pytest.raises(ValueError, match="two-stage serving"):
            build_two_stage_store(retrieval, rerank)

    def test_foldin_needs_reencoder(self, monkeypatch):
        monkeypatch.setenv("PIO_FOLDIN", "on")
        retrieval, rerank = _fake_models()
        with pytest.raises(ValueError, match="fold_in_rows"):
            build_two_stage_store(retrieval, rerank)
        rerank.fold_in_rows = lambda *a, **kw: None
        store = build_two_stage_store(retrieval, rerank)
        store.close()


class TestTwoStageServingHostCompose:
    def _pred(self, pairs):
        from predictionio_tpu.templates.recommendation.engine import (
            ItemScore,
            PredictedResult,
        )
        return PredictedResult(tuple(
            ItemScore(item=i, score=s) for i, s in pairs))

    def test_rerank_composes_on_host(self):
        serving = TwoStageServing()
        assert not serving.fused_bound
        head = self._pred([("a", 3.0), ("b", 2.0), ("c", 1.0)])
        tail = self._pred([("b", 10.0), ("c", 5.0)])
        out = serving.serve(None, [head, tail])
        assert [(s.item, s.score) for s in out.item_scores] == [
            ("b", 10.0), ("c", 5.0), ("a", 3.0)]

    def test_single_prediction_passthrough(self):
        serving = TwoStageServing()
        head = self._pred([("a", 3.0)])
        assert serving.serve(None, [head]) is head

    def test_fused_route(self):
        serving = TwoStageServing()
        calls = []
        serving.bind_fused(lambda q: calls.append(q) or "fused")
        assert serving.fused_bound
        assert serving.serve_fused("q1") == "fused"
        assert calls == ["q1"]


# ---------------------------------------------------------------------------
# Composite fold-in attach (both stages of a deployment fold)
# ---------------------------------------------------------------------------

class _MapN:
    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def get(self, k):
        return None


def _foldable_model(hook=False):
    m = types.SimpleNamespace(user_map=_MapN(4), item_map=_MapN(4),
                              device_server=lambda: None)
    if hook:
        m.fold_in_rows = lambda *a, **kw: None
    return m


def _fake_deployment(models, params):
    ep = types.SimpleNamespace(
        algorithm_params_list=params,
        data_source_params=("", types.SimpleNamespace(
            app_name="app", channel_name=None, event_names=("rate",))),
        preparator_params=("", types.SimpleNamespace(max_len=None)))
    return types.SimpleNamespace(models=models, engine_params=ep)


class TestCompositeFoldIn:
    def test_all_qualifying_models_attach(self, mem_storage):
        from predictionio_tpu.online.foldin import (
            CompositeFoldInConsumer,
            attach_foldin,
        )

        dep = _fake_deployment(
            [_foldable_model(), _foldable_model(hook=True)],
            [("als", ALSParams()), ("seqrec", object())])
        c = attach_foldin(dep)
        assert isinstance(c, CompositeFoldInConsumer)
        assert len(c.consumers) == 2
        s = c.stats()
        assert s["folds"] == 0 and len(s["targets"]) == 2
        assert c.stale is False

    def test_single_target_backcompat(self, mem_storage):
        from predictionio_tpu.online.foldin import (
            FoldInConsumer,
            attach_foldin,
        )

        dep = _fake_deployment([_foldable_model()],
                               [("als", ALSParams())])
        assert isinstance(attach_foldin(dep), FoldInConsumer)

    def test_qualifying_model_without_solve_refused(self, mem_storage):
        from predictionio_tpu.online.foldin import attach_foldin

        dep = _fake_deployment(
            [_foldable_model(), _foldable_model()],
            [("als", ALSParams()), ("x", object())])
        with pytest.raises(ValueError, match="fold_in_rows"):
            attach_foldin(dep)

    def test_shared_vocab_targets_share_patch_lock(self, mem_storage):
        """Two-stage targets share ONE user_map; their consumers must
        share ONE patch lock, and the second target to fold a new user
        must see the first's append (existing row, no double-assign,
        no 'already mapped' error — the live-deploy race)."""
        from predictionio_tpu.online.foldin import attach_foldin

        class _GrowMap:
            def __init__(self):
                self._m = {"u0": 0}

            def __len__(self):
                return len(self._m)

            def get(self, k):
                return self._m.get(k)

            def append(self, labels):
                for k in labels:
                    if k in self._m:
                        raise ValueError(f"label {k!r} already mapped")
                    self._m[k] = len(self._m)

        shared = _GrowMap()
        m1, m2 = _foldable_model(), _foldable_model(hook=True)
        m1.user_map = m2.user_map = shared
        other = _foldable_model()          # its own vocabulary
        dep = _fake_deployment(
            [m1, m2, other],
            [("als", ALSParams()), ("seq", object()),
             ("als2", ALSParams())])
        c = attach_foldin(dep)
        c1, c2, c3 = c.consumers
        assert c1._patch_lock is c2._patch_lock
        assert c3._patch_lock is not c1._patch_lock

        calls = []
        server = types.SimpleNamespace(
            patch_users=lambda idx, rows, seen_items=None:
                calls.append(np.asarray(idx).tolist()))
        rows = np.zeros((1, 2), dtype=np.float32)
        cols = [np.asarray([1, 2], dtype=np.int64)]
        kept1, new1 = c1._patch(server, ["u9"], cols, rows)
        kept2, new2 = c2._patch(server, ["u9"], cols, rows)
        assert (kept1, new1) == (0, 1)
        assert (kept2, new2) == (1, 0)      # second sees the append
        assert calls == [[1], [1]]          # same row, assigned once
        assert len(shared) == 2


# ---------------------------------------------------------------------------
# Satellite: multi-algorithm ensemble on the LIVE path (no TwoStage)
# ---------------------------------------------------------------------------

def two_als_first_factory() -> Engine:
    from predictionio_tpu.templates.recommendation.engine import (
        ALSAlgorithm,
        EventDataSource,
        RatingsPreparator,
    )
    return Engine(EventDataSource, RatingsPreparator,
                  {"als": ALSAlgorithm}, {"": LFirstServing})


@dataclasses.dataclass(frozen=True)
class RidgeParams(Params):
    lam: float = 0.1


def _make_ridge():
    from predictionio_tpu.templates.regression.engine import (
        LocalAlgorithm,
    )

    class _Ridge(LocalAlgorithm):
        params_class = RidgeParams

        def train(self, td):
            lam = float(self.params.lam)
            A = td.x.T @ td.x + lam * np.eye(td.x.shape[1])
            return np.linalg.solve(A, td.x.T @ td.y)

    return _Ridge


def laverage_regression_factory() -> Engine:
    from predictionio_tpu.templates.regression.engine import (
        LocalAlgorithm,
        LocalDataSource,
        LocalPreparator,
    )
    return Engine(LocalDataSource, LocalPreparator,
                  {"ols": LocalAlgorithm, "ridge": _make_ridge()},
                  {"": LAverageServing})


def _post(addr, path, body):
    host, port = addr
    conn = http.client.HTTPConnection(host, port, timeout=30)
    conn.request("POST", path, body=json.dumps(body),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = json.loads(resp.read().decode("utf-8"))
    conn.close()
    return resp.status, data


def _seed_ratings(app_name="multiapp", n_users=20):
    from predictionio_tpu.data import storage
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage.base import App

    aid = storage.get_metadata_apps().insert(App(0, app_name))
    le = storage.get_levents()
    le.init(aid)
    rng = np.random.default_rng(0)
    t0 = dt.datetime(2021, 1, 1, tzinfo=UTC)
    events = []
    for u in range(n_users):
        group = "a" if u < n_users // 2 else "b"
        for _ in range(8):
            events.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"{group}{rng.integers(0, 10)}",
                properties={"rating": float(rng.integers(4, 6))},
                event_time=t0))
    le.insert_batch(events, aid)
    return aid


class TestMultiAlgorithmLivePath:
    def test_lfirst_two_als_variants(self, mem_storage):
        """Two ALS variants behind LFirstServing: train both, deploy,
        query over HTTP — the served result is the FIRST variant's
        prediction, proving the ensemble composes on the live path."""
        from predictionio_tpu.templates.recommendation import (
            DataSourceParams,
            Query,
        )
        from predictionio_tpu.workflow import (
            QueryServer,
            ServerConfig,
            run_train,
        )
        from predictionio_tpu.workflow.create_server import (
            build_deployment,
            resolve_engine_instance,
            serve_query,
        )
        from predictionio_tpu.workflow.create_workflow import (
            WorkflowConfig,
            new_engine_instance,
        )

        _seed_ratings()
        engine = two_als_first_factory()
        params = EngineParams(
            data_source_params=("", DataSourceParams(
                app_name="multiapp")),
            algorithm_params_list=[
                ("als", ALSParams(rank=8, num_iterations=4, seed=1)),
                ("als", ALSParams(rank=4, num_iterations=4, seed=2))],
        )
        cfg = WorkflowConfig(
            engine_factory="tests.test_twostage:two_als_first_factory")
        iid = run_train(engine, params, new_engine_instance(cfg, params),
                        ctx=CTX)
        assert iid is not None
        dep = build_deployment(resolve_engine_instance(iid), CTX)
        assert len(dep.models) == 2 and len(dep.algorithms) == 2
        assert isinstance(dep.serving, LFirstServing)
        q = Query(user="u1", num=4)
        served = serve_query(dep, q)
        first = dep.algorithms[0].predict_base(dep.models[0], q)
        assert [s.item for s in served.item_scores] == \
            [s.item for s in first.item_scores]
        srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0)).start(
            undeploy_stale=False)
        try:
            status, result = _post(srv.address, "/queries.json",
                                   {"user": "u1", "num": 4})
            assert status == 200
            assert [s["item"] for s in result["itemScores"]] == \
                [s.item for s in first.item_scores]
        finally:
            srv.stop()

    def test_laverage_two_variants(self, mem_storage, tmp_path):
        """Two regression variants behind LAverageServing: the served
        value is the MEAN of the per-algorithm predictions (and equals
        neither alone — the second variant is heavily regularized)."""
        from predictionio_tpu.templates.regression import (
            DataSourceParams,
            PreparatorParams,
        )
        from predictionio_tpu.templates.regression.engine import (
            Query as RQuery,
        )
        from predictionio_tpu.workflow import (
            QueryServer,
            ServerConfig,
            run_train,
        )
        from predictionio_tpu.workflow.create_server import (
            build_deployment,
            resolve_engine_instance,
            serve_query,
        )
        from predictionio_tpu.workflow.create_workflow import (
            WorkflowConfig,
            new_engine_instance,
        )

        rng = np.random.default_rng(0)
        Xd = rng.normal(size=(60, 3))
        y = Xd @ np.asarray([2.0, -3.0, 0.5])
        f = tmp_path / "lr.txt"
        f.write_text("\n".join(
            f"{yi} " + " ".join(str(v) for v in row)
            for yi, row in zip(y, Xd)))
        engine = laverage_regression_factory()
        params = EngineParams(
            data_source_params=("", DataSourceParams(filepath=str(f))),
            preparator_params=("", PreparatorParams()),
            algorithm_params_list=[
                ("ols", EmptyParams()),
                ("ridge", RidgeParams(lam=50.0))],
        )
        cfg = WorkflowConfig(engine_factory="tests.test_twostage"
                                            ":laverage_regression_factory")
        iid = run_train(engine, params, new_engine_instance(cfg, params),
                        ctx=CTX)
        assert iid is not None
        dep = build_deployment(resolve_engine_instance(iid), CTX)
        assert isinstance(dep.serving, LAverageServing)
        q = RQuery(features=(1.0, 1.0, 2.0))
        served = serve_query(dep, q)
        singles = [a.predict_base(m, q)
                   for a, m in zip(dep.algorithms, dep.models)]
        assert served == pytest.approx(sum(singles) / 2)
        assert abs(singles[0] - singles[1]) > 1e-3, \
            "variants trained identically — the average proves nothing"
        srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0)).start(
            undeploy_stale=False)
        try:
            status, value = _post(srv.address, "/queries.json",
                                  {"features": [1.0, 1.0, 2.0]})
            assert status == 200
            assert float(value) == pytest.approx(served)
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# The deployed two-stage engine: train both stages -> fused serving
# ---------------------------------------------------------------------------

def _seed_chains(app_name="twostageapp", n_users=30, n_items=25, seed=0):
    from predictionio_tpu.data import storage
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage.base import App

    aid = storage.get_metadata_apps().insert(App(0, app_name))
    le = storage.get_levents()
    le.init(aid)
    rng = np.random.default_rng(seed)
    t0 = dt.datetime(2024, 1, 1, tzinfo=UTC)
    events = []
    for u in range(n_users):
        start = int(rng.integers(0, n_items))
        for j in range(int(rng.integers(5, 10))):
            events.append(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"i{(start + j) % n_items}",
                event_time=t0 + dt.timedelta(minutes=float(j))))
    le.insert_batch(events, aid)
    return aid


class TestTwoStageDeployed:
    def test_train_deploy_query_fused_zero_compile(self, mem_storage,
                                                   monkeypatch):
        """The tentpole acceptance slice: the twostage template trains
        BOTH stages from one event stream, deploys onto ONE fused
        store (serving binds the fused route), answers queries with the
        seen mask applied, and steady-state queries compile nothing."""
        from predictionio_tpu.templates.sequentialrec import (
            DataSourceParams,
            SeqRecParams,
        )
        from predictionio_tpu.templates.twostage import (
            TwoStagePreparatorParams,
            engine_factory,
        )
        from predictionio_tpu.utils import metrics
        from predictionio_tpu.workflow import (
            QueryServer,
            ServerConfig,
            run_train,
        )
        from predictionio_tpu.workflow.create_workflow import (
            WorkflowConfig,
            new_engine_instance,
        )

        _seed_chains()
        engine = engine_factory()
        params = EngineParams(
            data_source_params=("", DataSourceParams(
                app_name="twostageapp")),
            preparator_params=("", TwoStagePreparatorParams(
                max_seq_len=16)),
            algorithm_params_list=[
                ("als", ALSParams(rank=8, num_iterations=4, seed=0)),
                ("seqrec", SeqRecParams(
                    rank=8, n_layers=1, n_heads=2, max_seq_len=16,
                    num_steps=40, batch_size=16, n_negatives=8,
                    learning_rate=0.01, seed=0))],
        )
        cfg = WorkflowConfig(
            engine_factory="predictionio_tpu.templates.twostage"
                           ":engine_factory")
        iid = run_train(engine, params, new_engine_instance(cfg, params),
                        ctx=CTX)
        assert iid is not None
        assert metrics.install_jit_compile_listener()
        srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0)).start(
            undeploy_stale=False)
        try:
            dep = srv._deployment
            assert isinstance(dep.serving, TwoStageServing)
            assert dep.serving.fused_bound
            assert isinstance(dep.models[0]._server.store, TwoStageTopK)
            assert dep.models[0]._server.store is \
                dep.models[-1]._server.store
            # warm request outside the gate (lazy HTTP-layer caches)
            status, result = _post(srv.address, "/queries.json",
                                   {"user": "u1", "num": 3})
            assert status == 200 and result["itemScores"]
            c0 = metrics.JIT_COMPILES.value()
            for u in range(2, 16):
                status, result = _post(srv.address, "/queries.json",
                                       {"user": f"u{u}",
                                        "num": 3 + (u % 6)})
                assert status == 200 and result["itemScores"]
                scores = [s["score"] for s in result["itemScores"]]
                assert scores == sorted(scores, reverse=True)
            assert metrics.JIT_COMPILES.value() - c0 == 0, \
                "a steady-state two-stage query paid an XLA compile"
        finally:
            srv.stop()


@pytest.mark.slow
class TestQualityGate:
    def test_twostage_ndcg_not_worse_than_single_stage(self):
        """The ISSUE-20 quality half of the acceptance gate, on the
        seqrec Markov stream: NDCG@10 of the SERVED two-stage list
        (TwoStageTopK.twos_topk) >= max(ALS alone, seqrec alone) —
        fusing retrieval + re-rank into one device program costs no
        quality (quality_gates.run_twostage_check)."""
        import quality_gates

        out = quality_gates.run_twostage_check(
            n_users=80, n_items=50, num_steps=150)
        assert out["gate_ndcg_not_worse"] is True, out
        # the stream is built so the sequence model carries the signal;
        # the two-stage list must recover it THROUGH the ALS candidates
        assert out["ndcg_two_stage"] > out["ndcg_als_alone"], out
