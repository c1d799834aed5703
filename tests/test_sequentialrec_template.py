"""sequentialrec template tests: datasource (single-scan == streamed),
time-ordering preparator, train -> next-item predict, shared eval
protocols, deployed serving with the zero-compile gate, and online
fold-in freshness (a user's NEW event changes their served top-k with
no retrain and no /reload)."""

import datetime as dt
import http.client
import json
import time

import numpy as np
import pytest

from predictionio_tpu.controller import ComputeContext, EngineParams
from predictionio_tpu.data import storage
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.templates.sequentialrec import (
    DataSourceParams,
    Query,
    SeqPreparatorParams,
    SeqRecParams,
    SequenceDataSource,
    SequencePreparator,
    engine_factory,
)

UTC = dt.timezone.utc
CTX = ComputeContext()
T0 = dt.datetime(2024, 1, 1, tzinfo=UTC)
FACTORY = "predictionio_tpu.templates.sequentialrec:engine_factory"
N_ITEMS = 40


def view_event(user, item, minutes=0.0):
    return Event(event="view", entity_type="user", entity_id=user,
                 target_entity_type="item", target_entity_id=item,
                 event_time=T0 + dt.timedelta(minutes=minutes))


def seed_chains(app_name="seqapp", n_users=50, n_items=N_ITEMS, seed=0):
    """Deterministic chain stream: each user walks item (start+j) % M —
    the next item after a user's last is always predictable."""
    aid = storage.get_metadata_apps().insert(App(0, app_name))
    le = storage.get_levents()
    le.init(aid)
    rng = np.random.default_rng(seed)
    events = []
    for u in range(n_users):
        start = int(rng.integers(0, n_items))
        n = int(rng.integers(4, 12))
        for j in range(n):
            events.append(view_event(
                f"u{u}", f"i{(start + j) % n_items}", minutes=j))
    le.insert_batch(events, aid)
    return aid


def algo_params(num_steps=150, seed=0, **kw):
    return SeqRecParams(rank=16, n_layers=2, n_heads=2, max_seq_len=16,
                        num_steps=num_steps, batch_size=32,
                        n_negatives=32, learning_rate=0.01, seed=seed,
                        **kw)


def make_params(app_name="seqapp", **kw):
    return EngineParams(
        data_source_params=("", DataSourceParams(app_name=app_name)),
        preparator_params=("", SeqPreparatorParams(max_seq_len=16)),
        algorithm_params_list=[("seqrec", algo_params(**kw))],
    )


def train_instance(app_name="seqapp", **kw):
    from predictionio_tpu.workflow import run_train
    from predictionio_tpu.workflow.create_workflow import (
        WorkflowConfig,
        new_engine_instance,
    )

    engine = engine_factory()
    params = make_params(app_name, **kw)
    config = WorkflowConfig(engine_factory=FACTORY)
    iid = run_train(engine, params, new_engine_instance(config, params),
                    ctx=CTX)
    assert iid is not None
    return iid


def _post(addr, path, body):
    host, port = addr
    conn = http.client.HTTPConnection(host, port, timeout=30)
    conn.request("POST", path, body=json.dumps(body),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = json.loads(resp.read().decode("utf-8"))
    conn.close()
    return resp.status, data


class TestDataSource:
    def test_streamed_read_matches_single_scan(self, mem_storage):
        seed_chains()
        single = SequenceDataSource(DataSourceParams(
            app_name="seqapp")).read_training(CTX)
        streamed = SequenceDataSource(DataSourceParams(
            app_name="seqapp", streaming_block_size=37,
            decode_prefetch=2)).read_training(CTX)
        assert len(single) == len(streamed)
        # same multiset of (user, item, time) triples whatever the
        # block boundaries were
        def canon(td):
            return sorted(zip(td.users.astype(str),
                              td.items.astype(str), td.times))
        assert canon(single) == canon(streamed)

    def test_targetless_events_filtered(self, mem_storage):
        aid = storage.get_metadata_apps().insert(App(0, "seqapp"))
        le = storage.get_levents()
        le.init(aid)
        le.insert_batch([
            view_event("u1", "i1", 0),
            Event(event="view", entity_type="user", entity_id="u1",
                  event_time=T0),  # no target
        ], aid)
        td = SequenceDataSource(DataSourceParams(
            app_name="seqapp")).read_training(CTX)
        assert len(td) == 1

    def test_leave_last_out_eval_holds_most_recent(self, mem_storage):
        aid = storage.get_metadata_apps().insert(App(0, "seqapp"))
        le = storage.get_levents()
        le.init(aid)
        # u1's events arrive OUT of time order: the held-out actual
        # must be the latest by TIME (i9), not by arrival
        le.insert_batch([
            view_event("u1", "i9", minutes=50),
            view_event("u1", "i1", minutes=1),
            view_event("u1", "i2", minutes=2),
            view_event("u2", "i3", minutes=1),
        ], aid)
        sets = SequenceDataSource(DataSourceParams(
            app_name="seqapp")).read_eval(CTX)
        assert len(sets) == 1
        td, _, qa = sets[0]
        held = {q.user: a.items for q, a in qa}
        assert held == {"u1": ("i9",)}
        assert len(td) == 3  # u2's single event trains whole

    def test_sliding_eval_windows(self, mem_storage):
        aid = storage.get_metadata_apps().insert(App(0, "seqapp"))
        le = storage.get_levents()
        le.init(aid)
        le.insert_batch(
            [view_event("u1", f"i{j}", minutes=j * 1440) # one per day
             for j in range(10)], aid)
        ds = SequenceDataSource(DataSourceParams(
            app_name="seqapp",
            eval_first_until=(T0 + dt.timedelta(days=5)).isoformat(),
            eval_duration_days=2.0, eval_count=2))
        sets = ds.read_eval(CTX)
        assert len(sets) == 2
        td0, _, qa0 = sets[0]
        assert len(td0) == 5                      # days 0..4
        assert qa0[0][1].items == ("i5", "i6")    # days 5, 6
        td1, _, qa1 = sets[1]
        assert len(td1) == 7
        assert qa1[0][1].items == ("i7", "i8")


class TestPreparator:
    def test_sequences_are_time_ordered(self, mem_storage):
        aid = storage.get_metadata_apps().insert(App(0, "seqapp"))
        le = storage.get_levents()
        le.init(aid)
        le.insert_batch([
            view_event("u1", "i3", minutes=30),
            view_event("u1", "i1", minutes=10),
            view_event("u1", "i2", minutes=20),
        ], aid)
        td = SequenceDataSource(DataSourceParams(
            app_name="seqapp")).read_training(CTX)
        pd = SequencePreparator(SeqPreparatorParams(
            max_seq_len=16)).prepare(CTX, td)
        (bucket,) = pd.buckets
        decoded = pd.item_map.decode(
            bucket.ids[0][:3].astype(np.int64))
        assert list(decoded) == ["i1", "i2", "i3"]

    def test_seen_sets_cover_history(self, mem_storage):
        seed_chains(n_users=5)
        td = SequenceDataSource(DataSourceParams(
            app_name="seqapp")).read_training(CTX)
        pd = SequencePreparator(SeqPreparatorParams(
            max_seq_len=16)).prepare(CTX, td)
        for u, items in pd.seen.items():
            assert len(items) == len(np.unique(items))
            assert len(items) >= 1


class TestTrainPredict:
    def test_next_item_predicted_on_chain(self, mem_storage):
        seed_chains(seed=3)
        engine = engine_factory()
        params = make_params(seed=3)
        model = engine.train(CTX, params)[0]
        algo = engine._algorithms(params)[0]
        # for most users the top prediction should be the chain's next
        # item (their own history is seen-masked away)
        le = storage.get_levents()
        aid = storage.get_metadata_apps().get_by_name("seqapp").id
        hits = total = 0
        for u in range(0, 30, 3):
            evs = sorted(le.find(aid, entity_id=f"u{u}"),
                         key=lambda e: e.event_time)
            if not evs:
                continue
            nxt = f"i{(int(evs[-1].target_entity_id[1:]) + 1) % N_ITEMS}"
            r = algo.predict(model, Query(user=f"u{u}", num=10))
            total += 1
            hits += nxt in {s.item for s in r.item_scores}
        assert total >= 8
        assert hits / total > 0.7

    def test_all_negative_scores_still_serve_a_ranking(self,
                                                       mem_storage):
        """Transformer logits are only relatively calibrated: a user
        whose dot products are ALL negative must still get their num
        results (serve_positive_scores_only=False opts out of the
        implicit-ALS positivity filter), while device masks (-inf seen
        items) still drop."""
        from predictionio_tpu.data.bimap import StringIndexBiMap
        from predictionio_tpu.ops.seqrec import SeqRecParams, init_theta
        from predictionio_tpu.templates.sequentialrec import (
            SeqRecAlgorithm,
            SeqRecModel,
        )

        params = algo_params()
        theta = init_theta(6, params)
        model = SeqRecModel(
            user_vectors=-np.ones((2, 16), dtype=np.float32),
            item_vectors=np.ones((6, 16), dtype=np.float32),
            user_map=StringIndexBiMap.from_distinct(
                np.asarray(["u0", "u1"], dtype=object)),
            item_map=StringIndexBiMap.from_distinct(
                np.asarray([f"i{j}" for j in range(6)], dtype=object)),
            seen={0: np.asarray([0, 1])},
            theta=theta, enc_params=params, max_seq_len=16)
        algo = SeqRecAlgorithm(params)
        r = algo.predict(model, Query(user="u0", num=3))
        assert len(r.item_scores) == 3
        assert all(s.score < 0 for s in r.item_scores)
        assert {s.item for s in r.item_scores}.isdisjoint({"i0", "i1"})

    def test_unknown_user_empty(self, mem_storage):
        seed_chains(n_users=10)
        engine = engine_factory()
        params = make_params(num_steps=20)
        model = engine.train(CTX, params)[0]
        algo = engine._algorithms(params)[0]
        assert algo.predict(model, Query(user="nobody")).item_scores == ()

    def test_batch_predict_matches_single(self, mem_storage):
        seed_chains(n_users=12)
        engine = engine_factory()
        params = make_params(num_steps=30)
        model = engine.train(CTX, params)[0]
        algo = engine._algorithms(params)[0]
        qs = [(i, Query(user=f"u{i}", num=5)) for i in range(8)]
        batch = dict(algo.batch_predict(CTX, model, qs))
        for qx, q in qs:
            assert batch[qx] == algo.predict(model, q)

    def test_model_pickles_and_serves_after_reload(self, mem_storage):
        import pickle

        seed_chains(n_users=10)
        engine = engine_factory()
        params = make_params(num_steps=30)
        model = engine.train(CTX, params)[0]
        algo = engine._algorithms(params)[0]
        want = algo.predict(model, Query(user="u1", num=5))
        # a fold populates the cached device theta; pickling must drop
        # it along with the serving handles
        model.fold_in_rows([np.asarray([0, 1], dtype=np.int64)],
                           [np.ones(2, np.float32)])
        assert getattr(model, "_theta_device", None) is not None
        clone = pickle.loads(pickle.dumps(model))
        assert clone._server is None  # device handles dropped
        assert getattr(clone, "_theta_device", None) is None
        got = algo.predict(clone, Query(user="u1", num=5))
        assert got == want

    def test_fold_in_rows_matches_training_encode(self, mem_storage):
        """The fold-in hook re-encodes a user's own (time-ordered)
        history into their trained user vector: equal to the
        single-device encoder, and within the sequence-parallel
        reduction-order tolerance vs the model's stored vectors (the
        test mesh makes training encode through ring/Ulysses)."""
        from predictionio_tpu.ops.seqrec import (
            bucket_sequences,
            encode_users,
        )

        seed_chains(n_users=10, seed=5)
        engine = engine_factory()
        params = make_params(num_steps=30, seed=5)
        model = engine.train(CTX, params)[0]
        le = storage.get_levents()
        aid = storage.get_metadata_apps().get_by_name("seqapp").id
        for user in ("u0", "u3"):
            evs = sorted(le.find(aid, entity_id=user),
                         key=lambda e: e.event_time)
            cols = np.asarray(
                [model.item_map[e.target_entity_id] for e in evs],
                dtype=np.int64)
            rows = model.fold_in_rows([cols], [np.ones(len(cols),
                                                       np.float32)])
            uidx = model.user_map[user]
            # vs the single-device encode of the same sequence. The
            # fold pads its batch to 8 rows, the reference encodes 1:
            # two batch shapes are two compiled programs and owe no
            # common last bit (~2e-7 apart under jax 0.9.0) — the
            # bound is ~100x tighter than a bf16 pass would meet
            ref = encode_users(
                model.theta, bucket_sequences([cols], max_len=16), 1,
                model.enc_params)
            np.testing.assert_allclose(rows[0], ref[0],
                                       rtol=1e-5, atol=1e-6)
            # within SP tolerance vs the (mesh-encoded) stored vector
            np.testing.assert_allclose(rows[0],
                                       model.user_vectors[uidx],
                                       rtol=2e-4, atol=1e-5)


class TestDeployedServing:
    def test_deploy_query_and_zero_compile_gate(self, mem_storage,
                                                monkeypatch):
        """Deployed sequentialrec answers top-k through DeviceTopK with
        the steady-state zero-compile gate GREEN (jit-monitor asserted,
        not eyeballed) — the template inherits the AOT bucket ladder."""
        from predictionio_tpu.utils import metrics
        from predictionio_tpu.workflow import QueryServer, ServerConfig

        monkeypatch.setenv("PIO_SERVING_BACKEND", "device")
        seed_chains(seed=1)
        train_instance(seed=1)
        assert metrics.install_jit_compile_listener()
        srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0)).start(
            undeploy_stale=False)
        try:
            # warm request outside the gate (lazy HTTP-layer caches)
            status, result = _post(srv.address, "/queries.json",
                                   {"user": "u1", "num": 3})
            assert status == 200 and len(result["itemScores"]) == 3
            c0 = metrics.JIT_COMPILES.value()
            for u in range(2, 20):
                status, result = _post(srv.address, "/queries.json",
                                       {"user": f"u{u}",
                                        "num": 3 + (u % 8)})
                assert status == 200
                assert result["itemScores"]
            assert metrics.JIT_COMPILES.value() - c0 == 0, \
                "a steady-state sequentialrec query paid an XLA compile"
        finally:
            srv.stop()

    @pytest.mark.online
    def test_foldin_freshness_new_event_changes_topk(self, mem_storage,
                                                     monkeypatch):
        """The acceptance gate: a user's NEW event changes their served
        top-k within the default cadence — no retrain, no /reload. On
        the chain stream the change is DETERMINISTIC: after watching
        items a..b the model recommends b+1; one new view of item x
        moves the recommendation to x+1."""
        from predictionio_tpu.workflow import QueryServer, ServerConfig

        monkeypatch.setenv("PIO_FOLDIN_INTERVAL", "0.2")
        aid = seed_chains(seed=7)
        train_instance(seed=7, num_steps=200)
        srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0,
                                       foldin=True)).start(
            undeploy_stale=False)
        try:
            status, before = _post(srv.address, "/queries.json",
                                   {"user": "u2", "num": 5})
            assert status == 200 and before["itemScores"]
            # a fresh walk segment far from u2's history: the re-encode
            # must steer the top-k toward the new segment's successor
            le = storage.get_levents()
            before_top = [s["item"] for s in before["itemScores"]]
            new_items = [f"i{(int(before_top[0][1:]) + 15 + j) % N_ITEMS}"
                         for j in range(3)]
            for j, it in enumerate(new_items):
                le.insert(view_event("u2", it, minutes=10_000 + j), aid)
            expect = f"i{(int(new_items[-1][1:]) + 1) % N_ITEMS}"
            deadline = time.time() + 15
            changed = None
            while time.time() < deadline:
                status, after = _post(srv.address, "/queries.json",
                                      {"user": "u2", "num": 5})
                assert status == 200
                top = [s["item"] for s in after["itemScores"]]
                if top and top != before_top:
                    changed = top
                    break
                time.sleep(0.05)
            assert changed is not None, \
                "new event never changed the served top-k (no fold?)"
            assert expect in changed, (
                f"fold-in re-encode should recommend the new segment's "
                f"successor {expect}, got {changed}")
            # the new events are seen-masked out of the served list
            assert set(changed).isdisjoint(set(new_items))
        finally:
            srv.stop()

    @pytest.mark.online
    def test_new_user_servable_without_reload(self, mem_storage,
                                              monkeypatch):
        from predictionio_tpu.workflow import QueryServer, ServerConfig

        monkeypatch.setenv("PIO_FOLDIN_INTERVAL", "0.2")
        aid = seed_chains(seed=9)
        train_instance(seed=9)
        srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0,
                                       foldin=True)).start(
            undeploy_stale=False)
        try:
            status, result = _post(srv.address, "/queries.json",
                                   {"user": "fresh1"})
            assert status == 200 and result["itemScores"] == []
            le = storage.get_levents()
            for j in range(3):
                le.insert(view_event("fresh1", f"i{10 + j}",
                                     minutes=20_000 + j), aid)
            deadline = time.time() + 15
            result = None
            while time.time() < deadline:
                status, r = _post(srv.address, "/queries.json",
                                  {"user": "fresh1", "num": 5})
                assert status == 200
                if r["itemScores"]:
                    result = r
                    break
                time.sleep(0.05)
            assert result is not None, "fresh user never became servable"
            items = {s["item"] for s in result["itemScores"]}
            assert items.isdisjoint({"i10", "i11", "i12"})
        finally:
            srv.stop()


class TestRegistry:
    def test_template_listed(self, capsys):
        from predictionio_tpu.tools.template_commands import (
            BUILTIN_TEMPLATES,
            template_list,
        )

        assert "sequentialrec" in BUILTIN_TEMPLATES
        t = BUILTIN_TEMPLATES["sequentialrec"]
        assert t["engineFactory"] == FACTORY
        assert template_list() == 0
        out = capsys.readouterr().out
        assert "sequentialrec" in out

    def test_variant_params_resolve(self):
        """The registry variant's camelCase params must round-trip into
        the template's dataclasses (a stale registry entry would fail
        pio train at param-parse time)."""
        from predictionio_tpu.controller.engine import params_from_dict
        from predictionio_tpu.tools.template_commands import (
            BUILTIN_TEMPLATES,
        )

        variant = BUILTIN_TEMPLATES["sequentialrec"]["variant"]
        algo = variant["algorithms"][0]
        p = params_from_dict(SeqRecParams, algo["params"])
        assert p.rank == 32 and p.n_layers == 2 and p.num_steps == 300
        prep = params_from_dict(SeqPreparatorParams,
                                variant["preparator"]["params"])
        assert prep.max_seq_len == 32
