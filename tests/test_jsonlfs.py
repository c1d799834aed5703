"""Scale-ingest path: jsonlfs partitioned event store, streaming columnar
blocks (jsonlfs + sqlite keyset pagination), native value extraction, and
oracle equivalence against the generic events_to_columnar path."""

import datetime as dt

import numpy as np
import pytest

from predictionio_tpu.data.columnar import ColumnarEvents, events_to_columnar
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.jsonlfs import (
    JsonlFsLEvents,
    JsonlFsPEvents,
)
from predictionio_tpu.native import codec

UTC = dt.timezone.utc
APP = 1


def t(i):
    return dt.datetime(2020, 1, 1, 0, 0, 0, tzinfo=UTC) + \
        dt.timedelta(seconds=int(i))


def seed_events(n=25):
    evs = []
    for i in range(n):
        if i % 5 == 4:
            evs.append(Event(event="view", entity_type="user",
                             entity_id=f"u{i % 3}",
                             target_entity_type="item",
                             target_entity_id=f"i{i % 7}", event_time=t(i)))
        else:
            evs.append(Event(event="rate", entity_type="user",
                             entity_id=f"u{i % 3}",
                             target_entity_type="item",
                             target_entity_id=f"i{i % 7}",
                             properties={"rating": float(1 + i % 5)},
                             event_time=t(i)))
    return evs


@pytest.fixture
def store(tmp_path):
    pe = JsonlFsPEvents({"path": str(tmp_path / "ev"),
                         "part_max_events": 7})
    pe._l.init(APP)
    pe._l.insert_batch(seed_events(), APP)
    return pe


class TestPartitioning:
    def test_partitions_roll(self, store):
        parts = store._l._parts(store._l._dir(APP, None))
        assert len(parts) == 4  # 25 events / 7 per part
        assert all(p.endswith(".jsonl") for p in parts)

    def test_append_resumes_after_reopen(self, tmp_path):
        le = JsonlFsLEvents({"path": str(tmp_path / "ev"),
                             "part_max_events": 3})
        le.init(APP)
        le.insert_batch(seed_events(4), APP)
        # a fresh DAO (new process) keeps rolling where the old one left
        le2 = JsonlFsLEvents({"path": str(tmp_path / "ev"),
                              "part_max_events": 3})
        le2.insert_batch(seed_events(3), APP)
        parts = le2._parts(le2._dir(APP, None))
        assert len(parts) == 3
        assert len(list(le2.find(app_id=APP))) == 7


class TestTornAppendRecovery:
    """A killed writer leaves an unterminated final line; neither the
    next append nor any reader may be poisoned by it."""

    def _torn_store(self, tmp_path, n_good=4):
        le = JsonlFsLEvents({"path": str(tmp_path / "ev"),
                             "part_max_events": 100})
        le.init(APP)
        le.insert_batch(seed_events(n_good), APP)
        part = le._parts(le._dir(APP, None))[-1]
        with open(part, "a", encoding="utf-8") as f:
            f.write('{"event":"rate","entityType":"user","entityId"')
        return le, part

    def test_next_append_does_not_glue(self, tmp_path):
        le, part = self._torn_store(tmp_path)
        # a FRESH writer (simulating restart after the crash) appends
        le2 = JsonlFsLEvents({"path": str(tmp_path / "ev"),
                              "part_max_events": 100})
        le2.insert_batch(seed_events(3), APP)
        got = list(le2.find(app_id=APP))
        assert len(got) == 7  # 4 + 3; torn fragment is not an event
        # the repaired fragment is its own line, not glued to new JSON
        with open(part, encoding="utf-8") as f:
            lines = f.read().splitlines()
        assert sum(ln.endswith('"entityId"') for ln in lines) == 1

    def test_same_instance_append_repairs(self, tmp_path):
        le, part = self._torn_store(tmp_path)
        # same instance: cached writer state is invalidated by the size
        # check, the tail repaired, and the new batch lands cleanly
        le.insert_batch(seed_events(2), APP)
        assert len(list(le.find(app_id=APP))) == 6

    def test_readers_tolerate_torn_tail(self, tmp_path):
        le, part = self._torn_store(tmp_path)
        # typed reads skip the unterminated fragment without raising
        assert len(list(le.find(app_id=APP))) == 4
        # columnar reads too (both codec and oracle paths trim the tail)
        pe = JsonlFsPEvents({"path": str(tmp_path / "ev")})
        batch = pe.find_columnar(APP, value_property="rating")
        assert len(batch) == 4

    def test_delete_until_drops_terminated_fragment(self, tmp_path):
        le, part = self._torn_store(tmp_path)
        le._repair_tail(part)
        removed = le.delete_until(APP, t(2))
        # 2 pre-cutoff events + the unparsable fragment
        assert removed == 3
        assert len(list(le.find(app_id=APP))) == 2

    def test_second_writer_rolls_partitions_correctly(self, tmp_path):
        """Two live writer instances on one dir (eventserver + CLI
        import): neither overfills a partition from a stale cache."""
        a = JsonlFsLEvents({"path": str(tmp_path / "ev"),
                            "part_max_events": 3})
        b = JsonlFsLEvents({"path": str(tmp_path / "ev"),
                            "part_max_events": 3})
        a.init(APP)
        for i in range(4):
            a.insert_batch(seed_events(2), APP)
            b.insert_batch(seed_events(2), APP)
        d = a._dir(APP, None)
        for part in a._parts(d):
            with open(part, encoding="utf-8") as f:
                assert len(f.read().splitlines()) <= 3
        assert len(list(a.find(app_id=APP))) == 16


class TestEntityFilteredFind:
    """``find(entity_id=...)`` locates candidate lines by byte search
    (what keeps a fold-in gather off a typed parse of the whole store);
    it must return exactly what the typed full scan returns, however a
    line spells its id."""

    IDS = ["u1", "u12", "12", "True", 'q"uote', "back\\slash", "ünï",
           "u1 ", "[1]", "i3"]

    def test_matches_typed_full_scan_for_every_spelling(self, tmp_path):
        import json

        from predictionio_tpu.data.storage.jsonlfs import (
            _literal_searchable,
        )
        from predictionio_tpu.data.storage.memory import match_event

        le = JsonlFsLEvents({"path": str(tmp_path / "ev"),
                             "part_max_events": 7})
        le.init(APP)
        lines = []
        for n, eid in enumerate(self.IDS * 3):
            lines.append(json.dumps({
                "event": "rate", "entityType": "user", "entityId": eid,
                "eventId": f"e{n}", "targetEntityType": "item",
                # other ids show up as targets and property values too
                "targetEntityId": "u1" if n % 4 == 0 else "i3",
                "properties": {"rating": n % 5, "note": "u12"},
                "eventTime": "2020-01-01T00:00:00+00:00"},
                ensure_ascii=(n % 2 == 0)))
        # an id spelled as a JSON number, and one spelled with an escape
        lines.append('{"event":"rate","entityType":"user","entityId":12,'
                     '"eventId":"x1","targetEntityType":"item",'
                     '"targetEntityId":"i1",'
                     '"eventTime":"2020-01-01T00:00:00+00:00"}')
        lines.append('{"event":"rate","entityType":"user",'
                     '"entityId":"\\u00751","eventId":"x2",'
                     '"targetEntityType":"item","targetEntityId":"i1",'
                     '"eventTime":"2020-01-01T00:00:00+00:00"}')
        le.append_raw_lines(lines, APP)
        d = le._dir(APP, None)
        searched = 0
        for eid in self.IDS + ["nobody"]:
            got = [e.event_id for e in le.find(APP, entity_id=eid)]
            want = [e.event_id for e in le._iter_events(d)
                    if match_event(e, entity_id=eid)]
            assert got == want, eid
            searched += _literal_searchable(eid)
        assert [e.event_id for e in le.find(APP, entity_id="u1")][-1] \
            == "x2"  # the escaped spelling was found
        assert "x1" in [e.event_id for e in le.find(APP, entity_id="12")]
        assert 0 < searched < len(self.IDS) + 1  # both lanes exercised

    def test_unterminated_tail_is_not_an_event(self, tmp_path):
        le = JsonlFsLEvents({"path": str(tmp_path / "ev")})
        le.init(APP)
        le.insert_batch(seed_events(4), APP)
        part = le._parts(le._dir(APP, None))[0]
        with open(part, "ab") as f:  # a racing append's partial flush
            f.write(b'{"event":"rate","entityType":"user",'
                    b'"entityId":"u0","targetEnt')
        # a partition a writer has rolled to and not yet written
        open(part.replace("part-00000", "part-00001"), "wb").close()
        assert len(list(le.find(APP, entity_id="u0"))) == 2


class TestColumnar:
    def test_matches_generic_oracle(self, store):
        got = store.find_columnar(
            APP, entity_type="user", event_names=["rate", "view"],
            target_entity_type="item", value_property="rating",
            default_value=1.0)
        want = events_to_columnar(
            store.find(APP, entity_type="user",
                       event_names=["rate", "view"],
                       target_entity_type="item"),
            value_property="rating", default_value=1.0)
        assert len(got) == len(want) == 25
        assert got.entity_ids.tolist() == want.entity_ids.tolist()
        assert got.target_ids.tolist() == want.target_ids.tolist()
        np.testing.assert_allclose(got.values, want.values)
        np.testing.assert_allclose(got.event_times, want.event_times)

    def test_filters(self, store):
        rates = store.find_columnar(APP, event_names=["rate"],
                                    value_property="rating")
        assert len(rates) == 20
        assert set(rates.events.tolist()) == {"rate"}
        window = store.find_columnar(APP, start_time=t(5), until_time=t(10))
        assert len(window) == 5

    def test_strict_non_numeric_raises(self, tmp_path):
        pe = JsonlFsPEvents({"path": str(tmp_path / "ev")})
        pe._l.init(APP)
        pe._l.insert(Event(event="rate", entity_type="user", entity_id="u1",
                           target_entity_type="item", target_entity_id="i1",
                           properties={"rating": "five"}, event_time=t(0)),
                     APP)
        with pytest.raises(ValueError, match="non-numeric"):
            pe.find_columnar(APP, value_property="rating")
        lenient = pe.find_columnar(APP, value_property="rating",
                                   default_value=2.5, strict=False)
        assert lenient.values.tolist() == [2.5]

    def test_fallback_lines_reparsed_by_oracle(self, tmp_path):
        """A raw line the C++ codec punts on (numeric float entityId)
        still comes back, via the python oracle, with str() coercion."""
        pe = JsonlFsPEvents({"path": str(tmp_path / "ev")})
        pe._l.init(APP)
        pe._l.insert_batch(seed_events(3), APP)
        pe._l.append_raw_lines(
            ['{"event":"rate","entityType":"user","entityId":1.5,'
             '"targetEntityType":"item","targetEntityId":"i9",'
             '"properties":{"rating":4},'
             '"eventTime":"2020-01-01T00:09:00+00:00"}'], APP)
        batch = pe.find_columnar(APP, value_property="rating")
        assert len(batch) == 4
        assert "1.5" in batch.entity_ids.tolist()
        row = batch.entity_ids.tolist().index("1.5")
        assert batch.values[row] == 4.0


class TestBlocks:
    def test_jsonlfs_blocks_bounded_and_complete(self, store):
        blocks = list(store.find_columnar_blocks(
            APP, value_property="rating", block_size=5))
        assert all(len(b) <= 5 for b in blocks)
        whole = ColumnarEvents.concat(blocks)
        assert len(whole) == 25
        # storage order == insertion order here (ascending times)
        assert np.all(np.diff(whole.event_times) >= 0)

    def test_sqlite_blocks_keyset_pagination(self, tmp_path):
        from predictionio_tpu.data.storage.sqlite import SqlitePEvents

        pe = SqlitePEvents({"path": str(tmp_path / "ev.db")})
        pe._l.init(APP)
        pe._l.insert_batch(seed_events(), APP)
        blocks = list(pe.find_columnar_blocks(
            APP, event_names=["rate"], value_property="rating",
            block_size=6))
        assert all(len(b) <= 6 for b in blocks)
        whole = ColumnarEvents.concat(blocks)
        want = pe.find_columnar(APP, event_names=["rate"],
                                value_property="rating")
        assert len(whole) == len(want) == 20
        assert sorted(whole.entity_ids.tolist()) == \
            sorted(want.entity_ids.tolist())
        np.testing.assert_allclose(np.sort(whole.values),
                                   np.sort(want.values))

    def test_base_default_blocks(self):
        from predictionio_tpu.data.storage.memory import MemLEvents
        from predictionio_tpu.data.storage.base import LEventsBackedPEvents

        le = MemLEvents()
        le.init(APP)
        le.insert_batch(seed_events(), APP)
        pe = LEventsBackedPEvents(le)
        blocks = list(pe.find_columnar_blocks(APP, value_property="rating",
                                              block_size=10))
        assert [len(b) for b in blocks] == [10, 10, 5]


class TestEncodedBlocks:
    """The dictionary-encoded fast lane: jsonlfs blocks carry int32
    codes + distinct labels, zero per-event Python strings."""

    pytestmark = pytest.mark.skipif(
        not codec.is_available(),
        reason="native codec unavailable (encoded fast lane inactive)")

    def test_blocks_are_encoded_and_materialize_to_oracle(self, store):
        blocks = list(store.find_columnar_blocks(
            APP, value_property="rating", block_size=10))
        assert all(b.is_encoded for b in blocks)
        assert all(b.entity_ids is None for b in blocks)
        whole = ColumnarEvents.concat(blocks)  # materializes
        want = store.find_columnar(APP, value_property="rating")
        assert sorted(zip(whole.entity_ids.tolist(),
                          whole.target_ids.tolist(),
                          whole.values.tolist())) == \
            sorted(zip(want.entity_ids.tolist(),
                       want.target_ids.tolist(),
                       want.values.tolist()))

    def test_encoded_filters_match_object_path(self, store):
        enc = ColumnarEvents.concat(list(store.find_columnar_blocks(
            APP, event_names=["rate"], entity_type="user",
            target_entity_type="item", value_property="rating",
            block_size=9)))
        assert len(enc) == 20
        assert set(enc.events.tolist()) == {"rate"}

    def test_missing_target_code_is_none_after_materialize(self, tmp_path):
        pe = JsonlFsPEvents({"path": str(tmp_path / "ev")})
        pe._l.init(APP)
        pe._l.insert_batch(
            [Event(event="$set", entity_type="user", entity_id="u1",
                   properties={"x": 1}, event_time=t(0)),
             Event(event="rate", entity_type="user", entity_id="u1",
                   target_entity_type="item", target_entity_id="i1",
                   properties={"rating": 3}, event_time=t(1))], APP)
        [block] = list(pe.find_columnar_blocks(APP))
        assert block.is_encoded
        mat = block.materialize()
        assert mat.target_ids.tolist() == [None, "i1"]
        dropped = block.drop_missing_targets()
        assert len(dropped) == 1

    def test_encode_entities_on_encoded_block(self, store):
        blocks = list(store.find_columnar_blocks(
            APP, event_names=["rate"], target_entity_type="item",
            block_size=100))
        block = next(b for b in blocks if len(b))
        umap, imap, rows, cols = block.encode_entities()
        assert len(rows) == len(block)
        assert set(umap.decode(rows)) <= {"u0", "u1", "u2"}


class TestStreamingBuilder:
    def test_matches_single_scan_encoding(self, store):
        """Blocks through the incremental indexer == one-shot
        encode_entities on the full scan (same triples, same maps up to
        label order)."""
        from predictionio_tpu.data.columnar import StreamingRatingsBuilder

        builder = StreamingRatingsBuilder()
        for block in store.find_columnar_blocks(
                APP, value_property="rating", block_size=4):
            builder.add_block(block)
        user_map, item_map, rows, cols, vals = builder.finalize()
        assert builder.n_events == len(rows) == 25

        whole = store.find_columnar(APP, value_property="rating")
        # decode both back to strings: identical (user, item, value) bags
        streamed = sorted(zip(user_map.decode(rows).tolist(),
                              item_map.decode(cols).tolist(),
                              vals.tolist()))
        scanned = sorted(zip(whole.entity_ids.tolist(),
                             whole.target_ids.tolist(),
                             whole.values.tolist()))
        assert streamed == scanned

    def test_filtered_rows_never_register_phantom_entities(self, tmp_path):
        """A part's label table spans the WHOLE file; rows dropped by a
        filter must not leak their entities into the builder maps
        (regression: encoded-path label merge)."""
        from predictionio_tpu.data.columnar import StreamingRatingsBuilder

        pe = JsonlFsPEvents({"path": str(tmp_path / "ev")})
        pe._l.init(APP)
        pe._l.insert_batch(
            [Event(event="rate", entity_type="user", entity_id="u1",
                   target_entity_type="item", target_entity_id="i1",
                   properties={"rating": 3}, event_time=t(0)),
             Event(event="view", entity_type="user", entity_id="ghost",
                   target_entity_type="item", target_entity_id="phantom",
                   event_time=t(1)),
             Event(event="$set", entity_type="user", entity_id="setter",
                   properties={"x": 1}, event_time=t(2))], APP)
        b = StreamingRatingsBuilder()
        for block in pe.find_columnar_blocks(
                APP, event_names=["rate"], target_entity_type="item",
                value_property="rating"):
            b.add_block(block)
        user_map, item_map, rows, cols, vals = b.finalize()
        assert user_map.labels.tolist() == ["u1"]
        assert item_map.labels.tolist() == ["i1"]
        assert len(rows) == 1

    def test_drops_rows_without_target(self):
        from predictionio_tpu.data.columnar import (
            ColumnarEvents, StreamingRatingsBuilder,
        )

        block = ColumnarEvents(
            entity_ids=np.asarray(["a", "b"], dtype=object),
            target_ids=np.asarray(["x", None], dtype=object),
            values=np.asarray([1.0, 2.0], dtype=np.float32),
            event_times=np.zeros(2))
        b = StreamingRatingsBuilder()
        b.add_block(block)
        user_map, item_map, rows, cols, vals = b.finalize()
        assert b.n_events == 1 and rows.tolist() == [0]
        assert user_map.decode(rows).tolist() == ["a"]


class TestStreamingTrainE2E:
    def test_template_trains_from_jsonlfs_blocks(self, tmp_path,
                                                 monkeypatch):
        """Full DASE train over the jsonlfs backend with the streaming
        ingest path (streaming_block_size set): the engine never calls
        the single-scan read and the model serves."""
        from predictionio_tpu.controller import ComputeContext, EngineParams
        from predictionio_tpu.data import storage
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.ops.als import ALSParams
        from predictionio_tpu.templates.recommendation import (
            DataSourceParams, Query, engine_factory,
        )

        cfg = storage.StorageConfig(
            sources={"EV": {"type": "jsonlfs",
                            "path": str(tmp_path / "events"),
                            "part_max_events": 40},
                     "META": {"type": "memory"}},
            repositories={"EVENTDATA": "EV", "METADATA": "META",
                          "MODELDATA": "META"})
        storage.reset(cfg)
        try:
            aid = storage.get_metadata_apps().insert(App(0, "bigapp"))
            le = storage.get_levents()
            le.init(aid)
            rng = np.random.default_rng(1)
            evs = []
            for u in range(20):
                for _ in range(8):
                    evs.append(Event(
                        event="rate", entity_type="user", entity_id=f"u{u}",
                        target_entity_type="item",
                        target_entity_id=f"i{rng.integers(0, 12)}",
                        properties={"rating": float(rng.integers(1, 6))},
                        event_time=t(u)))
            le.insert_batch(evs, aid)

            engine = engine_factory()
            params = EngineParams(
                data_source_params=("", DataSourceParams(
                    app_name="bigapp", streaming_block_size=30)),
                algorithm_params_list=[
                    ("als", ALSParams(rank=4, num_iterations=2, seed=0))])
            persistable = engine.train(ComputeContext(), params, "big1")
            [model] = engine.prepare_deploy(ComputeContext(), params,
                                            "big1", persistable)
            algo = engine._algorithms(params)[0]
            res = algo.predict(model, Query(user="u1", num=3))
            assert 0 < len(res.item_scores) <= 3
        finally:
            storage.reset()

    def test_streaming_plus_bucketed_preparator(self, tmp_path):
        """The full scale recipe: jsonlfs store -> threaded streaming
        blocks -> bucketed layout -> sharded-capable training -> serve.
        The model must give the predictions of the plain-numpy trainer
        over the same events."""
        from als_reference import numpy_train_als
        from predictionio_tpu.controller import ComputeContext, EngineParams
        from predictionio_tpu.data import storage
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.ops.als import ALSParams
        from predictionio_tpu.templates.recommendation import (
            DataSourceParams, PreparatorParams, Query, engine_factory,
        )

        cfg = storage.StorageConfig(
            sources={"EV": {"type": "jsonlfs",
                            "path": str(tmp_path / "events"),
                            "part_max_events": 50},
                     "META": {"type": "memory"}},
            repositories={"EVENTDATA": "EV", "METADATA": "META",
                          "MODELDATA": "META"})
        storage.reset(cfg)
        try:
            aid = storage.get_metadata_apps().insert(App(0, "bigapp"))
            le = storage.get_levents()
            le.init(aid)
            rng = np.random.default_rng(2)
            evs = [Event(
                event="rate", entity_type="user",
                entity_id=f"u{rng.integers(0, 25)}",
                target_entity_type="item",
                target_entity_id=f"i{rng.integers(0, 15)}",
                properties={"rating": float(rng.integers(1, 6))},
                event_time=t(i)) for i in range(200)]
            le.insert_batch(evs, aid)

            engine = engine_factory()

            als = ALSParams(rank=4, num_iterations=2, seed=0)
            params = EngineParams(
                data_source_params=("", DataSourceParams(
                    app_name="bigapp", streaming_block_size=64)),
                preparator_params=("", PreparatorParams()),
                algorithm_params_list=[("als", als)])
            persistable = engine.train(ComputeContext(), params, "x")
            [model] = engine.prepare_deploy(ComputeContext(), params,
                                            "x", persistable)
            algo = engine._algorithms(params)[0]
            got = algo.predict(model, Query(user="u1", num=5))

            rows = [model.user_map[e.entity_id] for e in evs]
            cols = [model.item_map[e.target_entity_id] for e in evs]
            vals = [e.properties["rating"] for e in evs]
            X, Y = numpy_train_als(rows, cols, vals, len(model.user_map),
                                   len(model.item_map), als)
            u1 = model.user_map["u1"]
            scores = X[u1] @ Y.T
            scores[[c for r, c in zip(rows, cols) if r == u1]] = -np.inf
            top = np.argsort(-scores)[:5]
            assert [s.item for s in got.item_scores] == \
                list(model.item_map.decode(top))
            np.testing.assert_allclose(
                [s.score for s in got.item_scores], scores[top],
                rtol=1e-3)
        finally:
            storage.reset()
