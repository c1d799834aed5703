"""Backend-parametrized storage conformance suite.

Mirrors the reference pattern of one shared behavior suite run against every
backend (``LEventsSpec.scala:22-66`` — "Events can be implemented by:
HBLEvents / JDBCLEvents"). Here: memory, sqlite, and jsonlfs (events-only —
its metadata DAOs are memory stand-ins, so only the LEvents classes add
coverage on that row; a small part_max_events forces multi-partition
behavior through every test).
"""

import datetime as dt

import pytest

from predictionio_tpu.data.event import Event, EventValidationError
from predictionio_tpu.data.storage.base import (
    UNSET, AccessKey, App, Channel, EngineInstance, EvaluationInstance, Model,
)
from predictionio_tpu.data.storage.memory import (
    MemAccessKeys, MemApps, MemChannels, MemEngineInstances,
    MemEvaluationInstances, MemLEvents, MemModels,
)
from predictionio_tpu.data.storage.sqlite import (
    SqliteAccessKeys, SqliteApps, SqliteChannels, SqliteEngineInstances,
    SqliteEvaluationInstances, SqliteLEvents, SqliteModels,
)

UTC = dt.timezone.utc
APP = 1


@pytest.fixture(params=["memory", "sqlite", "jsonlfs", "resthttp"])
def backend(request, tmp_path):
    if request.param == "resthttp":
        # the networked lane: a live event server holding the data in
        # its OWN directory, storage-wire DAOs speaking HTTP to it —
        # the same behavior suite must pass over the wire
        from predictionio_tpu.data import storage as storage_mod
        from predictionio_tpu.data.api.event_server import (
            EventServer, EventServerConfig,
        )
        from predictionio_tpu.data.storage.resthttp import RestLEvents

        server_reg = storage_mod.StorageRegistry(storage_mod.StorageConfig(
            sources={"EV": {"type": "jsonlfs",
                            "path": str(tmp_path / "server_events"),
                            "part_max_events": 3},
                     "META": {"type": "memory"}},
            repositories={"EVENTDATA": "EV", "METADATA": "META",
                          "MODELDATA": "META"}))
        server = EventServer(
            EventServerConfig(ip="127.0.0.1", port=0,
                              service_key="conf-secret"),
            reg=server_reg).start()
        host, port = server.address
        cfg = {"url": f"http://{host}:{port}",
               "service_key": "conf-secret"}
        made = {
            "levents": RestLEvents(cfg), "apps": MemApps({}),
            "access_keys": MemAccessKeys({}), "channels": MemChannels({}),
            "engine_instances": MemEngineInstances({}),
            "evaluation_instances": MemEvaluationInstances({}),
            "models": MemModels({}),
        }
        yield made
        server.stop()
        return
    if request.param == "jsonlfs":
        from predictionio_tpu.data.storage.jsonlfs import JsonlFsLEvents

        make = {
            "levents": lambda cfg: JsonlFsLEvents(
                {"path": str(tmp_path / "events"), "part_max_events": 3}),
            "apps": MemApps, "access_keys": MemAccessKeys,
            "channels": MemChannels,
            "engine_instances": MemEngineInstances,
            "evaluation_instances": MemEvaluationInstances,
            "models": MemModels,
        }
        cfg = {}
    elif request.param == "memory":
        make = {
            "levents": MemLEvents, "apps": MemApps,
            "access_keys": MemAccessKeys, "channels": MemChannels,
            "engine_instances": MemEngineInstances,
            "evaluation_instances": MemEvaluationInstances,
            "models": MemModels,
        }
        cfg = {}
    else:
        make = {
            "levents": SqliteLEvents, "apps": SqliteApps,
            "access_keys": SqliteAccessKeys, "channels": SqliteChannels,
            "engine_instances": SqliteEngineInstances,
            "evaluation_instances": SqliteEvaluationInstances,
            "models": SqliteModels,
        }
        cfg = {"path": str(tmp_path / f"conf_{request.param}.db")}
    yield {k: v(cfg) for k, v in make.items()}


def t(i):
    return dt.datetime(2020, 1, 1, 0, 0, i, tzinfo=UTC)


def mk(i, name="rate", etype="user", eid="u1", **kw):
    return Event(event=name, entity_type=etype, entity_id=eid,
                 event_time=t(i), **kw)


class TestLEvents:
    def test_insert_get_delete(self, backend):
        le = backend["levents"]
        le.init(APP)
        eid = le.insert(mk(1, properties={"rating": 5}), APP)
        got = le.get(eid, APP)
        assert got is not None
        assert got.event_id == eid
        assert got.properties.get("rating", int) == 5
        assert le.delete(eid, APP)
        assert le.get(eid, APP) is None
        assert not le.delete(eid, APP)

    def test_insert_validates(self, backend):
        le = backend["levents"]
        le.init(APP)
        with pytest.raises(EventValidationError):
            le.insert(mk(1, name="$bogus"), APP)

    def test_find_time_range_is_half_open(self, backend):
        le = backend["levents"]
        le.init(APP)
        for i in range(5):
            le.insert(mk(i), APP)
        out = list(le.find(APP, start_time=t(1), until_time=t(3)))
        assert [e.event_time for e in out] == [t(1), t(2)]

    def test_find_filters(self, backend):
        le = backend["levents"]
        le.init(APP)
        le.insert(mk(1, name="rate", eid="u1", target_entity_type="item",
                     target_entity_id="i1"), APP)
        le.insert(mk(2, name="view", eid="u1", target_entity_type="item",
                     target_entity_id="i2"), APP)
        le.insert(mk(3, name="rate", eid="u2"), APP)
        assert len(list(le.find(APP, event_names=["rate"]))) == 2
        assert len(list(le.find(APP, entity_id="u1"))) == 2
        assert len(list(le.find(APP, target_entity_id="i2"))) == 1
        # explicit None target filter matches only events without target
        assert len(list(le.find(APP, target_entity_type=None))) == 1
        # UNSET means no filter at all
        assert len(list(le.find(APP, target_entity_type=UNSET))) == 3

    def test_find_limit_and_reversed(self, backend):
        le = backend["levents"]
        le.init(APP)
        for i in range(5):
            le.insert(mk(i), APP)
        out = list(le.find(APP, limit=2))
        assert [e.event_time for e in out] == [t(0), t(1)]
        out = list(le.find(APP, limit=2, reversed=True))
        assert [e.event_time for e in out] == [t(4), t(3)]

    def test_channel_isolation(self, backend):
        le = backend["levents"]
        le.init(APP)
        le.init(APP, 7)
        le.insert(mk(1), APP)
        le.insert(mk(2), APP, 7)
        assert len(list(le.find(APP))) == 1
        assert len(list(le.find(APP, channel_id=7))) == 1

    def test_app_isolation_and_remove(self, backend):
        le = backend["levents"]
        le.init(1)
        le.init(2)
        le.insert(mk(1), 1)
        le.insert(mk(1), 2)
        le.remove(1)
        assert len(list(le.find(1))) == 0
        assert len(list(le.find(2))) == 1

    def test_insert_batch(self, backend):
        le = backend["levents"]
        le.init(APP)
        ids = le.insert_batch([mk(i) for i in range(3)], APP)
        assert len(ids) == len(set(ids)) == 3
        assert len(list(le.find(APP))) == 3
        assert le.get(ids[0], APP) is not None

    def test_delete_until(self, backend):
        """Bulk pre-cutoff removal (cleanup-app capability) across every
        backend: events before the cutoff go, the rest stay readable,
        channel isolation holds."""
        le = backend["levents"]
        le.init(APP)
        le.init(APP, 0)
        le.insert_batch([mk(i) for i in range(6)], APP)       # t(0)..t(5)
        le.insert(mk(1), APP, 0)  # other channel, pre-cutoff
        removed = le.delete_until(APP, t(3), None)
        assert removed == 3
        rest = list(le.find(APP))
        assert len(rest) == 3
        assert min(e.event_time for e in rest) == t(3)
        # the other channel was untouched
        assert len(list(le.find(APP, channel_id=0))) == 1
        # idempotent: nothing left before the cutoff
        assert le.delete_until(APP, t(3), None) == 0
        # appends after a cleanup still work (jsonlfs writer recount)
        le.insert(mk(9), APP)
        assert len(list(le.find(APP))) == 4

    def test_aggregate_properties(self, backend):
        le = backend["levents"]
        le.init(APP)
        le.insert(Event(event="$set", entity_type="user", entity_id="u1",
                        properties={"a": 1, "b": 2}, event_time=t(1)), APP)
        le.insert(Event(event="$unset", entity_type="user", entity_id="u1",
                        properties={"b": 0}, event_time=t(2)), APP)
        le.insert(Event(event="$set", entity_type="item", entity_id="i1",
                        properties={"c": 3}, event_time=t(1)), APP)
        out = le.aggregate_properties(APP, "user")
        assert set(out) == {"u1"}
        assert out["u1"].fields == {"a": 1}
        out = le.aggregate_properties(APP, "user", required=["missing"])
        assert out == {}


class TestMetadata:
    def test_apps(self, backend):
        apps = backend["apps"]
        aid = apps.insert(App(0, "myapp", "desc"))
        assert aid
        assert apps.get(aid).name == "myapp"
        assert apps.get_by_name("myapp").id == aid
        assert apps.insert(App(0, "myapp")) is None  # duplicate name
        assert apps.update(App(aid, "renamed", None))
        assert apps.get_by_name("renamed") is not None
        assert [a.id for a in apps.get_all()] == [aid]
        assert apps.delete(aid)
        assert apps.get(aid) is None

    def test_apps_explicit_id_conflict(self, backend):
        apps = backend["apps"]
        assert apps.insert(App(5, "one")) == 5
        # requested id already taken -> None in EVERY backend
        assert apps.insert(App(5, "two")) is None
        assert apps.get_by_name("two") is None

    def test_channels_explicit_id(self, backend):
        ch = backend["channels"]
        assert ch.insert(Channel(9, "mobile", 12)) == 9
        assert ch.get(9).name == "mobile"
        assert ch.insert(Channel(9, "web", 12)) is None

    def test_access_keys(self, backend):
        ak = backend["access_keys"]
        key = ak.insert(AccessKey("", 12, ("rate",)))
        assert len(key) >= 48
        got = ak.get(key)
        assert got.appid == 12 and got.events == ("rate",)
        assert ak.get_by_appid(12)[0].key == key
        assert ak.update(AccessKey(key, 12, ()))
        assert ak.get(key).events == ()
        assert ak.delete(key)
        assert ak.get(key) is None

    def test_channels(self, backend):
        ch = backend["channels"]
        cid = ch.insert(Channel(0, "mobile", 12))
        assert cid
        assert ch.get(cid).name == "mobile"
        assert ch.insert(Channel(0, "bad name!", 12)) is None
        assert [c.id for c in ch.get_by_appid(12)] == [cid]
        assert ch.delete(cid)

    def test_engine_instances(self, backend):
        ei = backend["engine_instances"]
        base = EngineInstance(
            id="", status="INIT", start_time=t(1), end_time=t(1),
            engine_id="e", engine_version="1", engine_variant="default.json",
            engine_factory="f")
        import dataclasses
        iid = ei.insert(base)
        assert ei.get(iid).status == "INIT"
        ei.update(dataclasses.replace(ei.get(iid), status="COMPLETED",
                                      end_time=t(2)))
        iid2 = ei.insert(dataclasses.replace(base, start_time=t(5)))
        ei.update(dataclasses.replace(ei.get(iid2), status="COMPLETED"))
        latest = ei.get_latest_completed("e", "1", "default.json")
        assert latest.id == iid2  # newest start_time wins
        assert len(ei.get_completed("e", "1", "default.json")) == 2
        assert ei.delete(iid)
        assert ei.get(iid) is None

    def test_evaluation_instances(self, backend):
        evi = backend["evaluation_instances"]
        iid = evi.insert(EvaluationInstance(
            id="", status="INIT", start_time=t(1), end_time=t(1)))
        import dataclasses
        evi.update(dataclasses.replace(
            evi.get(iid), status="EVALCOMPLETED", evaluator_results="ok"))
        assert evi.get_completed()[0].evaluator_results == "ok"
        assert evi.delete(iid)

    def test_models(self, backend):
        m = backend["models"]
        m.insert(Model("m1", b"\x00\x01bytes"))
        assert m.get("m1").models == b"\x00\x01bytes"
        assert m.delete("m1")
        assert m.get("m1") is None


class TestLocalFSModels:
    """MODELDATA-only filesystem backend (LocalFSModels.scala analog)."""

    def _store(self, tmp_path):
        from predictionio_tpu.data.storage.localfs import LocalFSModels
        return LocalFSModels({"path": str(tmp_path / "models")})

    def test_roundtrip_and_overwrite(self, tmp_path):
        m = self._store(tmp_path)
        m.insert(Model("m1", b"v1"))
        m.insert(Model("m1", b"v2"))  # keyed upsert like the DB backends
        assert m.get("m1").models == b"v2"
        assert m.delete("m1")
        assert not m.delete("m1")
        assert m.get("m1") is None

    def test_id_sanitization(self, tmp_path):
        m = self._store(tmp_path)
        m.insert(Model("../../evil", b"x"))
        # blob stays inside the store directory
        import os
        assert not os.path.exists(tmp_path / "evil")
        assert m.get("../../evil").models == b"x"

    def test_registry_binding(self, tmp_path, monkeypatch):
        from predictionio_tpu.data import storage
        from predictionio_tpu.data.storage.base import StorageError

        monkeypatch.setenv("PIO_STORAGE_SOURCES_DB_TYPE", "memory")
        monkeypatch.setenv("PIO_STORAGE_SOURCES_FS_TYPE", "localfs")
        monkeypatch.setenv("PIO_STORAGE_SOURCES_FS_PATH",
                           str(tmp_path / "fsmodels"))
        monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_METADATA_SOURCE", "DB")
        monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE", "DB")
        monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE", "FS")
        storage.reset()
        try:
            models = storage.get_model_data_models()
            models.insert(Model("mm", b"blob"))
            assert list((tmp_path / "fsmodels").glob("pio_model_mm_*"))
            assert models.get("mm").models == b"blob"
            # binding EVENTDATA to the fs source must fail loudly
            monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE",
                               "FS")
            storage.reset()
            with pytest.raises(StorageError, match="does not support"):
                storage.get_levents()
        finally:
            storage.reset()


class TestSqliteConcurrency:
    """':memory:' must be one shared database across threads."""

    def test_memory_db_shared_across_threads(self):
        import threading
        from predictionio_tpu.data.storage.sqlite import (
            SqliteClient, SqliteLEvents)
        SqliteClient.shutdown_all()
        le = SqliteLEvents({})  # default :memory:
        le.init(APP)
        le.insert(mk(0), APP)
        errors = []

        def worker(i):
            try:
                le.insert(mk(i + 1), APP)
                assert len(list(le.find(APP))) >= 2
            except Exception as e:  # pragma: no cover - failure path
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert errors == []
        assert len(list(le.find(APP))) == 5
        SqliteClient.shutdown_all()

    def test_file_db_shared_across_threads(self, tmp_path):
        import threading
        from predictionio_tpu.data.storage.sqlite import (
            SqliteClient, SqliteLEvents)
        le = SqliteLEvents({"path": str(tmp_path / "threads.db")})
        le.init(APP)

        def worker(i):
            le.insert(mk(i), APP)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert len(list(le.find(APP))) == 8
        SqliteClient.shutdown_all()

    def test_dao_close_does_not_break_sibling_daos(self, tmp_path):
        from predictionio_tpu.data.storage.sqlite import (
            SqliteApps, SqliteLEvents)
        cfg = {"path": str(tmp_path / "shared.db")}
        le, apps = SqliteLEvents(cfg), SqliteApps(cfg)
        aid = apps.insert(App(0, "alive"))
        le.close()  # no-op at DAO level
        assert apps.get(aid).name == "alive"


class TestScanSnapshot:
    """find() must give snapshot semantics: writing while iterating must
    not change (or break) the rows the scan yields."""

    @pytest.mark.parametrize("kind", ["memory_backend", "sqlite_file",
                                      "sqlite_memory"])
    def test_write_while_iterating(self, kind, tmp_path):
        from predictionio_tpu.data.storage.memory import MemLEvents
        from predictionio_tpu.data.storage.sqlite import (
            SqliteClient, SqliteLEvents)
        if kind == "memory_backend":
            le = MemLEvents({})
        elif kind == "sqlite_file":
            le = SqliteLEvents({"path": str(tmp_path / "snap.db")})
        else:
            SqliteClient.shutdown_all()
            le = SqliteLEvents({})
        le.init(APP)
        for i in range(20):
            le.insert(mk(i, eid=f"u{i}"), APP)
        seen = []
        for ev in le.find(APP):
            seen.append(ev.entity_id)
            # interleaved write through the same DAO/connection
            le.insert(Event(
                event="rate", entity_type="user",
                entity_id=f"new{len(seen)}",
                event_time=dt.datetime(2020, 1, 2, tzinfo=UTC)
                + dt.timedelta(seconds=len(seen))), APP)
        assert seen == [f"u{i}" for i in range(20)]
        assert len(list(le.find(APP))) == 40
        if kind != "memory_backend":
            SqliteClient.shutdown_all()


class TestRegistryAndFacades:
    def test_env_config_parsing(self, monkeypatch):
        from predictionio_tpu.data.storage import StorageConfig
        cfg = StorageConfig.from_env({
            "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
            "PIO_STORAGE_SOURCES_SQL_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_SQL_PATH": "/tmp/x.db",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQL",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQL",
        })
        assert cfg.sources["SQL"]["path"] == "/tmp/x.db"
        assert cfg.repositories["METADATA"] == "SQL"
        assert cfg.repositories["EVENTDATA"] == "MEM"
        assert cfg.repositories["MODELDATA"] == "SQL"

    def test_unbound_repo_with_multiple_sources_raises(self):
        from predictionio_tpu.data.storage import StorageConfig
        from predictionio_tpu.data.storage.base import StorageError
        with pytest.raises(StorageError, match="MODELDATA"):
            StorageConfig.from_env({
                "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
                "PIO_STORAGE_SOURCES_SQL_TYPE": "sqlite",
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQL",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
            })

    def test_single_source_auto_binds(self):
        from predictionio_tpu.data.storage import StorageConfig
        cfg = StorageConfig.from_env({
            "PIO_STORAGE_SOURCES_ONLY_TYPE": "memory",
        })
        assert all(src == "ONLY" for src in cfg.repositories.values())

    def test_unknown_backend_type(self):
        from predictionio_tpu.data.storage import StorageConfig
        from predictionio_tpu.data.storage.base import StorageError
        with pytest.raises(StorageError):
            StorageConfig.from_env({"PIO_STORAGE_SOURCES_X_TYPE": "hbase9"})

    def test_verify_all_data_objects(self, mem_storage):
        mem_storage.verify_all_data_objects()

    def test_store_facades(self, mem_storage):
        from predictionio_tpu.data import storage
        from predictionio_tpu.data.store import (
            LEventStore, PEventStore, app_name_to_id)
        apps = storage.get_metadata_apps()
        aid = apps.insert(App(0, "fapp"))
        assert app_name_to_id("fapp") == (aid, None)
        with pytest.raises(ValueError):
            app_name_to_id("nope")
        le = storage.get_levents()
        le.init(aid)
        le.insert(mk(1, eid="u9", properties={"rating": 3}), aid)
        le.insert(Event(event="$set", entity_type="user", entity_id="u9",
                        properties={"vip": True}, event_time=t(2)), aid)
        evs = PEventStore.find("fapp", event_names=["rate"])
        assert len(evs) == 1
        props = PEventStore.aggregate_properties("fapp", "user")
        assert props["u9"].get("vip", bool) is True
        evs = LEventStore.find_by_entity("fapp", "user", "u9", limit=1)
        assert len(evs) == 1
