"""CLI tests: pio status / app verbs (Console.scala parity, growing)."""

import pytest

from predictionio_tpu.data import storage
from predictionio_tpu.tools.cli import main


class TestCli:
    def test_version(self, capsys):
        assert main(["version"]) == 0
        from predictionio_tpu import __version__
        assert capsys.readouterr().out.strip() == __version__

    def test_status(self, mem_storage, capsys):
        assert main(["status"]) == 0
        out = capsys.readouterr().out
        assert "ready to go" in out

    def test_app_lifecycle(self, mem_storage, capsys):
        assert main(["app", "new", "myapp", "--description", "d"]) == 0
        out = capsys.readouterr().out
        assert "Access Key:" in out
        app = storage.get_metadata_apps().get_by_name("myapp")
        assert app is not None
        keys = storage.get_metadata_access_keys().get_by_appid(app.id)
        assert len(keys) == 1

        assert main(["app", "new", "myapp"]) == 1  # duplicate

        assert main(["app", "list"]) == 0
        assert "myapp" in capsys.readouterr().out

        assert main(["app", "show", "myapp"]) == 0
        assert main(["app", "show", "nope"]) == 1
        capsys.readouterr()

        # data-delete wipes events but keeps the app
        from predictionio_tpu.data.event import Event
        le = storage.get_levents()
        le.insert(Event(event="rate", entity_type="user", entity_id="u",
                        target_entity_type="item", target_entity_id="i"),
                  app.id)
        assert main(["app", "data-delete", "myapp", "-f"]) == 0
        assert list(le.find(app.id)) == []
        assert storage.get_metadata_apps().get_by_name("myapp") is not None

        assert main(["app", "delete", "myapp", "-f"]) == 0
        assert storage.get_metadata_apps().get_by_name("myapp") is None
        assert storage.get_metadata_access_keys().get_by_appid(app.id) == []

    def test_app_data_cleanup_and_trim(self, mem_storage, capsys):
        """data-cleanup deletes pre-cutoff events (cleanup-app parity);
        data-trim copies a time window to another app (trim-app parity)."""
        import datetime as dt

        from predictionio_tpu.data.event import Event

        UTC = dt.timezone.utc
        main(["app", "new", "srcapp"])
        main(["app", "new", "dstapp"])
        src = storage.get_metadata_apps().get_by_name("srcapp")
        dst = storage.get_metadata_apps().get_by_name("dstapp")
        le = storage.get_levents()
        for i in range(6):
            le.insert(Event(event="rate", entity_type="user",
                            entity_id=f"u{i}", target_entity_type="item",
                            target_entity_id="i1",
                            event_time=dt.datetime(2022, 1, 1 + i,
                                                   tzinfo=UTC)), src.id)
        capsys.readouterr()

        # trim the middle window into dstapp first
        assert main(["app", "data-trim", "srcapp", "--dst", "dstapp",
                     "--start", "2022-01-02T00:00:00+00:00",
                     "--until", "2022-01-05T00:00:00+00:00"]) == 0
        assert "Copied 3 events" in capsys.readouterr().out
        copied = list(le.find(dst.id))
        assert len(copied) == 3
        assert {e.entity_id for e in copied} == {"u1", "u2", "u3"}

        # idempotent: a retry copies nothing new (ids already present)
        assert main(["app", "data-trim", "srcapp", "--dst", "dstapp",
                     "--start", "2022-01-02T00:00:00+00:00",
                     "--until", "2022-01-05T00:00:00+00:00"]) == 0
        assert "Copied 0 events" in capsys.readouterr().out
        assert len(list(le.find(dst.id))) == 3

        # cleanup everything before Jan 4 in the source
        assert main(["app", "data-cleanup", "srcapp", "-f",
                     "--before", "2022-01-04T00:00:00+00:00"]) == 0
        out = capsys.readouterr().out
        assert "Removed 3 events" in out
        rest = list(le.find(src.id))
        assert {e.entity_id for e in rest} == {"u3", "u4", "u5"}
        # destination untouched by the source cleanup
        assert len(list(le.find(dst.id))) == 3

        # error paths
        assert main(["app", "data-cleanup", "nope", "-f",
                     "--before", "2022-01-01T00:00:00+00:00"]) == 1
        assert main(["app", "data-cleanup", "srcapp", "-f",
                     "--before", "garbage"]) == 1
        assert main(["app", "data-trim", "srcapp", "--dst", "nope"]) == 1

    def test_channel_lifecycle(self, mem_storage, capsys):
        main(["app", "new", "chanapp"])
        assert main(["app", "channel-new", "chanapp", "weblogs"]) == 0
        assert main(["app", "channel-new", "chanapp", "weblogs"]) == 1  # dup
        assert main(["app", "channel-new", "chanapp", "bad name!"]) == 1
        assert main(["app", "channel-new", "noapp", "c"]) == 1
        capsys.readouterr()
        assert main(["app", "show", "chanapp"]) == 0
        assert "weblogs" in capsys.readouterr().out
        assert main(["app", "channel-delete", "chanapp", "weblogs",
                     "-f"]) == 0
        app = storage.get_metadata_apps().get_by_name("chanapp")
        assert storage.get_metadata_channels().get_by_appid(app.id) == []

    def test_accesskey_lifecycle(self, mem_storage, capsys):
        main(["app", "new", "akapp"])
        capsys.readouterr()
        assert main(["accesskey", "new", "akapp", "--events", "rate",
                     "buy"]) == 0
        out = capsys.readouterr().out
        key = out.split("access key:")[-1].strip()
        assert len(key) == 64
        app = storage.get_metadata_apps().get_by_name("akapp")
        keys = storage.get_metadata_access_keys().get_by_appid(app.id)
        assert any(k.events == ("rate", "buy") for k in keys)

        assert main(["accesskey", "list", "akapp"]) == 0
        assert key in capsys.readouterr().out
        assert main(["accesskey", "delete", key]) == 0
        assert main(["accesskey", "delete", key]) == 1
        assert main(["accesskey", "new", "noapp"]) == 1


class TestExportImport:
    def test_roundtrip(self, mem_storage, tmp_path, capsys):
        from predictionio_tpu.data.event import Event

        main(["app", "new", "expapp"])
        app = storage.get_metadata_apps().get_by_name("expapp")
        le = storage.get_levents()
        for i in range(5):
            le.insert(Event(event="rate", entity_type="user",
                            entity_id=f"u{i}", target_entity_type="item",
                            target_entity_id="i1",
                            properties={"rating": float(i)}), app.id)
        out = str(tmp_path / "events.jsonl")
        assert main(["export", "--app-name", "expapp", "--output", out]) == 0
        assert len(open(out).read().strip().splitlines()) == 5

        main(["app", "new", "impapp"])
        assert main(["import", "--app-name", "impapp", "--input", out]) == 0
        app2 = storage.get_metadata_apps().get_by_name("impapp")
        events = list(le.find(app2.id))
        assert len(events) == 5
        assert {e.entity_id for e in events} == {f"u{i}" for i in range(5)}

    def test_bad_args(self, mem_storage, tmp_path, capsys):
        assert main(["export", "--app-name", "ghost", "--output",
                     str(tmp_path / "x")]) == 1
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"event": "", "entityType": "u", "entityId": "1"}\n')
        main(["app", "new", "impbad"])
        assert main(["import", "--app-name", "impbad", "--input",
                     str(bad)]) == 1

    def test_columnar_roundtrip_full_fidelity(self, mem_storage, tmp_path,
                                              capsys):
        """The Parquet-analog format: every field survives a columnar
        round trip, including tags/prId/no-target events and None
        properties, and import auto-detects the format."""
        import datetime as dt

        from predictionio_tpu.data.event import Event

        main(["app", "new", "colapp"])
        app = storage.get_metadata_apps().get_by_name("colapp")
        le = storage.get_levents()
        t0 = dt.datetime(2021, 5, 1, tzinfo=dt.timezone.utc)
        evs = [
            Event(event="rate", entity_type="user", entity_id="u1",
                  target_entity_type="item", target_entity_id="i1",
                  properties={"rating": 4.5, "note": "great"},
                  tags=("a", "b"), pr_id="pr9", event_time=t0),
            Event(event="$set", entity_type="user", entity_id="u2",
                  properties={"vip": True},
                  event_time=t0 + dt.timedelta(seconds=1)),
            Event(event="view", entity_type="user", entity_id="u3",
                  target_entity_type="item", target_entity_id="i2",
                  event_time=t0 + dt.timedelta(seconds=2)),
        ]
        ids = le.insert_batch(evs, app.id)
        out = str(tmp_path / "events.npz")
        assert main(["export", "--app-name", "colapp", "--output", out,
                     "--format", "columnar"]) == 0
        from predictionio_tpu.tools.export_import import is_columnar_export
        assert is_columnar_export(out)

        main(["app", "new", "colimp"])
        assert main(["import", "--app-name", "colimp", "--input",
                     out]) == 0
        app2 = storage.get_metadata_apps().get_by_name("colimp")
        got = {e.entity_id: e for e in le.find(app2.id)}
        assert set(got) == {"u1", "u2", "u3"}
        e1 = got["u1"]
        assert e1.event_id == ids[0]  # ids preserved
        assert e1.properties.fields == {"rating": 4.5, "note": "great"}
        assert e1.tags == ("a", "b") and e1.pr_id == "pr9"
        assert e1.event_time == t0
        assert got["u2"].target_entity_type is None
        assert got["u2"].properties.fields == {"vip": True}
        assert got["u3"].properties.fields == {}

    def test_columnar_null_sentinel_string_survives(self, mem_storage,
                                                    tmp_path, capsys):
        """Regression (advisor finding): the columnar codec used the
        in-band string ``"\\0N"`` as its null sentinel, so a GENUINE
        ``"\\0N"`` value (entity id, prId...) decoded back as None. The
        null mask is now out-of-band; any string value round-trips."""
        import datetime as dt

        from predictionio_tpu.data.event import Event
        from predictionio_tpu.tools import export_import as ei

        # the unit mechanics: sentinel-looking values encode losslessly
        vals = ["\0N", None, "a", "\0N", "", None]
        codes, labels = ei._dict_encode(vals)
        assert ei._dict_decode(codes, labels) == vals
        codes, labels = ei._dict_encode([None, None])
        assert ei._dict_decode(codes, labels) == [None, None]

        main(["app", "new", "sentapp"])
        app = storage.get_metadata_apps().get_by_name("sentapp")
        le = storage.get_levents()
        t0 = dt.datetime(2021, 5, 1, tzinfo=dt.timezone.utc)
        le.insert_batch([
            Event(event="rate", entity_type="user", entity_id="\0N",
                  target_entity_type="item", target_entity_id="i1",
                  pr_id="\0N", event_time=t0),
            Event(event="view", entity_type="user", entity_id="u2",
                  target_entity_type="item", target_entity_id="i2",
                  event_time=t0),
        ], app.id)
        out = str(tmp_path / "events.npz")
        assert main(["export", "--app-name", "sentapp", "--output", out,
                     "--format", "columnar"]) == 0
        main(["app", "new", "sentimp"])
        assert main(["import", "--app-name", "sentimp", "--input",
                     out]) == 0
        app2 = storage.get_metadata_apps().get_by_name("sentimp")
        got = {e.entity_id: e for e in le.find(app2.id)}
        assert set(got) == {"\0N", "u2"}
        assert got["\0N"].pr_id == "\0N"
        assert got["u2"].pr_id is None

    def test_columnar_roundtrip_sqlite_raw_lane(self, sqlite_storage,
                                                tmp_path, capsys):
        import datetime as dt

        from predictionio_tpu.data.event import Event

        main(["app", "new", "colsql"])
        app = storage.get_metadata_apps().get_by_name("colsql")
        le = storage.get_levents()
        t0 = dt.datetime(2021, 5, 1, tzinfo=dt.timezone.utc)
        le.insert_batch(
            [Event(event="rate", entity_type="user", entity_id=f"u{i}",
                   target_entity_type="item", target_entity_id=f"i{i % 3}",
                   properties={"rating": float(i % 5)},
                   event_time=t0 + dt.timedelta(seconds=i))
             for i in range(50)], app.id)
        out = str(tmp_path / "events.npz")
        assert main(["export", "--app-name", "colsql", "--output", out,
                     "--format", "columnar"]) == 0
        main(["app", "new", "colsql2"])
        assert main(["import", "--app-name", "colsql2", "--input",
                     out]) == 0
        app2 = storage.get_metadata_apps().get_by_name("colsql2")
        got = list(le.find(app2.id))
        assert len(got) == 50
        assert {e.entity_id for e in got} == {f"u{i}" for i in range(50)}
        assert all(e.properties.get("rating") is not None for e in got)

    def test_columnar_import_validates(self, mem_storage, tmp_path,
                                       capsys):
        """A hand-built container must not bypass event validation."""
        import numpy as np

        from predictionio_tpu.tools import export_import as ei

        arrays = {
            "format_version": np.int64(ei.COLUMNAR_FORMAT_VERSION),
            "n_events": np.int64(1),
            "event_ids": np.asarray(["x"], dtype=np.str_),
            "event_times": np.asarray([0.0]),
            "creation_times": np.asarray([np.nan]),
            "properties": np.asarray([""], dtype=np.str_),
            "tags": np.asarray([""], dtype=np.str_),
        }
        cols = {"events": ["$bogus"], "entity_types": ["user"],
                "entity_ids": ["u1"], "target_entity_types": [None],
                "target_entity_ids": [None], "pr_ids": [None]}
        for name, vals in cols.items():
            codes, labels = ei._dict_encode(vals)
            arrays[f"{name}_codes"] = codes
            arrays[f"{name}_labels"] = labels
        bad = tmp_path / "bad.npz"
        with open(bad, "wb") as f:
            np.savez_compressed(f, **arrays)
        main(["app", "new", "colbad"])
        assert main(["import", "--app-name", "colbad", "--input",
                     str(bad)]) == 1
        err = capsys.readouterr().err
        assert "not a supported reserved event name" in err

    def test_columnar_import_rejects_bad_props_json(self, mem_storage,
                                                    tmp_path, capsys):
        """The raw lane writes property strings verbatim; malformed JSON
        must be rejected up front, not poison later reads."""
        import numpy as np

        from predictionio_tpu.tools import export_import as ei

        arrays = {
            "format_version": np.int64(ei.COLUMNAR_FORMAT_VERSION),
            "n_events": np.int64(1),
            "event_ids": np.asarray(["x"], dtype=np.str_),
            "event_times": np.asarray([1.0]),
            "creation_times": np.asarray([np.nan]),
            "properties": np.asarray(["{not json"], dtype=np.str_),
            "tags": np.asarray([""], dtype=np.str_),
        }
        cols = {"events": ["rate"], "entity_types": ["user"],
                "entity_ids": ["u1"], "target_entity_types": [None],
                "target_entity_ids": [None], "pr_ids": [None]}
        for name, vals in cols.items():
            codes, labels = ei._dict_encode(vals)
            arrays[f"{name}_codes"] = codes
            arrays[f"{name}_labels"] = labels
        bad = tmp_path / "badprops.npz"
        with open(bad, "wb") as f:
            np.savez_compressed(f, **arrays)
        main(["app", "new", "colbadp"])
        assert main(["import", "--app-name", "colbadp", "--input",
                     str(bad)]) == 1
        assert "bad properties JSON" in capsys.readouterr().err

    def test_import_zip_but_not_npz_errors_cleanly(self, mem_storage,
                                                   tmp_path, capsys):
        import zipfile

        z = tmp_path / "events.zip"
        with zipfile.ZipFile(z, "w") as zf:
            zf.writestr("events.jsonl", '{"event":"rate"}\n')
        main(["app", "new", "zipapp"])
        assert main(["import", "--app-name", "zipapp", "--input",
                     str(z)]) == 1
        assert "not a readable columnar" in capsys.readouterr().err

    def _columnar_roundtrip(self, tmp_path):
        """100k-event jsonl vs columnar export/import round trip; returns
        (t_jsonl, t_col, jsonl_path, npz_path, N)."""
        import time

        import numpy as np

        main(["app", "new", "bigexp"])
        app = storage.get_metadata_apps().get_by_name("bigexp")
        le = storage.get_levents()
        rng = np.random.default_rng(0)
        N = 100_000
        rows = [(f"id{i:06d}", "rate", "user",
                 f"u{rng.integers(0, 2000)}", "item",
                 f"i{rng.integers(0, 500)}",
                 '{"rating":%d}' % rng.integers(1, 6),
                 1600000000.0 + i, "[]", None, 1600000000.0)
                for i in range(N)]
        le.init(app.id)
        le.insert_raw_batch(rows, app.id, None)

        jl, npz = str(tmp_path / "e.jsonl"), str(tmp_path / "e.npz")
        t0 = time.perf_counter()
        assert main(["export", "--app-name", "bigexp", "--output",
                     jl]) == 0
        main(["app", "new", "impj"])
        assert main(["import", "--app-name", "impj", "--input", jl]) == 0
        t_jsonl = time.perf_counter() - t0

        t0 = time.perf_counter()
        assert main(["export", "--app-name", "bigexp", "--output", npz,
                     "--format", "columnar"]) == 0
        main(["app", "new", "impc"])
        assert main(["import", "--app-name", "impc", "--input",
                     npz]) == 0
        t_col = time.perf_counter() - t0
        return t_jsonl, t_col, jl, npz, N

    def test_columnar_roundtrip_smaller_at_scale(
            self, sqlite_storage, tmp_path, capsys):
        """The point of the format (EventsToFile.scala:35,94 parquet
        default): at 100k events the columnar file is an order of
        magnitude smaller than jsonl and the round trip is lossless
        (measured at 1M: 7MB vs 243MB). The wall-clock ratio is a
        separate perf-marked test — timing under CI load is noise, the
        file size is the deterministic hard check."""
        _, _, jl, npz, N = self._columnar_roundtrip(tmp_path)

        import os as _os
        assert _os.path.getsize(npz) < _os.path.getsize(jl) / 10
        le = storage.get_levents()
        aj = storage.get_metadata_apps().get_by_name("impj")
        ac = storage.get_metadata_apps().get_by_name("impc")
        nj = sum(1 for _ in le.find(aj.id, limit=-1))
        nc = sum(1 for _ in le.find(ac.id, limit=-1))
        assert nj == nc == N

    @pytest.mark.perf
    @pytest.mark.slow
    def test_columnar_roundtrip_wallclock_ratio(
            self, sqlite_storage, tmp_path, capsys):
        """Perf-only (run with ``-m perf``): the columnar round trip must
        not be catastrophically slower than jsonl (measured 1.6x FASTER
        at 1M; 1.5x is a generous noise margin). Excluded from tier-1 —
        wall-clock ratios flake under parallel CI load."""
        t_jsonl, t_col, _, _, _ = self._columnar_roundtrip(tmp_path)
        assert t_col < t_jsonl * 1.5, (t_col, t_jsonl)

    def test_bad_format_flag(self, mem_storage, tmp_path, capsys):
        main(["app", "new", "fmtapp"])
        import pytest as _pytest
        with _pytest.raises(SystemExit):
            main(["export", "--app-name", "fmtapp", "--output",
                  str(tmp_path / "x"), "--format", "parquet"])


class TestTemplateAndLifecycleVerbs:
    def seed(self, app_name="cliapp", n_users=12):
        import datetime as dt
        import numpy as np
        from predictionio_tpu.data.event import Event
        from predictionio_tpu.data.storage.base import App

        aid = storage.get_metadata_apps().insert(App(0, app_name))
        le = storage.get_levents()
        le.init(aid)
        rng = np.random.default_rng(1)
        t0 = dt.datetime(2021, 1, 1, tzinfo=dt.timezone.utc)
        le.insert_batch([
            Event(event="rate", entity_type="user", entity_id=f"u{u}",
                  target_entity_type="item",
                  target_entity_id=f"i{rng.integers(0, 6)}",
                  properties={"rating": float(rng.integers(1, 6))},
                  event_time=t0)
            for u in range(n_users) for _ in range(5)], aid)
        return aid

    def test_template_list_get_build_train(self, mem_storage, tmp_path,
                                           capsys, monkeypatch):
        import json

        assert main(["template", "list"]) == 0
        assert "recommendation" in capsys.readouterr().out

        engine_dir = tmp_path / "myengine"
        assert main(["template", "get", "recommendation",
                     str(engine_dir)]) == 0
        variant_path = engine_dir / "engine.json"
        assert main(["template", "get", "recommendation",
                     str(engine_dir)]) == 1  # already exists
        assert main(["template", "get", "nope", str(tmp_path / "x")]) == 1
        capsys.readouterr()

        self.seed()
        variant = json.loads(variant_path.read_text())
        variant["datasource"]["params"]["appName"] = "cliapp"
        variant["algorithms"][0]["params"].update(
            {"rank": 4, "numIterations": 2})
        variant_path.write_text(json.dumps(variant))

        assert main(["build", "--engine-variant", str(variant_path)]) == 0
        assert "ready for training" in capsys.readouterr().out

        assert main(["train", "--engine-variant", str(variant_path)]) == 0
        out = capsys.readouterr().out
        assert "Training completed" in out
        iid = out.split("ID:")[-1].strip()
        instance = storage.get_metadata_engine_instances().get(iid)
        assert instance.status == "COMPLETED"
        assert storage.get_model_data_models().get(iid) is not None

    def test_train_stop_after_read(self, mem_storage, tmp_path, capsys):
        import json

        engine_dir = tmp_path / "e2"
        main(["template", "get", "recommendation", str(engine_dir)])
        self.seed("stopapp")
        variant_path = engine_dir / "engine.json"
        variant = json.loads(variant_path.read_text())
        variant["datasource"]["params"]["appName"] = "stopapp"
        variant_path.write_text(json.dumps(variant))
        capsys.readouterr()
        assert main(["train", "--engine-variant", str(variant_path),
                     "--stop-after-read"]) == 0
        assert "interrupted" in capsys.readouterr().out

    def test_build_errors(self, mem_storage, tmp_path, capsys):
        import json

        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"engineFactory": "nope.nope:f"}))
        assert main(["build", "--engine-variant", str(bad)]) == 1
        none = tmp_path / "none.json"
        none.write_text(json.dumps({}))
        assert main(["build", "--engine-variant", str(none)]) == 1

    def test_eval_verb(self, mem_storage, capsys):
        self.seed("evalapp", n_users=10)
        assert main(["eval", "tests.cli_eval_fixture:make_evaluation",
                     "tests.cli_eval_fixture:make_generator"]) == 0
        out = capsys.readouterr().out
        assert "[INFO]" in out
        rows = storage.get_metadata_evaluation_instances().get_completed()
        assert len(rows) == 1
        assert rows[0].evaluation_class == (
            "tests.cli_eval_fixture:make_evaluation")


class TestPrecisionFlags:
    """--precision / --serve-precision plumbing (the CLI arm of the
    ops/als.py + ops/serving.py precision policy)."""

    def test_unknown_precision_value_rejected(self, capsys):
        # argparse choices: a typo'd lane must never reach training.
        # (int8 is serving-only: valid for --serve-precision since
        # PR 11, still rejected for the training-side --precision.)
        with pytest.raises(SystemExit):
            main(["train", "--precision", "fp16"])
        with pytest.raises(SystemExit):
            main(["train", "--precision", "int8"])
        with pytest.raises(SystemExit):
            main(["deploy", "--serve-precision", "fp16"])
        with pytest.raises(SystemExit):
            main(["deploy", "--serve-kernel", "mosaic"])

    def test_train_precision_flag_sets_env(self, mem_storage, tmp_path,
                                           capsys, monkeypatch):
        """--precision bf16 lands in PIO_ALS_PRECISION, the single
        source of truth the per-call resolver reads — so the flag
        affects the very training the command runs."""
        import json
        import os

        # setenv("") (not delenv): cmd_train writes os.environ directly,
        # so monkeypatch must have a recorded value to restore — an
        # empty string resolves to the default lane either way
        monkeypatch.setenv("PIO_ALS_PRECISION", "")
        engine_dir = tmp_path / "precengine"
        assert main(["template", "get", "recommendation",
                     str(engine_dir)]) == 0
        TestTemplateAndLifecycleVerbs().seed("precapp")
        variant_path = engine_dir / "engine.json"
        variant = json.loads(variant_path.read_text())
        variant["datasource"]["params"]["appName"] = "precapp"
        variant_path.write_text(json.dumps(variant))
        capsys.readouterr()
        assert main(["train", "--engine-variant", str(variant_path),
                     "--precision", "bf16"]) == 0
        assert os.environ.get("PIO_ALS_PRECISION") == "bf16"
        assert "Training completed" in capsys.readouterr().out

    def test_serve_precision_flag_sets_env(self, monkeypatch):
        from predictionio_tpu.tools.run_commands import (
            _apply_precision_flags,
        )

        import argparse
        import os

        monkeypatch.setenv("PIO_SERVE_PRECISION", "")
        _apply_precision_flags(argparse.Namespace(serve_precision="bf16"))
        assert os.environ.get("PIO_SERVE_PRECISION") == "bf16"
