"""DataMap/PropertyMap/EntityMap behavior (parity: DataMapSpec)."""

import pytest

from predictionio_tpu.data.datamap import DataMap, DataMapError, EntityMap


class TestDataMap:
    def test_typed_get(self):
        d = DataMap({"a": 1, "b": "x", "c": 2.5, "d": True, "e": [1, 2]})
        assert d.get("a", int) == 1
        assert d.get("b", str) == "x"
        assert d.get("c", float) == 2.5
        assert d.get("a", float) == 1.0  # int widens to float
        assert d.get("d", bool) is True
        assert d.get_list("e") == [1, 2]

    def test_missing_raises(self):
        with pytest.raises(DataMapError):
            DataMap().get("nope")

    def test_get_opt(self):
        assert DataMap().get_opt("nope") is None
        assert DataMap({"a": 3}).get_opt("a", int) == 3

    def test_default(self):
        assert DataMap().get("nope", int, default=7) == 7

    def test_type_error(self):
        with pytest.raises(DataMapError):
            DataMap({"a": "str"}).get("a", int)
        with pytest.raises(DataMapError):
            DataMap({"a": True}).get("a", int)  # bool is not int

    def test_merge_and_without(self):
        d = DataMap({"a": 1, "b": 2})
        m = d.merged({"b": 3, "c": 4})
        assert m.fields == {"a": 1, "b": 3, "c": 4}
        w = m.without(["a", "c"])
        assert w.fields == {"b": 3}
        # operators
        assert (d | {"c": 9}).fields == {"a": 1, "b": 2, "c": 9}
        assert (d - ["a"]).fields == {"b": 2}

    def test_json_roundtrip(self):
        d = DataMap({"a": 1, "b": [1, "x"], "c": {"n": 2}})
        assert DataMap.from_json(d.to_json()) == d

    def test_equality_with_mapping(self):
        assert DataMap({"a": 1}) == {"a": 1}

    def test_get_mapping_semantics(self):
        # dm.get(key, default) must behave like Mapping.get
        d = DataMap({"a": 1})
        assert d.get("a", 0) == 1
        assert d.get("missing", "fallback") == "fallback"
        assert d.get("missing", None) is None
        # typed accessor still works alongside
        assert d.get("a", int, 7) == 1
        assert d.get("missing", int, 7) == 7
        with pytest.raises(TypeError):
            d.get("a", 0, 1)  # non-type typ with explicit default


class TestEntityMap:
    def test_indexing(self):
        em = EntityMap({"u1": {"x": 1}, "u2": {"x": 2}})
        assert len(em) == 2
        assert em.index_of("u1") == 0
        assert em.entity_of(1) == "u2"
        assert em["u2"] == {"x": 2}
        assert "u1" in em
