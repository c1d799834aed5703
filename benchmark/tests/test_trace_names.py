"""``harness/trace_names.py`` on the small profile kept beside this
file (``chip_names.xplane.pb``, written by ``make_names_fixture.py``
with the names a TPU v5 lite trace of this program carries). Every
expected number is worked out by hand from the layout in the
generator's docstring."""

import os

import pytest

from benchmark.harness import trace_names as tn
from benchmark.tests import make_names_fixture

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "chip_names.xplane.pb")


def test_fixture_file_is_what_the_generator_writes():
    with open(FIXTURE, "rb") as f:
        assert f.read() == make_names_fixture.build()


@pytest.fixture(scope="module")
def planes():
    with open(FIXTURE, "rb") as f:
        return tn.read_xspace(f.read())


def test_the_reader_sees_names_times_and_metadata_stats(planes):
    assert [p.name for p in planes] == ["/device:TPU:0", "/host:CPU"]
    device = planes[0]
    assert [ln.name for ln in device.lines] == ["XLA Modules", "XLA Ops"]
    assert device.lines[0].events == [(1, 1000.0, 9000.0),
                                      (1, 15000.0, 23000.0)]
    assert device.event_names[1] == "jit_two_topk(77)"
    assert device.event_stats[4] == {"tf_op": make_names_fixture.TOPK}
    assert device.event_stats[3] == {}        # the compiler's copy
    # jax.profiler.ProfileData reads the same file, minus those stats
    import jax

    prof = jax.profiler.ProfileData.from_file(FIXTURE)
    ops = [ln for p in prof.planes for ln in p.lines
           if ln.name == "XLA Ops"][0]
    assert [e.start_ns for e in ops.events][:2] == [1000.0, 6000.0]
    assert all(dict(e.stats) == {} for e in ops.events)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(two_topk)/stage1/topk/fused_topk/pallas_call:",
     "stage1/topk/fused_topk"),
    ("jit(two_topk)/rerank/jit(_take)/gather:", "rerank"),
    ("jit(users_topk_xla)/vmap(gather_q)/jit(_take)/gather:", "gather_q"),
    ("jit(_als_iterations_bucketed_impl)/while/body/user_step/solve/"
     "factor/while/body/dynamic_slice:", "user_step/solve/factor"),
    ("jit(f)/while/body/item_step/assemble/bl,blr,bls->brs/dot_general:",
     "item_step/assemble/bl,blr,bls->brs"),
    ("jit(f)/a/b/c/d/add:", "a/b/c"),            # depth 3
    ("jit(f)/add:", tn.NO_SCOPE), ("", tn.NO_SCOPE), ("uids", tn.NO_SCOPE),
])
def test_scope_of(op_name, scope):
    assert tn.scope_of(op_name) == scope


def test_device_self_time_by_scope(planes):
    got = tn.device_scopes(planes)
    # two dispatches: copy 5000, top-k 2500, re-rank gather 400, pack
    # 100 each; the while's 4000 hold a 3000 body of the same scope
    assert got["self_s"] == pytest.approx(20500e-9)
    assert [(k, round(v * 1e9)) for k, v, _ in got["scopes"]] == [
        ("(no scope) copy", 10000), ("stage1/topk/fused_topk", 5000),
        ("user_step/solve/factor", 4000), ("rerank", 800),
        ("gather_q", 500), ("rerank/pack", 200)]
    assert sum(share for _, _, share in got["scopes"]) == \
        pytest.approx(1.0)
    # one scope level: the pack joins its parent, the solve its step
    flat = dict((k, round(v * 1e9))
                for k, v, _ in tn.device_scopes(planes, depth=1)["scopes"])
    assert flat == {"(no scope) copy": 10000, "stage1": 5000,
                    "user_step": 4000, "rerank": 1000, "gather_q": 500}


def test_only_stage_annotations_name_a_gap(planes):
    names = {n for n, _, _ in tn.stage_events(planes)}
    assert "device.user_topk" not in names
    assert "query POST /queries.json" not in names
    assert {"batch.idle", "dispatch.enqueue", "ladder.lower"} <= names


def test_idle_gaps_are_named_by_the_stage_that_covers_most(planes):
    gaps = tn.idle_gaps(planes)
    # ops end 9000 / start 15000; end 23000 / start 30000: the first gap
    # is wait 100, fetch 500, deliver 500, window 2000, form 200, lock
    # 10, enqueue 2490, wait 200; the second wait 100, fetch 400, idle
    # 6000 and 500 of nothing
    assert [(n, round(s * 1e9)) for n, s, _ in gaps] == [
        ("batch.idle", 7000), ("dispatch.enqueue", 6000)]
    assert gaps[0][2] == pytest.approx(6000 / 7000)
    assert gaps[1][2] == pytest.approx(2490 / 6000)
    assert tn.idle_by_stage(tn.idle_gaps(planes, top=None)) == [
        ["batch.idle", pytest.approx(7000e-9), 1],
        ["dispatch.enqueue", pytest.approx(6000e-9), 1]]


def test_a_nested_stage_takes_its_time_off_the_outer_one(planes):
    stages = tn.stage_events(planes)
    assert tn.name_gap(stages, 50000, 60000) == \
        ("ladder.lower", pytest.approx(0.6))
    assert tn.name_gap(stages, 50000, 52000) == \
        ("ladder.compile", pytest.approx(1.0))
    assert tn.name_gap(stages, 70000, 80000) == ("unannotated", 0.0)


def test_reduce_file_is_json_and_says_which_stat_it_read():
    import json

    got = tn.reduce_file(FIXTURE)
    assert got["scope_stat"] == "tf_op"
    assert got["device_scopes"][0][0] == "(no scope) copy"
    assert got["stages_seen"][0] == "batch.deliver"
    json.dumps(got)
