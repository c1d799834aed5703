"""The trace reduction on a small profile kept beside this file
(``tpu_like.xplane.pb``, written by ``make_fixture.py`` in the layout
of a one-chip TPU trace). Every expected number below is worked out by
hand from the layout in ``make_fixture.py``'s docstring."""

import os

import pytest

from benchmark.harness import trace_reduce as tr
from benchmark.tests import make_fixture

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "tpu_like.xplane.pb")


def test_fixture_file_is_what_the_generator_writes():
    with open(FIXTURE, "rb") as f:
        assert f.read() == make_fixture.build()


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_file(
        FIXTURE, gap_label=lambda a, b: f"{a * 1e9:.0f}-{b * 1e9:.0f}")


def test_busy_and_window(reduced):
    # ops: [1000,2000] u [1500,3000] = 2000; [5000,6000] = 1000;
    # [7000,8500] = 1500 -> 4500 ns of a 10,000 ns profile
    assert reduced["planes"] == ["/device:TPU:0"]
    assert reduced["busy_s"] == pytest.approx(4500e-9)
    assert reduced["window_s"] == pytest.approx(10000e-9)


def test_per_module_time(reduced):
    assert reduced["modules"]["jit_prog"] == {
        "seconds": pytest.approx(3000e-9), "count": 2}
    assert reduced["modules"]["jit_step"] == {
        "seconds": pytest.approx(2000e-9), "count": 1}


def test_top_ops(reduced):
    # SELF time under short names: while.9 spans 7000-8500 and holds
    # fusion.3 (7200-8200), so it keeps 500 ns; the partly overlapping
    # pair fusion.1 / custom-call.2 is not nested and keeps its own
    ops = dict(reduced["device_ops"])
    assert ops == {"fusion.1": pytest.approx(2000e-9),
                   "custom-call.2": pytest.approx(1500e-9),
                   "fusion.3": pytest.approx(1000e-9),
                   "while.9": pytest.approx(500e-9)}
    assert reduced["device_ops"][0][0] == "fusion.1"


def test_idle_gaps_longest_first_and_labelled_on_the_epoch_clock(reduced):
    # gaps: 3000-5000 (2000), 8500-10000 (1500), 0-1000, 6000-7000
    secs = [g[1] for g in reduced["idle_gaps"]]
    assert secs == [pytest.approx(2000e-9), pytest.approx(1500e-9),
                    pytest.approx(1000e-9), pytest.approx(1000e-9)]
    # the label callback got epoch seconds: start_epoch + gap midpoint
    # the label callback got epoch seconds: the profile started at
    # 5,000,000 ns on the epoch clock, the longest gap is 3000-5000
    assert reduced["profile_start_epoch_s"] == pytest.approx(0.005)
    assert reduced["idle_gaps"][0][0] == "5003000-5005000"
    assert reduced["idle_gaps"][1][0] == "5008500-5010000"


def test_window_clips_events(reduced):
    import jax

    prof = jax.profiler.ProfileData.from_file(FIXTURE)
    r = tr.reduce_profile(prof, window_ns=(1500.0, 5500.0))
    # [1500,3000] + [5000,5500]
    assert r["busy_s"] == pytest.approx(2000e-9)
    assert r["modules"]["jit_prog"]["seconds"] == pytest.approx(2000e-9)


def test_no_device_plane_returns_none(tmp_path):
    host_only = make_fixture._bytes(1, make_fixture._plane(
        2, "/host:CPU", lines=[(1, "python", [(1, 0, 10)])],
        event_names=[(1, "x")]))
    p = tmp_path / "host.xplane.pb"
    p.write_bytes(host_only)
    assert tr.reduce_file(str(p)) is None


def test_union_intervals():
    assert tr.union_intervals([(5, 6), (1, 3), (2, 4), (6, 7)]) == \
        [(1, 4), (5, 7)]
    assert tr.module_name("jit_prog(123)") == "jit_prog"
