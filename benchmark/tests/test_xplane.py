"""The trace reduction against the hand-built trace beside it."""

import os

import pytest

from benchmark import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(os.path.dirname(HERE), "xplane_fixture.textproto")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    with open(FIXTURE) as f:
        return xplane.reduce(ProfileData.from_text_proto(f.read()))


def test_busy_is_the_union_not_the_sum(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(8e-6)
    assert reduced["busy_s"] == pytest.approx(6e-6)


def test_self_time_leaves_out_nested_operations(reduced):
    assert reduced["ops"]["while.1"] == pytest.approx(2e-6)
    assert reduced["ops"]["fusion.2"] == pytest.approx(3e-6)
    assert reduced["ops"]["sort.3"] == pytest.approx(1e-6)
    assert reduced["device_ops"][0] == ["fusion.2", pytest.approx(3e-6)]


def test_programs_by_name_without_fingerprint(reduced):
    assert reduced["programs"] == {
        "jit__hashed_replay_epochs": pytest.approx(5e-6),
        "jit__hashed_step": pytest.approx(1e-6)}


def test_gap_is_named_by_the_innermost_bench_span(reduced):
    assert reduced["gaps"] == {"parse": pytest.approx(2e-6)}


def test_window_clips_and_counts_leading_idle(reduced):
    from jax.profiler import ProfileData

    with open(FIXTURE) as f:
        r = xplane.reduce(ProfileData.from_text_proto(f.read()),
                          window=(0, 10_000))
    assert r["busy_s"] == pytest.approx(6e-6)
    assert r["window_s"] == pytest.approx(10e-6)
    # 0..1,000 ns: the midpoint 500 is where 'job' starts; 9,000..10,000:
    # the midpoint 9,500 is where it has ended
    assert r["gaps"] == {"job": pytest.approx(1e-6),
                         "parse": pytest.approx(2e-6),
                         "no_bench_span": pytest.approx(1e-6)}


def test_union():
    assert xplane.union([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]


def test_window_by_span_name_and_span_seconds():
    from jax.profiler import ProfileData

    with open(FIXTURE) as f:
        r = xplane.reduce(ProfileData.from_text_proto(f.read()),
                          window="job")
    assert r["window_s"] == pytest.approx(9e-6)
    assert r["spans"] == {"job": [pytest.approx(9e-6)],
                          "parse": [pytest.approx(1e-6)]}
