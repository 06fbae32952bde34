"""The trace reduction on the small trace beside it gives the busy
share, the op times and the idle attribution computed by hand in the
trace file's comments."""

import os

import pytest
from jax.profiler import ProfileData

import xplane

TRACE = os.path.join(os.path.dirname(__file__), "data", "small_trace.pbtxt")


@pytest.fixture(scope="module")
def profile():
    with open(TRACE, encoding="utf-8") as f:
        return ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(f.read()))


def test_busy_is_the_union_of_op_intervals_averaged_over_devices(profile):
    out = xplane.reduce_trace(profile)
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(3000e-9)
    slice_s = 10000e-9
    assert 100.0 * out["busy_s"] / slice_s == pytest.approx(30.0)


def test_ops_are_ranked_by_device_time_under_xla_names(profile):
    ops = xplane.reduce_trace(profile)["device_ops"]
    assert [name for name, _ in ops] == ["scatter.2", "fusion.1"]
    assert dict(ops)["scatter.2"] == pytest.approx(4000e-9)
    assert dict(ops)["fusion.1"] == pytest.approx(3000e-9)


def test_idle_gaps_go_to_the_host_annotation_that_covers_them(profile):
    gaps = dict(xplane.reduce_trace(profile)["idle_gaps"])
    assert gaps["bench.ingest"] == pytest.approx(2000e-9)
    assert gaps["bench.fire"] == pytest.approx(800e-9)
    assert gaps[xplane.OUTSIDE] == pytest.approx(700e-9)


def test_a_trace_with_no_device_plane_gives_nothing():
    host_only = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(
            'planes { id: 1 name: "/host:CPU" }'))
    assert xplane.reduce_trace(host_only) is None


def test_merge_and_covered():
    merged = xplane.merge([(5, 6), (0, 2), (1, 3), (3, 4)])
    assert merged.tolist() == [[0, 3], [3, 4], [5, 6]] \
        or merged.tolist() == [[0, 4], [5, 6]]
    assert xplane.covered(xplane.merge([(0, 2), (5, 6)]),
                          [-1, 1, 2, 4, 5.5, 9]).tolist() \
        == [0, 1, 2, 2, 2.5, 3]
