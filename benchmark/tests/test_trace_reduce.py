"""The trace reduction on a small recorded trace (``data/small_trace.pbtxt``,
written by hand in the layout of a v5e trace)."""

from __future__ import annotations

import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def xplane(tmp_path):
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "data", "small_trace.pbtxt")) as f:
        text = "\n".join(line for line in f.read().splitlines()
                         if not line.lstrip().startswith("#"))
    d = tmp_path / "plugins" / "profile" / "2026_09_27"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def test_union():
    import trace_reduce

    assert trace_reduce.union([(3, 5), (0, 1), (4, 4.5), (5, 6), (8, 9)]) == [
        [0, 1], [3, 6], [8, 9]]


def test_reduction(xplane):
    import trace_reduce

    # host clock: the window is [100.0040, 100.0100] s and its end is the
    # fence (the last op ends at 11 ms), so on the device's clock it is
    # [5.0, 11.0] ms: op A (1..3) is out, the first op B (4..6) is cut at 5
    spans = [("dispatch", 100.0020, 100.0031), ("h2d", 100.0045, 100.0066)]
    r = trace_reduce.reduce_dir(xplane, 100.0040, 100.0100, 100.0100, spans)
    assert r.n_device_planes == 1 and r.n_op_events == 5
    assert r.window_s == pytest.approx(0.006)
    # busy: 5..6 (B, C inside it) and 8..11 (B, D)
    assert r.busy_s == pytest.approx(0.004)
    ops = dict(r.top_ops(10))
    assert ops["%while.2 = (s32[], f32[8]) while(%tuple.1)"] == pytest.approx(0.003)
    assert ops["%add.3 = f32[8] add(%a, %b)"] == pytest.approx(0.0005)
    assert not any(k.startswith("%fusion.1") for k in ops)
    # one idle gap, 6..8 ms on the device's clock = 100.005..100.007 on the
    # host's: the h2d span covers most of it
    assert r.top_gaps(10) == [["h2d", pytest.approx(0.002)]]


def test_fence_before_the_window_end(xplane):
    import trace_reduce

    # the last device op ended 1 ms before the window was closed
    r = trace_reduce.reduce_dir(xplane, 100.0040, 100.0110, 100.0100, [])
    assert r.window_s == pytest.approx(0.007)
    assert r.busy_s == pytest.approx(0.004)
    assert r.top_gaps(10)[0][0] == trace_reduce.NO_SPAN
    assert sum(g for _, g in r.top_gaps(10)) == pytest.approx(0.003)


def test_empty_trace(tmp_path):
    import trace_reduce

    with pytest.raises(FileNotFoundError):
        trace_reduce.reduce_dir(str(tmp_path), 0.0, 1.0, 1.0, [])
