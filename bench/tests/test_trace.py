"""``bench/trace.py`` on a small trace recorded on a v5e, and its interval
arithmetic on hand-made intervals.

The recording (``data/tiny.xplane.pb``, one TPU v5e): inside a host span
``window``, three runs of a jitted 1024² matmul + tanh, then 30 ms of host
sleep in a span ``host_sleep``, then one jitted exp-sum.
"""
from __future__ import annotations

import os

import pytest

from bench import trace

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace._union([(5, 7), (0, 2), (1, 3), (3, 4), (6, 9)]) == \
        [(0, 4), (5, 9)]
    assert trace._union([]) == []


def test_host_activity_takes_the_innermost_span():
    spans = [(0, 100, "window"), (40, 60, "host_sleep"), (45, 50, "inner")]
    assert trace.host_activity(spans, 46, 49) == "inner"
    assert trace.host_activity(spans, 52, 58) == "host_sleep"
    assert trace.host_activity(spans, 10, 20) == "window"
    assert trace.host_activity(spans, 200, 300) == "idle host"


@pytest.fixture(scope="module")
def tiny():
    return trace.reduce_trace(TINY)


def test_busy_is_the_union_within_the_window(tiny):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(TINY)
    dev = tiny.devices[0]
    plane = pd.find_plane_with_name(dev)
    evs = [(e.start_ns, e.start_ns + e.duration_ns)
           for ln in plane.lines if ln.name in trace.OP_LINES
           for e in ln.events]
    total = sum(b - a for a, b in evs) * 1e-9
    assert 0 < tiny.busy_s[dev] <= total + 1e-12
    assert tiny.busy_s[dev] < tiny.window_s


def test_per_op_sums_match_the_events_inside_the_window(tiny):
    """Each op's count and seconds are those of its events clipped to the
    host span ``window`` (the device clock runs about a millisecond
    ahead of the host's in this recording, so the first matmul falls
    before the window)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(TINY)
    w0, w1 = [(e.start_ns, e.start_ns + e.duration_ns)
              for p in pd.planes if p.name.startswith("/host:")
              for ln in p.lines for e in ln.events if e.name == "window"][0]
    plane = pd.find_plane_with_name(tiny.devices[0])
    sums = {}
    for ln in plane.lines:
        if ln.name in trace.OP_LINES:
            for e in ln.events:
                inside = min(e.start_ns + e.duration_ns, w1) - max(e.start_ns, w0)
                if inside > 0:
                    c, s = sums.get(e.name, (0, 0.0))
                    sums[e.name] = (c + 1, s + inside * 1e-9)
    assert tiny.ops.keys() == sums.keys()
    for name, (c, s) in tiny.ops.items():
        assert c == sums[name][0]
        assert s == pytest.approx(sums[name][1], rel=1e-9, abs=1e-12)
    assert tiny.window_s == pytest.approx((w1 - w0) * 1e-9)


def test_longest_gap_is_the_host_sleep(tiny):
    name, secs = tiny.top_gaps(1)[0]
    assert name == "host_sleep"
    assert 0.025 < secs < 0.06
