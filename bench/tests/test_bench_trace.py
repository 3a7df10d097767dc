"""The trace reduction on a small trace recorded on one TPU v5e: three runs
of one jitted program, 2 ms apart, with the clock annotation before them."""
from types import SimpleNamespace

import pytest

from helpers import DATA
from bench import trace_reduce


@pytest.fixture(scope="module")
def red():
    return trace_reduce.load(str(DATA / "tiny.xplane.pb"))


def test_device_plane_and_programs(red):
    assert red.devices == 1
    runs = {k: v for k, v in red.module_runs.items() if k.startswith("jit__lambda")}
    assert list(runs.values()) == [3]
    name = next(iter(runs))
    assert 0 < red.module_ns[name] < 3 * 1e5


def test_busy_is_a_union_inside_the_span(red):
    assert red.intervals == sorted(red.intervals)
    assert all(s < e for s, e in red.intervals)
    assert all(a[1] < b[0] for a, b in zip(red.intervals, red.intervals[1:]))
    span = red.intervals[-1][1] - red.intervals[0][0]
    assert 0 < red.busy_ns <= span
    assert red.busy_ns <= sum(red.op_ns.values())


def test_clock_and_idle_gaps(red):
    assert red.clock_ns is not None
    lo, hi = red.intervals[0][0], red.intervals[-1][1]
    # one fake tick whose phases cover the whole span on the host clock
    mono = 100.0
    t0 = mono + (lo - red.clock_ns) / 1e9
    t1 = mono + (hi - red.clock_ns) / 1e9
    tick = SimpleNamespace(segments=[("dispatch", t0, t1)])
    gaps = trace_reduce.idle_gaps(red, (lo, hi), [tick], mono, top=2)
    assert len(gaps) == 2
    assert all(phase == "dispatch" and 1e-4 < sec < 1e-2 for phase, sec in gaps)
    assert gaps[0][1] >= gaps[1][1]
    # outside every tick the host was waiting for requests
    gaps = trace_reduce.idle_gaps(red, (lo, hi), [], mono, top=1)
    assert gaps[0][0] == "waiting_for_requests"


def test_merge():
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
