"""The lane reduction (``bench/lanes.py``) on a small trace of the tiny test
configuration's scheduler recorded on one TPU v5e with its lane map, the
readers of the host-phase and L-wait metrics, and the harness's own
reduction of ``tiny.xplane.pb``, pinned."""
import json
import math
from types import SimpleNamespace

import pytest

from helpers import DATA, ROOT
from bench import lanes, run, trace_reduce

N_LANE_METRICS = ("tick.l_decode_ms", "tick.s_decode_ms", "tick.l_admit_ms",
                  "tick.s_admit_ms", "tick.other_ms")


@pytest.fixture(scope="module")
def fixture():
    from jax.profiler import ProfileData
    meta = json.loads((DATA / "tiny-lanes.json").read_text())
    pd = ProfileData.from_file(str(DATA / "tiny-lanes.xplane.pb"))
    return meta, lanes.reduce_lanes(pd, meta["lanes"])


def test_harness_reduction_of_the_first_trace_is_unchanged():
    red = trace_reduce.load(str(DATA / "tiny.xplane.pb"))
    assert red.devices == 1 and red.busy_ns == 7265.0
    assert red.intervals == [
        [46953140.0, 46953154.0], [46953155.0, 46953158.0], [46953160.0, 46955565.0],
        [49915137.0, 49915151.0], [49915152.0, 49915155.0], [49915157.0, 49917562.0],
        [53078177.0, 53078191.0], [53078192.0, 53078194.0], [53078196.0, 53080601.0]]
    assert {k.split(" = ")[0]: v for k, v in red.op_ns.items()} == {
        "%copy-start": 42.0, "%copy-done": 8.0, "%fusion": 7215.0}
    assert red.module_ns == {"jit__lambda(11645033422356117288)": 7282.0}
    assert red.module_runs == {"jit__lambda(11645033422356117288)": 3}
    assert red.clock_ns == 48137250.0


def test_lanes_are_disjoint_and_inside_the_tick(fixture):
    _, red = fixture
    assert red.runs > 0 and red.module_ns > 0
    assert {"S/admit", "S/decode", "L/admit", "L/decode"} <= set(red.lane_iv)
    # no instant of device time belongs to two lanes
    union = trace_reduce.merge([iv for ivs in red.lane_iv.values() for iv in ivs])
    assert sum(e - s for s, e in union) == pytest.approx(sum(red.lane_ns.values()))
    assert 0 <= red.other_ns < red.module_ns


def test_lane_metrics_sum_to_the_tick_program_time(fixture):
    _, red = fixture
    out = lanes.summary(red)
    parts = sum(out[name] for name in N_LANE_METRICS)
    assert parts == pytest.approx(out["tick.device_ms"], rel=0.01)
    assert all(out[name] >= 0 for name in N_LANE_METRICS)
    assert out["tick.l_decode_ms"] > 0 and out["tick.s_decode_ms"] > 0


def test_host_phases_are_found(fixture):
    meta, red = fixture
    ticks = [h for h in red.host if h[0] == lanes.TICK]
    phases = [h for h in red.host if h[0] != lanes.TICK]
    assert len(ticks) == meta["ticks"] and len(phases) == meta["segments"]
    assert {"sched.fault_tick", "sched.host_fetch", "sched.observe"} <= \
        {name for name, _, _ in phases}
    # every phase lies inside one tick
    for name, s, e in phases:
        assert any(ts <= s and e <= te for _, ts, te in ticks), name
    out = lanes.summary(red)
    assert out["sched.ticks"] == meta["ticks"]
    assert 0 <= out["sched.blocking_ms_per_tick"]


def test_trimming_keeps_what_the_reductions_read(fixture):
    """The recorder cuts the trace with ``trim_xspace``; cutting it again
    changes nothing the reductions read."""
    from jax.profiler import ProfileData
    meta, red = fixture
    raw = (DATA / "tiny-lanes.xplane.pb").read_bytes()
    again = lanes.reduce_lanes(ProfileData.from_serialized_xspace(lanes.trim_xspace(raw)),
                               meta["lanes"])
    assert lanes.summary(again) == lanes.summary(red)
    assert again.op_ns == red.op_ns


def test_instruction_names():
    assert lanes.instruction("%conditional.2 = (f32[2]) conditional(s32[] %p)") == \
        "conditional.2"
    assert lanes.instruction("%fusion") == "fusion"


def test_idle_inside_spans():
    busy = [[0, 10], [20, 30], [40, 50]]
    assert lanes.idle_ns(busy, [(0, 10)]) == 0
    assert lanes.idle_ns(busy, [(5, 25)]) == 10
    assert lanes.idle_ns(busy, [(12, 18), (35, 60)]) == 6 + 15
    assert lanes.idle_ns([], [(1, 4)]) == 3


def _span(kind, t0, t1):
    return SimpleNamespace(kind=kind, t0=t0, t1=t1)


def test_l_wait_reader():
    read, unit = run.metric_readers(_spec("sched.l_wait_ms_p95"))["sched.l_wait_ms_p95"]
    traces = {rid: SimpleNamespace(spans=[_span("queued", 0.0, 1.0)] + (
        [_span("l_queued", 2.0, 2.0 + 0.01 * rid)] if rid % 2 else []))
        for rid in range(1, 41)}
    traces[41] = SimpleNamespace(spans=[_span("l_queued", 3.0, math.nan)])
    got = read(SimpleNamespace(in_window=set(traces), traces=traces))
    waits = sorted(0.01 * rid for rid in range(1, 41, 2))
    assert unit == "ms" and got == pytest.approx(1e3 * _p95(waits))
    assert read(SimpleNamespace(in_window={2, 4}, traces=traces)) is None


def test_blocking_reader_puts_the_phases_on_the_trace_clock():
    read, _ = run.metric_readers(_spec("sched.blocking_ms_per_tick"))[
        "sched.blocking_ms_per_tick"]
    # the clock annotation: monotonic 50 s is trace 150 s; the device is
    # busy 0-4 ms and 6-10 ms after it
    clock, clock_ns = 50.0, 150e9
    trace = SimpleNamespace(intervals=[[150e9, 150e9 + 4e6], [150e9 + 6e6, 150e9 + 10e6]],
                            clock_ns=clock_ns)
    seg = [("fault_tick", 50.0, 50.003), ("build_operands", 50.003, 50.005),
           ("host_fetch", 50.005, 50.009), ("postprocess", 50.009, 50.011)]
    tick = SimpleNamespace(t0=50.0, t1=50.011, segments=seg)
    got = read(SimpleNamespace(trace=trace, traced=(49.0, 51.0), clock=clock, ticks=[tick]))
    # idle inside the non-host_fetch phases: 4-5 ms (build_operands) and
    # 10-11 ms (postprocess)
    assert got == pytest.approx(2.0)
    outside = SimpleNamespace(trace=trace, traced=(60.0, 61.0), clock=clock, ticks=[tick])
    assert read(outside) is None


def _p95(values):
    import numpy as np
    return float(np.percentile(values, 95))


def _spec(name):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return dict(spec, per_layer=[m for m in spec["per_layer"] if m["name"] == name])
