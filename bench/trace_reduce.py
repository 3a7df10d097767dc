"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device metrics.

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:`` plane);
* module time: the device time of each execution of a compiled program
  (the ``XLA Modules`` line), summed by program name;
* op time: device time per operation name, summed;
* idle gaps: the gaps between busy intervals inside the traced span, each
  named by the host phase (the telemetry tick segment) that held the
  gap's midpoint.

The host's ``time.monotonic`` clock is put on the trace's clock by one
``TraceAnnotation`` named ``bench_clock`` whose monotonic time is known.
"""
from __future__ import annotations

from bisect import bisect_right
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLOCK = "bench_clock"


@dataclass
class Reduced:
    devices: int
    busy_ns: float                       # mean over devices
    intervals: list                      # merged busy intervals of device 0, ns
    op_ns: dict = field(default_factory=dict)       # op name -> ns, all devices
    module_ns: dict = field(default_factory=dict)   # program -> ns, device 0
    module_runs: dict = field(default_factory=dict)  # program -> executions
    clock_ns: float | None = None        # trace time of the clock annotation


def merge(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:")
            and any(ln.name == OPS_LINE for ln in p.lines)]


def reduce_profile(pd) -> Reduced:
    planes = _device_planes(pd)
    if not planes:
        raise ValueError("trace holds no device plane with an 'XLA Ops' line: "
                         f"{[p.name for p in pd.planes]}")
    planes.sort(key=lambda p: p.name)
    red = Reduced(devices=len(planes), busy_ns=0.0, intervals=[])
    busy = []
    for i, plane in enumerate(planes):
        spans = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                    red.op_ns[ev.name] = red.op_ns.get(ev.name, 0.0) + ev.duration_ns
            elif line.name == MODULES_LINE and i == 0:
                for ev in line.events:
                    red.module_ns[ev.name] = red.module_ns.get(ev.name, 0.0) + ev.duration_ns
                    red.module_runs[ev.name] = red.module_runs.get(ev.name, 0) + 1
        merged = merge(spans)
        busy.append(sum(e - s for s, e in merged))
        if i == 0:
            red.intervals = merged
    red.busy_ns = sum(busy) / len(busy)
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == CLOCK:
                        red.clock_ns = ev.start_ns
    return red


def load(path: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def idle_gaps(red: Reduced, span_ns, ticks, clock_mono: float, top: int = 10):
    """The ``top`` longest gaps in device 0's busy intervals inside
    ``span_ns`` = (start, stop) trace ns, each as (host phase, seconds).
    ``ticks`` are telemetry ``TickRecord``s (monotonic segments); a gap
    outside every tick is the scheduler waiting for a request."""
    lo, hi = span_ns
    edges, gaps = [], []
    prev = lo
    for s, e in red.intervals:
        if e <= lo or s >= hi:
            continue
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    segs = sorted((t0, t1, name) for tk in ticks for name, t0, t1 in tk.segments)
    edges = [s[0] for s in segs]
    out = []
    for s, e in gaps[:top]:
        mid = clock_mono + ((s + e) / 2 - red.clock_ns) / 1e9
        i = bisect_right(edges, mid) - 1
        phase = segs[i][2] if i >= 0 and segs[i][1] >= mid else "waiting_for_requests"
        out.append([phase, (e - s) / 1e9])
    return out
