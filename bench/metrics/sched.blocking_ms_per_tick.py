"""Device-idle milliseconds per scheduler tick inside the host phases that do
not wait for the device (every telemetry phase but host_fetch), over the
ticks of the traced span: each tick's phase segments, put on the trace's
clock by the ``bench_clock`` annotation, against device 0's busy intervals
(``bench/lanes.py`` reads the same from the ``sched.*`` annotations on the
trace's own clock)."""
from bench import lanes


def read(run):
    if run.trace is None or run.traced is None or run.clock is None \
            or run.trace.clock_ns is None:
        return None
    t0, t1 = run.traced
    ticks = [tk for tk in run.ticks if t0 <= tk.t0 and tk.t1 <= t1]
    if not ticks:
        return None
    off = run.trace.clock_ns - 1e9 * run.clock
    spans = [(1e9 * a + off, 1e9 * b + off) for tk in ticks
             for name, a, b in tk.segments if name != "host_fetch"]
    return lanes.idle_ns(run.trace.intervals, spans) / len(ticks) / 1e6
