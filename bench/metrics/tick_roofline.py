"""Share of the roofline that the tick program reaches in the traced span:
the least time its useful work needs on this chip, max(FLOPs / peak FLOP/s,
bytes / HBM bandwidth) per tick (``bench/work.py``), summed over the ticks
dispatched in the traced span, over the device time of the tick program's
executions there, in percent."""
from bench import work


def read(run):
    if run.trace is None or run.peak is None:
        return None
    names = [n for n in run.trace.module_ns if "tick" in n] or list(run.trace.module_ns)
    device_s = sum(run.trace.module_ns[n] for n in names) / 1e9
    t0, t1 = run.traced
    least = sum(work.least_time(f, b, run.peak) for tk, f, b in run.work
                if t0 <= tk.t0 < t1)
    if device_s <= 0 or least <= 0:
        return None
    return 100.0 * least / device_s
