"""Share of the roofline that the tick program reaches in the traced span of
a Zamba2 cell: the least time of the hybrid's useful work, max(FLOPs / peak
FLOP/s, bytes / HBM bandwidth) per tick (``bench/work_hybrid.py``: mamba
projections, conv and recurrence with each live slot's state read and
written, the shared blocks per application with their adapters and linears,
the K/V of every application), summed over the ticks dispatched in the
traced span, over the device time of the tick program's executions there,
in percent.  Nothing to read in a cell of another family."""
from bench import work_hybrid


def read(run):
    if run.trace is None or run.peak is None or run.config.get("family") != "zamba2":
        return None
    names = [n for n in run.trace.module_ns if "tick" in n] or list(run.trace.module_ns)
    device_s = sum(run.trace.module_ns[n] for n in names) / 1e9
    t0, t1 = run.traced
    least = sum(work_hybrid.least_time(f, b, run.peak)
                for tk, f, b in work_hybrid.tick_work(run) if t0 <= tk.t0 < t1)
    if device_s <= 0 or least <= 0:
        return None
    return 100.0 * least / device_s
