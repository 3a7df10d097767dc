"""Share of the traced span in which no operation ran on the device: one
minus the union of the ``XLA Ops`` intervals over the span, in percent."""


def read(run):
    if run.trace is None or run.traced is None:
        return None
    t0, t1 = run.traced
    return 100.0 * (1.0 - run.trace.busy_ns / 1e9 / (t1 - t0))
