"""Device milliseconds per execution of the scheduler's tick program in the
traced span: the ``XLA Modules`` time of that program over its runs."""


def tick_modules(red):
    names = [n for n in red.module_ns if "tick" in n] or list(red.module_ns)
    return sum(red.module_ns[n] for n in names), sum(red.module_runs[n] for n in names)


def read(run):
    if run.trace is None:
        return None
    ns, runs = tick_modules(run.trace)
    return ns / runs / 1e6 if runs else None
