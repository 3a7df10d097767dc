"""Host milliseconds per scheduler tick over the window: the telemetry
segments fault_tick + build_operands + dispatch + postprocess of each tick
(host_fetch, the wait for the device, is left out)."""
PHASES = ("fault_tick", "build_operands", "dispatch", "postprocess")


def read(run):
    ticks = run.window_ticks
    if not ticks:
        return None
    host = sum(t1 - t0 for tk in ticks for name, t0, t1 in tk.segments if name in PHASES)
    return 1e3 * host / len(ticks)
