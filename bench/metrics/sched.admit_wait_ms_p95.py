"""95th percentile over the window's requests of the telemetry ``queued``
span: from the request's due time to the tick that admitted it to an S slot,
in milliseconds."""
import numpy as np


def read(run):
    waits = []
    for rid in run.in_window:
        tr = run.traces.get(rid)
        span = next((s for s in tr.spans if s.kind == "queued"), None) if tr else None
        if span is not None:
            waits.append(span.t1 - span.t0)
    return 1e3 * float(np.percentile(waits, 95)) if waits else None
