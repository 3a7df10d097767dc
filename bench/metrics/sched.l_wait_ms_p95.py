"""95th percentile over the window's escalated requests of the telemetry
``l_queued`` span: from the escalation's first send to the start of the tick
that admitted it to an L slot (or to its terminal, if none did), in
milliseconds."""
import math

import numpy as np


def read(run):
    waits = []
    for rid in run.in_window:
        tr = run.traces.get(rid)
        span = next((s for s in tr.spans if s.kind == "l_queued"), None) if tr else None
        if span is not None and math.isfinite(span.t1):
            waits.append(span.t1 - span.t0)
    return 1e3 * float(np.percentile(waits, 95)) if waits else None
