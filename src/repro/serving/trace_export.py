"""Chrome ``trace_event`` JSON export for serving telemetry.

Converts a :class:`repro.serving.telemetry.Telemetry` collector into the
Trace Event Format consumed by Perfetto (https://ui.perfetto.dev) and
``chrome://tracing``:

* **pid 0 — scheduler**: one ``X`` (complete) event per tick-phase wall
  segment on tid 0, plus ``C`` (counter) events for the per-tick pool
  gauges (free pages, refcount total, prefix-index size, COW copies,
  breaker state, queue depths);
* **pid 1 — S tier / pid 2 — L tier**: one thread (tid = slot) per serving
  slot, carrying that slot's request spans (``admitted``,
  ``prefill_chunk[i]``, ``decode_block[j]``, ``l_verify``); queue-resident
  spans (``queued``, ``escalate_attempt[k]``, and ``l_queued`` on the L
  tier) live on dedicated ``queue``/``transport`` tracks;
* escalations are drawn as **flow events** (``ph: "s"`` at the S-side
  ``escalate_attempt`` start, ``ph: "f"`` binding to the enclosing slice at
  the L-side ``l_verify`` start, ``id`` = request id) so Perfetto renders
  an S->L arrow per escalation attempt;
* terminal statuses appear as ``i`` (instant) markers named
  ``terminal:<status>``;
* watchdog/audit events recorded via :meth:`Telemetry.instant` (e.g.
  ``slo_breach:<kind>``) render as global ``i`` markers on pid 0 under
  ``cat: "slo"``; when a :class:`~repro.serving.audit.GateAudit` is
  installed its per-tick aggregates (running ECE, offload rate, regret
  cost) arrive through the tick gauges and so become counter tracks.

Timestamps are microseconds relative to the collector's earliest event, so
traces start at t=0 regardless of the host's monotonic epoch.
"""
from __future__ import annotations

import json
import math
from typing import Any, Dict, List

# transport/queue pseudo-slots get a tid far above any real slot index
_QUEUE_TID = 1000
_TIER_PID = {"S": 1, "L": 2, "": 0}
# mesh replicas: first replica-specific pid; replica r renders as its own
# process so Perfetto shows one lane group per S shard
_REPLICA_PID0 = 3


def _tier_pid(tier: str) -> int:
    """pid for a tier label: "S"/"L"/"" are fixed; mesh replica labels
    ("S0".."S{R-1}") map to stable per-replica pids (S0 shares pid 1 with
    the historical single-S process — replica 0 IS that process at a 1x1
    debug mesh)."""
    if len(tier) > 1 and tier[0] == "S" and tier[1:].isdigit():
        r = int(tier[1:])
        return 1 if r == 0 else _REPLICA_PID0 + (r - 1)
    return _TIER_PID.get(tier, 0)


def _epoch(tel) -> float:
    t0 = math.inf
    for tick in tel.ticks:
        t0 = min(t0, tick.t0)
    for tr in tel.traces.values():
        for s in tr.spans:
            t0 = min(t0, s.t0)
    for t, _name, _args in getattr(tel, "events", ()):
        t0 = min(t0, t)
    return 0.0 if math.isinf(t0) else t0


def chrome_trace(tel) -> Dict[str, Any]:
    """Render a Telemetry collector as a Chrome trace_event dict."""
    epoch = _epoch(tel)

    def us(t: float) -> float:
        return round((t - epoch) * 1e6, 3)

    ev: List[Dict[str, Any]] = []

    def meta(pid: int, tid: int | None, key: str, name: str) -> None:
        e = {"ph": "M", "pid": pid, "name": key, "args": {"name": name}}
        if tid is not None:
            e["tid"] = tid
        ev.append(e)

    meta(0, None, "process_name", "scheduler")
    meta(0, 0, "thread_name", "tick phases")
    meta(1, None, "process_name", "S tier")
    meta(2, None, "process_name", "L tier")
    meta(1, _QUEUE_TID, "thread_name", "admission queue")
    meta(1, _QUEUE_TID + 1, "thread_name", "escalation transport")
    meta(2, _QUEUE_TID, "thread_name", "L admission queue")
    # mesh replicas beyond S0 get their own process lanes, named up front
    # from the tier labels actually present in the trace
    named_pids = {0, 1, 2}
    for tr in tel.traces.values():
        for s in tr.spans:
            pid = _tier_pid(s.tier)
            if pid not in named_pids:
                named_pids.add(pid)
                meta(pid, None, "process_name", f"S tier replica {s.tier[1:]}")
    seen_tids = set()

    # -- scheduler ticks: phase slices + gauge counters ---------------------
    for tick in tel.ticks:
        for phase, t0, t1 in tick.segments:
            ev.append({"ph": "X", "pid": 0, "tid": 0, "name": phase,
                       "cat": "tick", "ts": us(t0),
                       "dur": max(round((t1 - t0) * 1e6, 3), 0.001),
                       "args": {"tick": tick.index}})
        for k, v in tick.gauges.items():
            ev.append({"ph": "C", "pid": 0, "name": k, "ts": us(tick.t0),
                       "args": {"value": v}})

    # -- watchdog / audit instant events ------------------------------------
    for t, name, args in getattr(tel, "events", ()):
        ev.append({"ph": "i", "pid": 0, "tid": 0, "s": "g", "name": name,
                   "cat": "slo", "ts": us(t), "args": dict(args)})

    # -- request spans ------------------------------------------------------
    for rid in sorted(tel.traces):
        tr = tel.traces[rid]
        for s in tr.spans:
            pid = _tier_pid(s.tier)
            if s.kind == "queued":
                tid = _QUEUE_TID
            elif s.kind in ("escalate_attempt", "escalate_backoff"):
                tid = _QUEUE_TID + 1
            else:
                tid = s.slot if s.slot >= 0 else _QUEUE_TID
            if (pid, tid) not in seen_tids and tid < _QUEUE_TID:
                seen_tids.add((pid, tid))
                meta(pid, tid, "thread_name", f"slot {tid}")
            t1 = s.t0 if not math.isfinite(s.t1) else s.t1
            args = {"request_id": rid, **s.args}
            if s.kind == "terminal":
                ev.append({"ph": "i", "pid": pid, "tid": tid, "s": "t",
                           "name": f"terminal:{s.args.get('status', '?')}",
                           "cat": "request", "ts": us(s.t0), "args": args})
                continue
            name = s.kind
            for idx_key in ("i", "j", "k"):
                if idx_key in s.args:
                    name = f"{s.kind}[{s.args[idx_key]}]"
                    break
            ev.append({"ph": "X", "pid": pid, "tid": tid, "name": name,
                       "cat": "request", "ts": us(s.t0),
                       "dur": max(round((t1 - s.t0) * 1e6, 3), 0.001),
                       "args": args})
            if s.kind == "escalate_attempt":
                ev.append({"ph": "s", "pid": pid, "tid": tid,
                           "name": "escalate", "cat": "flow",
                           "id": rid, "ts": us(s.t0)})
            elif s.kind == "l_verify":
                ev.append({"ph": "f", "pid": pid, "tid": tid, "bp": "e",
                           "name": "escalate", "cat": "flow",
                           "id": rid, "ts": us(s.t0)})

    return {"traceEvents": ev, "displayTimeUnit": "ms",
            "otherData": {"exporter": "repro.serving.trace_export"}}


def write_chrome_trace(tel, path: str) -> Dict[str, Any]:
    """Serialize :func:`chrome_trace` to ``path``; returns the dict."""
    doc = chrome_trace(tel)
    with open(path, "w") as f:
        json.dump(doc, f)
    return doc
