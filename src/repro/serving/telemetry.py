"""Serving telemetry: typed counters, per-request span trees, tick-phase
timing, pool gauges, and streaming histograms — zero overhead when disabled.

The paper's claims are latency/bandwidth/energy claims, and every planned
policy (the arXiv:2112.11413 drop rule, online theta per arXiv:2304.00891)
acts on per-request, per-phase timing signals.  This module is where those
signals live.  Everything here is HOST-side bookkeeping over state the
scheduler already holds: enabling telemetry never adds a device dispatch, a
host sync, or an operand — ``stream_compiles == 1`` and one-sync-per-tick
are untouched (tests/test_telemetry.py asserts both), and with telemetry
disabled (the default) the scheduler's hooks are a single ``is None`` test.

Three faces:

**1. Typed counters** (:class:`SchedCounters` / :class:`EngineCounters`).
The ad-hoc ``stats`` dicts of ``ContinuousScheduler`` and ``HIEngine`` are
now read/write VIEWS (:class:`StatsView` / :class:`EngineStatsView`) over
dataclasses of typed fields — same dict API (``stats["ticks"]``,
``.items()``, ``**stats``), no test or bench churn, but one authoritative
store.  The engine no longer copies-and-zeroes the scheduler's fault
counters: :class:`EngineStatsView` reads the live scheduler's counters
through the view (``engine total = retired base + live scheduler``), so the
two can never diverge (test-asserted).

**2. Per-request span trees** (:class:`Telemetry`, :class:`Span`,
:class:`RequestTrace`).  Every request accumulates a flat list of spans that
reads as the tree::

    queued -> admitted -> prefill_chunk[i] -> decode_block[j]
           -> l_queued (escalate_attempt[k] ...) -> l_verify -> terminal

with the terminal status (``ok`` / ``degraded_local`` / ``dropped`` /
``rejected``), TTFT, TPOT, queue-wait ticks, and retry counts attached.
Span kinds:

* ``queued``          — submit to S-tier slot admission;
* ``admitted``        — the admission tick (args: tier, slot, prefill
  ``start``, ``chunked``/``restore`` flags);
* ``prefill_chunk``   — one span per chunk-lane tick (args: ``i`` chunk
  index, ``fed``, ``keep``);
* ``decode_block``    — one span per tick the slot decoded (args: ``j``
  block index, ``steps``);
* ``escalate_attempt``— one span per S->L transport attempt, send to
  arrival/failure (args: ``k`` = attempt, ``outcome``);
* ``l_queued``        — the wait for the L tier: from the escalation's
  first send to the start of the tick that admits it to an L slot, across
  every transport attempt (its ``escalate_attempt`` children), or to its
  terminal if no L slot takes it (args: ``outcome``);
* ``l_verify``        — L-tier residency for the escalation (admission to
  finish/abort);
* ``terminal``        — zero-length marker carrying the final status.

Span timestamps are ``time.monotonic()`` seconds (the clock the scheduler
already uses for ``submit_time``/TTFT); a request's device work inside a
tick is attributed to the tick's wall bracket.  Which lane of the compiled
tick the device spent its time in is the profiler trace's to say: the
tick's lanes run under named scopes, and
``ContinuousScheduler.lane_of_ops`` maps the trace's device operations to
them.

**3. Tick-phase timing + gauges** (:class:`TickRecord`).  Each scheduler
tick is decomposed host-side into contiguous wall-time phases, so every
host microsecond between ``begin_tick`` and ``end_tick`` belongs to one:

* ``fault_tick``     — breaker/transport/drop bookkeeping + slot admission;
* ``build_operands`` — numpy operand assembly (``tick_inputs``);
* ``dispatch``       — executable call (submit; XLA may run async);
* ``host_fetch``     — the tick's single device->host sync (device time
  surfaces here on async backends);
* ``postprocess``    — token absorb, finish/escalation bookkeeping;
* ``observe``        — gauges, SLO watchdog, flight recorder.

While a collector is installed each tick is also a
``jax.profiler.StepTraceAnnotation("sched.tick")`` and each phase a
``jax.profiler.TraceAnnotation("sched.<phase>")`` of the same extent, so a
profiler trace holds the host phases on its own clock beside the device's
operations.  Outside a trace they record nothing.

plus per-tick pool gauges sampled from host state the scheduler already
holds: free pages, total refcounts, prefix-index size, COW copies, breaker
state, L-queue depth, in-flight escalations, busy slots per tier.

Decision-quality observability (``serving/audit.py``, PR 9)
-----------------------------------------------------------
The time-blind half of observability lives next door: ``GateAudit`` is the
per-decision gate audit stream.  Each :class:`~repro.serving.audit.
AuditRecord` carries ``(rid, tier, tclass, kind, conf, theta_in_effect,
offload, ok)`` where ``kind`` is one of ``admit`` / ``chunk`` / ``decode``
(per-token gate evaluations), ``request`` (the request-level escalation
decision) or ``l_agree`` (completed escalation: S tokens matched L's).
``theta_in_effect`` records the threshold the device ACTUALLY used —
``FAIL_LOCAL_THETA`` while the circuit breaker is open.  Aggregates:
streaming reliability bins (``core/calibrate.p_histogram`` bin semantics),
running ECE + offload-rate per ``Request.tclass`` traffic class, a
theta-margin histogram, and empirical-regret counters vs the L-agreement
oracle.  When both collectors are installed the scheduler binds
``telemetry.audit = audit`` so the ``hi_audit_*`` families ride
:meth:`Telemetry.prometheus_text` and the audit gauges become Chrome-trace
counter tracks.

The SLO watchdog (:class:`~repro.serving.audit.SLOWatchdog`, configured by
:class:`~repro.serving.audit.SLOThresholds`: ``ttft_p95`` / ``tpot_p95``
seconds, ``queue_depth``, ``ece_max`` / ``offload_rate_max`` drift bounds
with ``min_outcomes`` / ``min_requests`` warm-up floors) is evaluated once
per tick; breaches append to ``watchdog.breaches``, emit
:meth:`Telemetry.instant` events (Chrome ``i`` markers), and trigger the
flight recorder (``serving/flight_recorder.py`` — a bounded ring of
deterministic per-tick snapshots dumped as postmortem JSON on watchdog
breach, breaker-open, ``check_invariants`` failure, or the idle-tick
stall bound).

Exporters
---------
* :meth:`Telemetry.histogram_summary` — log-bucketed streaming histograms
  (TTFT / TPOT / queue-wait / escalation latency) with p50/p95/p99;
* :meth:`Telemetry.prometheus_text` — a Prometheus text-format snapshot.
  Every family carries ``# HELP``/``# TYPE`` lines and label values are
  escaped per the text exposition format.  Keys: ``hi_<counter>_total``
  one per :class:`SchedCounters` field (e.g. ``hi_requests_total``,
  ``hi_degraded_local_total``),
  ``hi_tick_phase_seconds_total{phase=...}`` per tick-phase bucket,
  ``hi_gauge{name=...,tier=...}`` last-sampled pool gauges, and per
  histogram ``hi_<name>_seconds`` a ``_count`` / ``_sum`` /
  ``_bucket{le=...}`` family (``hi_ttft_seconds``, ``hi_tpot_seconds``,
  ``hi_queue_wait_ticks``, ``hi_esc_latency_seconds``; the unbounded
  overflow bucket folds into ``+Inf`` — no finite ``le`` edge).  With a
  ``GateAudit`` bound, the audit families are appended:
  ``hi_audit_decisions_total``, ``hi_audit_outcomes_total``,
  ``hi_audit_regret_total{kind=...}``, ``hi_audit_regret_cost``,
  ``hi_audit_ece{tclass=...}``, ``hi_audit_offload_rate{tclass=...}``,
  ``hi_audit_reliability_total{bin=...,outcome=...}``, and
  ``hi_audit_theta_margin`` (histogram);
* ``serving/trace_export.py`` — Chrome ``trace_event`` JSON (one track per
  slot per tier, escalations as S->L flow events, watchdog breaches as
  instant markers, audit aggregates as counter tracks), loadable in
  Perfetto.

``benchmarks/bench_serving.py --trace-out`` wires it to traffic; its
``--telemetry-smoke`` and ``--audit-smoke`` CI gates compare CPU wall
clock with the collector on and off, which says nothing of the cost on the
chip (``PERF.md`` records that).
"""
from __future__ import annotations

import math
import time
from collections.abc import MutableMapping
from dataclasses import dataclass, field, fields
from typing import Any, Dict, List, Optional, Tuple

from jax import profiler

# Tick-phase wall-time buckets, in intra-tick order.  ``transfer_overlap``
# only accumulates in mesh mode: it brackets the escalation staging-buffer
# operand build + device copy dispatched at tick top, i.e. the S->L transfer
# work that the sharded executable overlaps with the same tick's S-side
# prefill/decode (bench_serving --mesh-smoke asserts it is nonzero).
PHASES = ("fault_tick", "transfer_overlap", "build_operands", "dispatch",
          "host_fetch", "postprocess", "observe")

_now = time.monotonic

# one-line HELP strings for the prometheus_text metric families
_HELP = {
    "hi_tick_phase_seconds_total":
        "Cumulative wall seconds per scheduler tick phase.",
    "hi_gauge":
        "Last-sampled per-tick pool/breaker/queue gauge (tier-labelled).",
    "ttft": "Time to first token.",
    "tpot": "Time per output token (after the first).",
    "queue_wait": "Escalation queue wait.",
    "esc_latency": "Escalation send-to-terminal latency.",
}


def escape_label(value: str) -> str:
    """Escape a Prometheus label VALUE per the text exposition format
    (backslash, double quote, newline)."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


# ---------------------------------------------------------------------------
# typed counters + dict views
# ---------------------------------------------------------------------------

@dataclass
class SchedCounters:
    """The ContinuousScheduler's typed counters (one instance per scheduler;
    ``scheduler.stats`` is a :class:`StatsView` over it)."""
    requests: int = 0
    offloaded: int = 0
    dropped: int = 0
    ticks: int = 0
    compiles: int = 0
    serve_time: float = 0.0
    degraded_local: int = 0
    rejected: int = 0
    breaker_open_ticks: int = 0
    breaker_opens: int = 0
    esc_retries: int = 0
    esc_lost: int = 0


@dataclass
class EngineCounters:
    """HIEngine's own counter store.  For the keys the scheduler also
    counts, this holds the RETIRED base (drain-path contributions plus the
    folded totals of replaced schedulers); :class:`EngineStatsView` adds the
    live scheduler's counters on read."""
    requests: int = 0
    offloaded: int = 0
    dropped: int = 0
    serve_time: float = 0.0
    compiles: int = 0
    stream_compiles: int = 0
    stream_ticks: int = 0
    prefill_tokens_saved: int = 0
    degraded_local: int = 0
    rejected: int = 0
    breaker_open_ticks: int = 0
    breaker_opens: int = 0
    esc_retries: int = 0
    esc_lost: int = 0


class StatsView(MutableMapping):
    """Dict-API view over a counters dataclass: ``view["ticks"] += 1``
    mutates ``counters.ticks``.  Unknown keys raise KeyError (typos that a
    plain dict would silently absorb)."""

    def __init__(self, counters: Any):
        self._c = counters
        self._keys = tuple(f.name for f in fields(counters))

    def __getitem__(self, k):
        if k not in self._keys:
            raise KeyError(k)
        return getattr(self._c, k)

    def __setitem__(self, k, v):
        if k not in self._keys:
            raise KeyError(k)
        setattr(self._c, k, v)

    def __delitem__(self, k):
        raise TypeError("typed counters cannot be deleted")

    def __iter__(self):
        return iter(self._keys)

    def __len__(self):
        return len(self._keys)

    def __repr__(self):
        return f"StatsView({dict(self)})"


# engine key -> scheduler counter attribute it mirrors live
_MIRROR = {
    "requests": "requests", "offloaded": "offloaded", "dropped": "dropped",
    "serve_time": "serve_time", "stream_ticks": "ticks",
    "degraded_local": "degraded_local", "rejected": "rejected",
    "breaker_open_ticks": "breaker_open_ticks",
    "breaker_opens": "breaker_opens", "esc_retries": "esc_retries",
    "esc_lost": "esc_lost",
}


class EngineStatsView(StatsView):
    """Engine stats = retired base + the LIVE scheduler's typed counters.

    The engine used to copy the scheduler's fault counters key by key after
    every ``serve_stream`` and zero the originals — two stores that could
    silently diverge.  Now there is one authority: the scheduler's
    :class:`SchedCounters`.  Reads of a mirrored key add the attached
    scheduler's live value; writes adjust the base so the observed total
    becomes the written value (``stats[k] += x`` adds exactly ``x``
    regardless of live activity).  ``prefill_tokens_saved`` mirrors the
    pools' prefix stats the same way.  When the engine replaces its cached
    scheduler, :meth:`detach` folds the live totals into the base so
    nothing is lost."""

    def __init__(self, counters: EngineCounters):
        super().__init__(counters)
        self._sched = None

    def attach(self, sched) -> None:
        if self._sched is not None and self._sched is not sched:
            self.detach()
        self._sched = sched

    def detach(self) -> None:
        """Fold the attached scheduler's live counters into the base."""
        if self._sched is None:
            return
        sched, self._sched = self._sched, None
        for k in tuple(_MIRROR) + ("prefill_tokens_saved",):
            setattr(self._c, k, getattr(self._c, k) + self._live(sched, k))

    @staticmethod
    def _live(sched, k):
        if k == "prefill_tokens_saved":
            return sched.prefix_stats.get("tokens_saved", 0)
        return getattr(sched.counters, _MIRROR[k])

    def __getitem__(self, k):
        v = super().__getitem__(k)
        if self._sched is not None and (k in _MIRROR
                                        or k == "prefill_tokens_saved"):
            v = v + self._live(self._sched, k)
        return v

    def __setitem__(self, k, v):
        if self._sched is not None and (k in _MIRROR
                                        or k == "prefill_tokens_saved"):
            v = v - self._live(self._sched, k)
        super().__setitem__(k, v)


# ---------------------------------------------------------------------------
# streaming histograms
# ---------------------------------------------------------------------------

class Histogram:
    """Streaming log-bucketed histogram: base-2 buckets over [lo, hi).

    Bucket 0 is the underflow (< lo), the last bucket the overflow; bucket
    ``i`` covers ``[lo * 2^(i-1), lo * 2^i)``.  Constant memory, O(1)
    record, quantiles by cumulative-count walk (geometric-midpoint estimate
    within the landing bucket — exact min/max are tracked separately)."""

    def __init__(self, lo: float = 1e-4, hi: float = 100.0,
                 unit: str = "seconds"):
        if lo <= 0 or hi <= lo:
            raise ValueError(f"need 0 < lo < hi, got ({lo}, {hi})")
        self.lo = lo
        self.unit = unit
        self.n_buckets = int(math.ceil(math.log2(hi / lo))) + 2
        self.counts = [0] * self.n_buckets
        self.count = 0
        self.total = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf

    def record(self, v: float) -> None:
        if not math.isfinite(v):
            return
        if v < self.lo:
            i = 0
        else:
            i = min(self.n_buckets - 1,
                    1 + int(math.floor(math.log2(v / self.lo))))
        self.counts[i] += 1
        self.count += 1
        self.total += v
        self.vmin = min(self.vmin, v)
        self.vmax = max(self.vmax, v)

    def upper_edge(self, i: int) -> float:
        return self.lo * 2.0 ** i           # bucket i covers [edge/2, edge)

    def quantile(self, q: float) -> float:
        if not self.count:
            return math.nan
        target = q * self.count
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= target and c:
                if i == 0:
                    return min(self.lo, self.vmax)
                hi = min(self.upper_edge(i), self.vmax)
                lo = max(self.upper_edge(i - 1), self.vmin)
                return math.sqrt(lo * hi) if lo > 0 else hi / 2
        return self.vmax

    def summary(self) -> Dict[str, float]:
        if not self.count:
            return {"count": 0}
        return {"count": self.count,
                "mean": self.total / self.count,
                "min": self.vmin, "max": self.vmax,
                "p50": self.quantile(0.50),
                "p95": self.quantile(0.95),
                "p99": self.quantile(0.99)}


# ---------------------------------------------------------------------------
# spans + traces + ticks
# ---------------------------------------------------------------------------

@dataclass
class Span:
    kind: str                   # queued / admitted / prefill_chunk / ...
    t0: float                   # monotonic seconds
    t1: float                   # t0 == t1 for instant markers
    tier: str                   # "S" / "L" / "" (scheduler-level)
    slot: int = -1
    args: Dict[str, Any] = field(default_factory=dict)


@dataclass
class RequestTrace:
    """One request's span tree (flat list, tree by construction order)."""
    rid: int
    submit_t: float = 0.0
    spans: List[Span] = field(default_factory=list)
    status: str = ""            # set at terminal
    ttft: float = math.nan
    tpot: float = math.nan
    n_tokens: int = 0
    queue_wait_ticks: int = 0
    escalation_retries: int = 0

    @property
    def complete(self) -> bool:
        return bool(self.status)


@dataclass
class TickRecord:
    index: int                  # global scheduler tick number
    t0: float
    t1: float = 0.0
    # ordered (phase, start, end) wall segments within the tick
    segments: List[Tuple[str, float, float]] = field(default_factory=list)
    gauges: Dict[str, float] = field(default_factory=dict)


class Telemetry:
    """Per-run collector threaded through ContinuousScheduler / HIEngine.

    The scheduler holds ``tel = None`` by default; every hook call site is
    guarded by ``if tel is not None`` — disabled telemetry costs one branch
    per site and allocates nothing.  One Telemetry instance may span several
    ``serve_stream`` calls (counters/histograms accumulate; ticks/spans
    append)."""

    def __init__(self):
        self.traces: Dict[int, RequestTrace] = {}
        self.ticks: List[TickRecord] = []
        self.phase_time: Dict[str, float] = {p: 0.0 for p in PHASES}
        self.hists: Dict[str, Histogram] = {
            "ttft": Histogram(1e-4, 100.0),
            "tpot": Histogram(1e-5, 100.0),
            "queue_wait": Histogram(0.5, 4096.0, unit="ticks"),
            "esc_latency": Histogram(1e-4, 100.0),
        }
        self.counters: Optional[SchedCounters] = None   # bound by scheduler
        # GateAudit bound by the scheduler when both are installed — its
        # hi_audit_* families ride prometheus_text; None = no audit lines
        self.audit = None
        # (t, name, args) instant events (SLO watchdog breaches) — rendered
        # as Chrome ``i`` markers on the scheduler track by trace_export
        self.events: List[Tuple[float, str, Dict[str, Any]]] = []
        self._tick: Optional[TickRecord] = None
        # the running phase: (name, start, its profiler annotation), and the
        # tick's step annotation
        self._phase: Optional[Tuple[str, float, Any]] = None
        self._step: Any = None
        # (rid, kind) -> open span awaiting its close
        self._open: Dict[Tuple[int, str], Span] = {}
        # per-(rid, kind) occurrence counters for the [i]/[j]/[k] indices
        self._seq: Dict[Tuple[int, str], int] = {}

    # -- tick lifecycle -----------------------------------------------------

    def begin_tick(self, index: int, phase: str) -> None:
        """Open tick ``index`` with its first phase (a tick left open by a
        run that raised is dropped)."""
        self.drop_tick()
        self._step = profiler.StepTraceAnnotation("sched.tick",
                                                  step_num=index)
        self._step.__enter__()
        t = _now()
        self._tick = TickRecord(index=index, t0=t)
        self._open_phase(phase, t)

    def phase(self, name: str) -> None:
        """End the running phase and start ``name``."""
        t = _now()
        self._close_phase(t)
        self._open_phase(name, t)

    def _open_phase(self, name: str, t: float) -> None:
        ann = profiler.TraceAnnotation(f"sched.{name}")
        ann.__enter__()
        self._phase = (name, t, ann)

    def _close_phase(self, t: float) -> None:
        name, t0, ann = self._phase
        ann.__exit__(None, None, None)
        self._phase = None
        self._tick.segments.append((name, t0, t))
        self.phase_time[name] = self.phase_time.get(name, 0.0) + (t - t0)

    def end_tick(self, gauges: Dict[str, float]) -> None:
        tick = self._tick
        if tick is None:
            return
        tick.t1 = _now()
        self._close_phase(tick.t1)
        self._step.__exit__(None, None, None)
        self._step = None
        tick.gauges = gauges
        self.ticks.append(tick)
        self._tick = None

    def drop_tick(self) -> None:
        """Close the annotations of a tick that dispatched nothing, and keep
        no record of it."""
        if self._tick is None:
            return
        self._phase[2].__exit__(None, None, None)
        self._step.__exit__(None, None, None)
        self._tick, self._phase, self._step = None, None, None

    @property
    def current_tick(self) -> Optional[TickRecord]:
        """The tick in flight, its finished phases in ``segments``."""
        return self._tick

    def instant(self, name: str, **args: Any) -> None:
        """Record a scheduler-level instant event (e.g. an SLO watchdog
        breach) — exported as a Chrome ``i`` marker on the tick track."""
        self.events.append((_now(), name, args))

    @property
    def tick_bracket(self) -> Tuple[float, float]:
        """(start, now) of the in-flight tick — device work inside the tick
        is attributed to this bracket."""
        t = _now()
        return (self._tick.t0 if self._tick is not None else t, t)

    # -- request spans ------------------------------------------------------

    def _trace(self, rid: int, submit_t: float = 0.0) -> RequestTrace:
        tr = self.traces.get(rid)
        if tr is None:
            tr = self.traces[rid] = RequestTrace(rid, submit_t=submit_t)
        return tr

    def _next_idx(self, rid: int, kind: str) -> int:
        i = self._seq.get((rid, kind), 0)
        self._seq[(rid, kind)] = i + 1
        return i

    def span_point(self, rid: int, kind: str, tier: str, slot: int,
                   **args) -> Span:
        """Closed span covering the current tick bracket."""
        t0, t1 = self.tick_bracket
        sp = Span(kind, t0, t1, tier, slot, args)
        self._trace(rid).spans.append(sp)
        return sp

    def span_open(self, rid: int, kind: str, tier: str, slot: int,
                  **args) -> Span:
        sp = Span(kind, _now(), math.nan, tier, slot, args)
        self._trace(rid).spans.append(sp)
        self._open[(rid, kind)] = sp
        return sp

    def span_close(self, rid: int, kind: str, **args) -> Optional[Span]:
        sp = self._open.pop((rid, kind), None)
        if sp is not None:
            sp.t1 = _now()
            sp.args.update(args)
        return sp

    # -- scheduler hooks ----------------------------------------------------

    def req_admitted(self, tier: str, slot: int, rid: int, submit_t: float,
                     *, chunked: bool = False, restore: bool = False,
                     start: int = 0) -> None:
        tr = self._trace(rid, submit_t)
        t0, t1 = self.tick_bracket
        if tier == "L":
            sp = self.span_close(rid, "l_queued", outcome="admitted")
            if sp is not None:
                sp.t1 = t0            # the wait ends where the L tick begins
        # mesh mode names the S replicas "S0".."S{R-1}"; every S-side tier
        # label starts the queued span, the exact "L" label opens l_verify
        if tier.startswith("S") and not any(s.kind == "queued"
                                            for s in tr.spans):
            tr.submit_t = submit_t
            tr.spans.append(Span("queued", submit_t, t0, "S"))
        tr.spans.append(Span("admitted", t0, t1, tier, slot,
                             {"chunked": chunked, "restore": restore,
                              "start": start}))
        if tier == "L":
            # L residency: admission to finish/abort
            self.span_open(rid, "l_verify", "L", slot)

    def req_chunk(self, tier: str, slot: int, rid: int, fed: int,
                  keep: int) -> None:
        self.span_point(rid, "prefill_chunk", tier, slot,
                        i=self._next_idx(rid, f"{tier}:prefill_chunk"),
                        fed=fed, keep=keep)

    def req_decode(self, tier: str, slot: int, rid: int, steps: int) -> None:
        self.span_point(rid, "decode_block", tier, slot,
                        j=self._next_idx(rid, f"{tier}:decode_block"),
                        steps=steps)

    def req_escalated(self, rid: int) -> None:
        """The S answer escalates: the wait for an L slot starts."""
        self.span_open(rid, "l_queued", "L", -1)

    def req_esc_send(self, rid: int, slot: int, attempt: int) -> None:
        self.span_open(rid, "escalate_attempt", "S", slot, k=attempt)

    def req_esc_end(self, rid: int, outcome: str) -> None:
        """Close the in-flight escalate_attempt span: ``outcome`` is
        ``arrived`` / ``lost`` / ``timeout`` / ``aborted`` / ``gave_up``."""
        self.span_close(rid, "escalate_attempt", outcome=outcome)

    def req_esc_retry(self, rid: int, attempt: int,
                      resend_tick: int) -> None:
        self.span_point(rid, "escalate_backoff", "S", -1, k=attempt,
                        resend_tick=resend_tick)

    def req_l_release(self, rid: int, outcome: str) -> None:
        self.span_close(rid, "l_verify", outcome=outcome)

    def req_terminal(self, rid: int, record: Dict[str, Any]) -> None:
        """The request reached its FINAL status: close open spans, stamp the
        terminal marker, and feed the latency histograms."""
        tr = self._trace(rid)
        t = _now()
        self.req_esc_end(rid, "gave_up")
        self.span_close(rid, "l_queued", outcome=record.get("status", ""))
        self.req_l_release(rid, record.get("status", ""))
        tr.status = str(record.get("status", "ok"))
        tr.ttft = float(record.get("ttft", math.nan))
        tr.n_tokens = int(len(record.get("tokens", ())))
        tr.queue_wait_ticks = int(record.get("queue_wait_ticks", 0))
        tr.escalation_retries = int(record.get("escalation_retries", 0))
        if tr.n_tokens > 1 and math.isfinite(tr.ttft):
            first = tr.submit_t + tr.ttft
            tr.tpot = max(t - first, 0.0) / (tr.n_tokens - 1)
            self.hists["tpot"].record(tr.tpot)
        if math.isfinite(tr.ttft):
            self.hists["ttft"].record(tr.ttft)
        self.hists["queue_wait"].record(tr.queue_wait_ticks)
        esc0 = next((s for s in tr.spans
                     if s.kind == "escalate_attempt"), None)
        if esc0 is not None:
            self.hists["esc_latency"].record(t - esc0.t0)
        tr.spans.append(Span("terminal", t, t, "S", -1,
                             {"status": tr.status}))

    # -- exporters ----------------------------------------------------------

    def histogram_summary(self) -> Dict[str, Dict[str, float]]:
        return {name: h.summary() for name, h in self.hists.items()}

    def phase_summary(self) -> Dict[str, float]:
        """Cumulative wall seconds per tick-phase bucket."""
        return dict(self.phase_time)

    def prometheus_text(self) -> str:
        """Prometheus text-format snapshot (see module docstring for the
        key schema).  Every family carries ``# HELP`` + ``# TYPE`` lines,
        label values are escaped per the text exposition format, and the
        histograms' unbounded overflow bucket is folded into ``+Inf``
        (finite ``le`` edges stop at the last bounded bucket)."""
        lines: List[str] = []
        if self.counters is not None:
            for f in fields(self.counters):
                v = getattr(self.counters, f.name)
                metric = f"hi_{f.name}_total"
                lines.append(f"# HELP {metric} Cumulative scheduler "
                             f"counter '{f.name}'.")
                lines.append(f"# TYPE {metric} counter")
                lines.append(f"{metric} {v}")
        lines.append("# HELP hi_tick_phase_seconds_total "
                     f"{_HELP['hi_tick_phase_seconds_total']}")
        lines.append("# TYPE hi_tick_phase_seconds_total counter")
        for p in PHASES:
            lines.append(
                f'hi_tick_phase_seconds_total{{phase="{escape_label(p)}"}} '
                f"{self.phase_time.get(p, 0.0):.9f}")
        if self.ticks:
            lines.append(f"# HELP hi_gauge {_HELP['hi_gauge']}")
            lines.append("# TYPE hi_gauge gauge")
            for k, v in sorted(self.ticks[-1].gauges.items()):
                name, _, tier = k.partition("@")
                # mesh-replica tiers ("S0".."S{R-1}") split into a stable
                # tier="S" plus a replica label, so one PromQL selector
                # aggregates over replicas; plain "S"/"L" stay single-label
                if len(tier) > 1 and tier[0] == "S" and tier[1:].isdigit():
                    tag = f',tier="S",replica="{tier[1:]}"'
                elif tier:
                    tag = f',tier="{escape_label(tier)}"'
                else:
                    tag = ""
                lines.append(
                    f'hi_gauge{{name="{escape_label(name)}"{tag}}} {v}')
        for name, h in self.hists.items():
            unit = h.unit
            metric = f"hi_{name}_{unit}"
            lines.append(f"# HELP {metric} "
                         f"{_HELP.get(name, f'{name} distribution.')}")
            lines.append(f"# TYPE {metric} histogram")
            cum = 0
            # the last bucket is the unbounded overflow: it must NOT emit a
            # finite ``le`` edge — its count reaches the +Inf line only
            last = h.n_buckets - 1
            for i, c in enumerate(h.counts):
                cum += c
                if c and i < last:
                    edge = h.upper_edge(i)
                    lines.append(f'{metric}_bucket{{le="{edge:g}"}} {cum}')
            lines.append(f'{metric}_bucket{{le="+Inf"}} {h.count}')
            lines.append(f"{metric}_sum {h.total:.9f}")
            lines.append(f"{metric}_count {h.count}")
        if self.audit is not None:
            lines.extend(self.audit.prometheus_lines())
        return "\n".join(lines) + "\n"
