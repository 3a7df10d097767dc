"""Serving telemetry invariants (PR 7).

* spans are emitted for EXACTLY the requests that terminate, with the
  terminal status matching the result record (including the zero-budget
  dropper and admission rejection);
* telemetry-enabled greedy output is token-identical to disabled (the
  collector is pure host-side bookkeeping);
* one ``_host_fetch`` sync per tick and ``stream_compiles == 1`` hold with
  telemetry ON;
* ``stats['serve_time']`` is single-entry (one ``finally``), surviving a
  mid-run exception;
* engine and scheduler counter views can NEVER diverge (the engine reads
  the scheduler's typed counters live instead of copy-and-zeroing);
* histogram bucketing/quantiles, the Prometheus snapshot, the StatsView
  dict API, and the Chrome trace structure;
* the tick phases tile each tick and appear in a ``jax.profiler`` trace as
  ``sched.<phase>`` annotations inside ``sched.tick`` steps, and the
  ``l_queued`` span times each escalation's wait for an L slot.
"""
import json
import math

import numpy as np
import pytest

from repro.configs.base import HIConfig
from repro.configs.registry import ARCHS
from repro.serving import engine as engine_mod
from repro.serving import trace_export
from repro.serving.batcher import AdmissionQueue, Request
from repro.serving.engine import build_engine
from repro.serving.faults import STATUSES, FaultSchedule, RetryPolicy
from repro.serving.scheduler import ContinuousScheduler
from repro.serving.telemetry import (Histogram, SchedCounters, StatsView,
                                     Telemetry)

STEPS = 3
KW = dict(buckets=(8, 16), num_slots=3, l_slots=2, page_size=8)

_STATE = {}


def _requests(n=7, dropper=True):
    rng = np.random.default_rng(42)
    reqs = []
    for i in range(n):
        ln = int(rng.integers(4, 16))
        budget = 0.0 if (dropper and i == 5) else None
        reqs.append(Request(i, rng.integers(0, 500, ln).astype(np.int32),
                            max_new_tokens=STEPS, latency_budget=budget))
    return reqs


def _eng():
    if not _STATE:
        cfg = ARCHS["qwen2-1.5b"].reduced()
        _STATE["eng"] = build_engine(
            cfg, HIConfig(theta=0.6, capacity_factor=1.0),
            max_new_tokens=STEPS, cache_len=32)
    return _STATE["eng"]


# ---------------------------------------------------------------------------
# span completeness + terminal-status agreement
# ---------------------------------------------------------------------------

def test_spans_for_exactly_the_terminating_requests():
    eng = _eng()
    tel = Telemetry()
    res = eng.serve_stream(_requests(), telemetry=tel, validate=True, **KW)
    assert set(tel.traces) == set(res), \
        "span trees must exist for exactly the requests that terminated"
    for rid, rec in res.items():
        tr = tel.traces[rid]
        assert tr.complete
        assert tr.status == rec["status"] and tr.status in STATUSES
        kinds = [s.kind for s in tr.spans]
        assert kinds[0] == "queued" and kinds[-1] == "terminal"
        assert kinds.count("terminal") == 1, "exactly one terminal marker"
        assert "admitted" in kinds
        if rec["status"] == "ok" and rec["served_remote"]:
            assert "escalate_attempt" in kinds and "l_verify" in kinds
        # every span closed: no NaN end times survive termination
        assert all(math.isfinite(s.t1) for s in tr.spans)
    # the traces mirror the result records
    assert all(tel.traces[r].status == res[r]["status"] for r in res)


def test_enabled_output_token_identical_to_disabled():
    eng = _eng()
    base = eng.serve_stream(_requests(), validate=True, **KW)
    on = eng.serve_stream(_requests(), telemetry=Telemetry(),
                          validate=True, **KW)
    assert set(base) == set(on)
    for rid in base:
        np.testing.assert_array_equal(base[rid]["tokens"], on[rid]["tokens"])
        assert base[rid]["status"] == on[rid]["status"]
    assert eng.stats["stream_compiles"] == 1


def test_one_host_sync_per_tick_with_telemetry_on(monkeypatch):
    eng = _eng()
    tel = Telemetry()
    syncs = {"n": 0}
    real = engine_mod._host_fetch

    def counting(x):
        syncs["n"] += 1
        return real(x)

    monkeypatch.setattr(engine_mod, "_host_fetch", counting)
    ticks0 = eng.stats["stream_ticks"]
    eng.serve_stream(_requests(dropper=False), telemetry=tel, **KW)
    assert syncs["n"] == eng.stats["stream_ticks"] - ticks0 == len(tel.ticks)
    assert eng.stats["stream_compiles"] == 1


# ---------------------------------------------------------------------------
# satellite: serve_time single-entry
# ---------------------------------------------------------------------------

def test_serve_time_single_entry_on_exception(monkeypatch):
    """The old code added serve_time on each return path; the ``finally``
    must record it exactly once INCLUDING when the run dies mid-loop."""
    eng = _eng()
    cfg = ARCHS["qwen2-1.5b"].reduced()
    sched = ContinuousScheduler(
        eng.s, eng.l, HIConfig(theta=0.0, capacity_factor=1.0),
        max_prompt_len=16, max_new_tokens=STEPS, num_slots=2, l_slots=1,
        page_size=8, decode_block=2, prefix_sharing=False)
    rng = np.random.default_rng(3)
    queue = AdmissionQueue(buckets=(8, 16))
    queue.submit(Request(0, rng.integers(0, cfg.vocab_size, 8)
                         .astype(np.int32), max_new_tokens=STEPS))
    assert sched.stats["serve_time"] == 0.0

    def boom(theta_j):
        raise RuntimeError("injected dispatch failure")

    monkeypatch.setattr(sched, "_dispatch", boom)
    with pytest.raises(RuntimeError, match="injected"):
        sched.run(queue)
    t_fail = sched.stats["serve_time"]
    assert t_fail > 0.0, "the finally block must book the failed run's time"

    # a successful run books exactly one more increment
    monkeypatch.undo()
    queue2 = AdmissionQueue(buckets=(8, 16))
    queue2.submit(Request(1, rng.integers(0, cfg.vocab_size, 8)
                          .astype(np.int32), max_new_tokens=STEPS))
    sched.run(queue2)
    assert sched.stats["serve_time"] > t_fail


# ---------------------------------------------------------------------------
# satellite: engine/scheduler counter views never diverge
# ---------------------------------------------------------------------------

def test_engine_view_never_diverges_from_scheduler():
    eng = _eng()
    eng.serve_stream(_requests(), validate=True, **KW)
    sched = eng._stream[1]
    base0 = {k: eng.stats[k] for k in eng.stats}
    eng.serve_stream(_requests(), validate=True,
                     faults=FaultSchedule(seed=7, loss_prob=1.0),
                     retry=RetryPolicy(ack_timeout_ticks=2, max_retries=1,
                                       backoff_cap_ticks=2,
                                       breaker_threshold=2,
                                       breaker_cooldown_ticks=4), **KW)
    assert eng._stream[1] is sched, "scheduler must be reused (same config)"
    # mirrored keys: engine total == base before this run + scheduler delta
    # is implied by construction; what must hold OBSERVABLY is that engine
    # totals move in lock-step with the scheduler's counters
    for k in ("requests", "offloaded", "dropped", "degraded_local",
              "rejected", "breaker_open_ticks", "breaker_opens",
              "esc_retries", "esc_lost"):
        assert eng.stats[k] - base0[k] >= 0
    # the live identity: engine total minus retired base == scheduler live
    for ek, sk in (("requests", "requests"), ("stream_ticks", "ticks"),
                   ("degraded_local", "degraded_local"),
                   ("esc_retries", "esc_retries"),
                   ("esc_lost", "esc_lost")):
        assert eng.stats[ek] == getattr(eng.counters, ek) + sched.stats[sk]
    # a 100%-loss run with max_retries=1 must degrade every escalation, and
    # both views agree on the count
    assert sched.stats["degraded_local"] > 0
    assert eng.stats["degraded_local"] == \
        eng.counters.degraded_local + sched.counters.degraded_local
    # writes through the view stay arithmetically exact under a live mirror
    before = eng.stats["requests"]
    eng.stats["requests"] += 5
    assert eng.stats["requests"] == before + 5
    eng.stats["requests"] -= 5


# ---------------------------------------------------------------------------
# primitives: StatsView, Histogram, Prometheus snapshot, Chrome trace
# ---------------------------------------------------------------------------

def test_stats_view_dict_api():
    c = SchedCounters()
    v = StatsView(c)
    v["ticks"] += 3
    assert c.ticks == 3 and v["ticks"] == 3
    assert "ticks" in v and len(v) == len(dict(v))
    assert dict(**v)["ticks"] == 3            # ** unpacking (summary())
    with pytest.raises(KeyError):
        v["not_a_counter"]
    with pytest.raises(KeyError):
        v["not_a_counter"] = 1
    with pytest.raises(TypeError):
        del v["ticks"]


def test_histogram_buckets_and_quantiles():
    h = Histogram(lo=1e-3, hi=10.0)
    for v in (0.0005, 0.002, 0.002, 0.004, 0.008, 5.0):
        h.record(v)
    h.record(float("nan"))                     # ignored
    s = h.summary()
    assert s["count"] == 6
    assert s["min"] == pytest.approx(0.0005) and s["max"] == pytest.approx(5.0)
    assert s["mean"] == pytest.approx(sum((0.0005, 0.002, 0.002, 0.004,
                                           0.008, 5.0)) / 6)
    # p50 lands in the [2ms, 4ms) bucket; p99 in the overflow-side bucket
    assert 0.001 <= s["p50"] <= 0.004
    assert s["p99"] <= 5.0 and s["p99"] >= 1.0
    # monotone quantiles
    assert s["p50"] <= s["p95"] <= s["p99"]
    empty = Histogram()
    assert empty.summary() == {"count": 0}
    assert math.isnan(empty.quantile(0.5))


def test_prometheus_snapshot_keys():
    eng = _eng()
    tel = Telemetry()
    eng.serve_stream(_requests(), telemetry=tel, **KW)
    txt = tel.prometheus_text()
    for key in ("hi_requests_total", "hi_degraded_local_total",
                "hi_ticks_total",
                'hi_tick_phase_seconds_total{phase="dispatch"}',
                'hi_tick_phase_seconds_total{phase="host_fetch"}',
                'hi_gauge{name="free_pages",tier="S"}',
                'hi_gauge{name="breaker_state"}',
                "hi_ttft_seconds_count", "hi_ttft_seconds_sum",
                'hi_ttft_seconds_bucket{le="+Inf"}',
                "hi_tpot_seconds_count", "hi_queue_wait_ticks_count"):
        assert key in txt, f"missing Prometheus key: {key}"


def test_chrome_trace_structure(tmp_path):
    eng = _eng()
    tel = Telemetry()
    res = eng.serve_stream(_requests(), telemetry=tel, validate=True, **KW)
    path = tmp_path / "trace.json"
    doc = trace_export.write_chrome_trace(tel, str(path))
    loaded = json.loads(path.read_text())
    assert loaded["traceEvents"], "trace must not be empty"
    ev = doc["traceEvents"]
    # one complete span tree per request: a terminal instant per request,
    # status matching the result record
    terminals = {e["args"]["request_id"]: e for e in ev
                 if e["ph"] == "i" and e["name"].startswith("terminal:")}
    assert set(terminals) == set(res)
    for rid, rec in res.items():
        assert terminals[rid]["name"] == f"terminal:{rec['status']}"
    # tick-phase slices on the scheduler track
    phases = {e["name"] for e in ev if e.get("pid") == 0 and e["ph"] == "X"}
    assert {"build_operands", "dispatch", "host_fetch"} <= phases
    # escalations drawn as S->L flows: starts pair with finishes by id
    starts = {e["id"] for e in ev if e["ph"] == "s"}
    finishes = {e["id"] for e in ev if e["ph"] == "f"}
    served_remote = {r for r, rec in res.items() if rec["served_remote"]}
    assert served_remote <= starts, "every served escalation has a flow start"
    assert served_remote <= finishes, "and a flow finish on the L track"
    # counter (gauge) events exist
    assert any(e["ph"] == "C" for e in ev)
    # timestamps are relative: nothing starts before 0
    assert min(e["ts"] for e in ev if "ts" in e) >= 0.0


# ---------------------------------------------------------------------------
# the profiler trace's host phases, and the wait for the L tier
# ---------------------------------------------------------------------------

def test_profiler_trace_holds_each_phase_and_tick(tmp_path):
    """Under ``jax.profiler`` every recorded tick segment is one
    ``sched.<phase>`` host event (``observe`` among them) and every tick one
    ``sched.tick`` step; the segments tile each tick."""
    import jax
    from jax.profiler import ProfileData

    eng = _eng()
    tel = Telemetry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.serve_stream(_requests(dropper=False), telemetry=tel, **KW)
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    names = [ev.name for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for ev in line.events
             if ev.name.startswith("sched.")]
    segs = [name for tk in tel.ticks for name, _, _ in tk.segments]
    assert names.count("sched.tick") == len(tel.ticks) > 0
    assert sorted(n for n in names if n != "sched.tick") == \
        sorted(f"sched.{p}" for p in segs)
    assert "observe" in segs
    for tk in tel.ticks:
        assert tk.segments[0][1] == tk.t0 and tk.segments[-1][2] == tk.t1
        assert [s[0] for s in tk.segments][-2:] == ["postprocess", "observe"]
        assert all(a[2] == b[1] for a, b in zip(tk.segments, tk.segments[1:]))


def test_l_queued_spans_the_wait_for_an_l_slot():
    """Every request that crossed to the L tier has one ``l_queued`` span,
    from its escalation to the start of its L ``admitted`` span, over its
    transport attempts; requests answered locally have none."""
    eng = _eng()
    sched = ContinuousScheduler(
        eng.s, eng.l, eng.hi, max_prompt_len=16, max_new_tokens=STEPS,
        num_slots=3, l_slots=2, page_size=8)

    def serve(theta, tel=None):
        queue = AdmissionQueue(buckets=(8, 16))
        for r in _requests(dropper=False):
            queue.submit(r)
        sched.set_telemetry(tel)
        return sched.run(queue, theta=theta)

    # theta at the median S confidence: half the requests escalate
    theta = float(np.median([r["confidence"] for r in serve(-1.0).values()]))
    tel = Telemetry()
    res = serve(theta, tel)
    remote = {r for r, rec in res.items() if rec["served_remote"]}
    assert remote and remote != set(res)
    for rid, rec in res.items():
        spans = tel.traces[rid].spans
        waits = [s for s in spans if s.kind == "l_queued"]
        if rid not in remote:
            assert not waits and not rec["offloaded"]
            continue
        (wait,) = waits
        l_admit = next(s for s in spans
                       if s.kind == "admitted" and s.tier == "L")
        assert wait.t1 == l_admit.t0 and wait.args["outcome"] == "admitted"
        first = next(s for s in spans if s.kind == "escalate_attempt")
        assert wait.t0 <= first.t0 < wait.t1
