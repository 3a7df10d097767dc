"""Fault-tolerant escalation across the S→L serving path: deterministic
seeded injection (serving/faults.py), retry with capped backoff, the
fail-local circuit breaker (closed → open → half-open), bounded admission
rejection, the arXiv:2112.11413 drop policy's resource accounting, and
leak-free degradation — all host-side, with the ONE compiled tick
executable untouched."""
import numpy as np
import pytest

from repro.configs.base import HIConfig
from repro.configs.registry import ARCHS
from repro.serving.batcher import Request
from repro.serving.engine import build_engine
from repro.serving.faults import (STATUSES, CircuitBreaker, FaultSchedule,
                                  RetryPolicy)

STEPS = 3
KW = dict(buckets=(8,), num_slots=2, page_size=8)


def _reqs(cfg, n, **kw):
    rng = np.random.default_rng(0)
    return [Request(i, rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=STEPS, **kw) for i in range(n)]


@pytest.fixture(scope="module")
def eng():
    """One engine (ONE compiled tick executable) shared by every fault
    scenario below — fault schedules are per-run operand state, so reuse
    across wildly different schedules is itself part of the test."""
    cfg = ARCHS["qwen2-1.5b"].reduced()
    # theta 1.1 > any confidence: every request wants escalation, which
    # maximises the faulted path's exposure
    return cfg, build_engine(cfg, HIConfig(theta=1.1, capacity_factor=1.0),
                             max_new_tokens=STEPS, cache_len=32)


@pytest.fixture(scope="module")
def ref(eng):
    """Fault-free reference outputs on the shared traffic."""
    cfg, e = eng
    # 8 covers every test below (the _reqs stream is a deterministic prefix:
    # the first n requests are identical for any n)
    return e.serve_stream(_reqs(cfg, 8), validate=True, **KW)


# ---------------------------------------------------------------------------
# faults.py units
# ---------------------------------------------------------------------------
def test_fault_schedule_deterministic():
    """Every transit decision is a pure function of (seed, rid, attempt):
    replaying a schedule — in any call order — yields identical faults."""
    fs = FaultSchedule(seed=7, loss_prob=0.4, delay_ticks=1, delay_jitter=3)
    draws = [(rid, att, fs.transit(rid, att))
             for rid in range(20) for att in range(3)]
    for rid, att, d in reversed(draws):          # different call order
        assert fs.transit(rid, att) == d
    assert any(d is None for _, _, d in draws)   # losses occur
    kept = [d for _, _, d in draws if d is not None]
    assert kept and all(1 <= d <= 4 for d in kept)
    # a different seed gives a different fault sequence
    fs2 = FaultSchedule(seed=8, loss_prob=0.4, delay_ticks=1, delay_jitter=3)
    assert [fs2.transit(r, a) for r, a, _ in draws] != [d for _, _, d in draws]
    # window queries
    fs3 = FaultSchedule(outages=((2, 5),), spikes=((7, 9),))
    assert not fs3.in_outage(1) and fs3.in_outage(2) and fs3.in_outage(4)
    assert not fs3.in_outage(5)                  # [a, b) half-open
    assert fs3.l_paused(8) and not fs3.l_paused(6)


def test_circuit_breaker_state_machine():
    """closed → open on CONSECUTIVE failures, cooldown → half-open, probe
    failure re-opens, probe success closes and resets the failure count."""
    pol = RetryPolicy(breaker_threshold=3, breaker_cooldown_ticks=5)
    brk = CircuitBreaker(pol)
    brk.record_failure(0)
    brk.record_success()                         # success resets the streak
    brk.record_failure(1)
    brk.record_failure(1)
    assert brk.state_at(2) == CircuitBreaker.CLOSED
    brk.record_failure(2)                        # 3rd consecutive: opens
    assert brk.state == CircuitBreaker.OPEN and brk.opens == 1
    assert brk.state_at(6) == CircuitBreaker.OPEN
    assert brk.state_at(7) == CircuitBreaker.HALF_OPEN
    brk.record_failure(7)                        # probe fails: re-opens
    assert brk.state == CircuitBreaker.OPEN and brk.opens == 2
    assert brk.state_at(12) == CircuitBreaker.HALF_OPEN
    brk.record_success()                         # probe succeeds: closes
    assert brk.state == CircuitBreaker.CLOSED and brk.failures == 0


def test_active_faults_run_on_chunk_sharing_build():
    """An active loss/delay schedule composes with chunked prefill and
    prefix sharing: one compile, exactly one record with a legal status per
    request, and no slot holds pages after the drain."""
    cfg = ARCHS["qwen2-1.5b"].reduced()
    e = build_engine(cfg, HIConfig(theta=1.1, capacity_factor=1.0),
                     max_new_tokens=STEPS, cache_len=48)
    rng = np.random.default_rng(1)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=STEPS)
            for i, n in enumerate([8, 24, 16, 24, 8, 16])]
    out = e.serve_stream(
        reqs, buckets=(8, 16, 24), num_slots=3, page_size=8,
        prefix_sharing=True, chunk_prefill=True, chunk_size=8,
        validate=True,
        faults=FaultSchedule(seed=5, loss_prob=0.4, delay_ticks=1,
                             delay_jitter=2),
        retry=RetryPolicy(ack_timeout_ticks=2, max_retries=2,
                          backoff_base_ticks=1, backoff_cap_ticks=2))
    assert sorted(out) == list(range(len(reqs)))
    assert all(r["status"] in STATUSES for r in out.values())
    assert e.stats["esc_lost"] > 0            # the schedule was active
    assert e.stats["stream_compiles"] == 1
    sched = e._stream[1]
    assert sched.srt.pool.held_slots == [] and sched.lrt.pool.held_slots == []


# ---------------------------------------------------------------------------
# scheduler resilience, end to end
# ---------------------------------------------------------------------------
def test_lost_escalations_degrade_local(eng, ref):
    """Total escalation loss: retries exhaust, every request degrades to its
    S-tier answer (token-identical to the fault-free S run), pages don't
    leak, and the executable never recompiles."""
    cfg, e = eng
    deg0 = e.stats["degraded_local"]
    out = e.serve_stream(
        _reqs(cfg, 6), validate=True,
        faults=FaultSchedule(seed=3, loss_prob=1.0),
        retry=RetryPolicy(ack_timeout_ticks=1, max_retries=1,
                          breaker_threshold=100),   # isolate the retry path
        **KW)
    assert set(out) == set(range(6))
    for rid, rec in out.items():
        assert rec["status"] == "degraded_local"
        assert rec["offloaded"] and not rec["served_remote"]
        np.testing.assert_array_equal(rec["tokens"], rec["s_tokens"])
        np.testing.assert_array_equal(rec["tokens"], ref[rid]["s_tokens"])
        assert rec["escalation_retries"] == 1
    assert e.stats["degraded_local"] - deg0 == 6
    assert e.stats["esc_lost"] >= 6
    sched = e._stream[1]
    assert sched.srt.pool.held_slots == [] and sched.lrt.pool.held_slots == []
    assert e.stats["stream_compiles"] == 1


def test_outage_opens_breaker_then_recovers(eng, ref):
    """An L outage window aborts in-flight L work (leak-free), consecutive
    failures open the breaker into fail-local mode, and after the window +
    cooldown the half-open probe re-admits escalations — later requests are
    served remote again, with outputs identical to the fault-free run."""
    cfg, e = eng
    opens0 = e.stats["breaker_opens"]
    open_ticks0 = e.stats["breaker_open_ticks"]
    out = e.serve_stream(
        _reqs(cfg, 8), validate=True,
        faults=FaultSchedule(seed=5, outages=((1, 4),)),
        retry=RetryPolicy(ack_timeout_ticks=1, max_retries=1,
                          breaker_threshold=2, breaker_cooldown_ticks=2),
        **KW)
    statuses = {rid: rec["status"] for rid, rec in out.items()}
    assert set(statuses.values()) <= {"ok", "degraded_local"}
    assert "degraded_local" in statuses.values()      # outage casualties
    assert "ok" in statuses.values()                  # post-outage recovery
    for rid, rec in out.items():
        np.testing.assert_array_equal(rec["s_tokens"], ref[rid]["s_tokens"])
        if rec["status"] == "ok":
            assert rec["served_remote"]
            np.testing.assert_array_equal(rec["tokens"], ref[rid]["tokens"])
        else:
            np.testing.assert_array_equal(rec["tokens"], rec["s_tokens"])
    assert e.stats["breaker_opens"] > opens0
    assert e.stats["breaker_open_ticks"] > open_ticks0
    sched = e._stream[1]
    sched.srt.pool.check_invariants()
    sched.lrt.pool.check_invariants()
    assert sched.srt.pool.held_slots == [] and sched.lrt.pool.held_slots == []
    assert e.stats["stream_compiles"] == 1


def test_pure_delay_keeps_outputs_identical(eng, ref):
    """Delivery delay alone (no loss, no windows) only stretches the queue
    wait: every escalation still lands on L and outputs are token-identical
    to the fault-free run."""
    cfg, e = eng
    out = e.serve_stream(
        _reqs(cfg, 6), validate=True,
        faults=FaultSchedule(seed=11, delay_ticks=2, delay_jitter=2),
        retry=RetryPolicy(ack_timeout_ticks=8), **KW)
    for rid, rec in out.items():
        assert rec["status"] == "ok" and rec["served_remote"]
        assert rec["queue_wait_ticks"] >= 2
        np.testing.assert_array_equal(rec["tokens"], ref[rid]["tokens"])
    assert e.stats["stream_compiles"] == 1


def test_drop_expired_releases_every_l_resource(eng):
    """Satellite check for the arXiv:2112.11413 drop path: a queued
    escalation reserves NOTHING on the L side at S-finish time (lookup and
    page claim both happen at L admission), so repeated drops must leave the
    L pool byte-for-byte free and both pools' invariants intact."""
    cfg, e = eng
    sched = e._stream[1] if e._stream else None
    for _ in range(2):                        # repeated drops, warm index
        out = e.serve_stream(_reqs(cfg, 6, latency_budget=0.0),
                             validate=True, **KW)
        sched = e._stream[1]
        for rec in out.values():
            assert rec["status"] == "dropped" and rec["dropped"]
            assert rec["offloaded"] and not rec["served_remote"]
        sched.srt.pool.check_invariants()
        sched.lrt.pool.check_invariants()
        # the L tier never admitted anything: no slots held, and every
        # non-null page is free or index-retained from EARLIER (ok) runs
        assert sched.lrt.pool.held_slots == []
    assert e.stats["stream_compiles"] == 1


def test_admission_rejection_is_bounded():
    """Satellite regression: a prompt whose page demand can NEVER be
    satisfied used to spin forever at the queue head (scheduler.py
    ``queue.appendleft``); it must now fail with ``status='rejected'`` and a
    clear warning after ``admit_retry_limit`` fruitless ticks, while
    satisfiable traffic behind it is still served."""
    from repro.serving.batcher import AdmissionQueue
    from repro.serving.scheduler import ContinuousScheduler

    cfg = ARCHS["qwen2-1.5b"].reduced()
    hi = HIConfig(theta=0.0, capacity_factor=1.0)    # S-only
    eng = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32)
    # 2 usable pages (num_pages=3 incl. the null page): a 16-bucket prompt
    # needs 3 pages of context and can never be admitted; an 8-bucket one
    # needs 2 and fits
    sched = ContinuousScheduler(
        eng.s, eng.l, hi, max_prompt_len=16, max_new_tokens=STEPS,
        num_slots=2, l_slots=1, page_size=8, decode_block=2,
        prefix_sharing=False, num_pages=3)
    sched.set_faults(policy=RetryPolicy(admit_retry_limit=4))
    rng = np.random.default_rng(2)
    queue = AdmissionQueue(buckets=(8, 16))
    queue.submit(Request(0, rng.integers(0, cfg.vocab_size, 16)
                         .astype(np.int32), max_new_tokens=STEPS))
    queue.submit(Request(1, rng.integers(0, cfg.vocab_size, 8)
                         .astype(np.int32), max_new_tokens=STEPS))
    with pytest.warns(RuntimeWarning, match="rejected"):
        results = sched.run(queue)
    assert set(results) == {0, 1}
    assert results[0]["status"] == "rejected"
    assert len(results[0]["tokens"]) == 0
    assert results[1]["status"] == "ok"
    assert len(results[1]["tokens"]) == STEPS
    assert sched.stats["rejected"] == 1
    sched.srt.pool.check_invariants()
    sched.lrt.pool.check_invariants()
    assert sched.srt.pool.held_slots == [] and sched.lrt.pool.held_slots == []


def test_spike_window_delays_but_serves(eng, ref):
    """A latency-spike window pauses L admission without failing anything:
    escalations wait it out in the queue and are then served remote with
    fault-free-identical outputs; the wait is visible in the records."""
    cfg, e = eng
    out = e.serve_stream(
        _reqs(cfg, 6), validate=True,
        faults=FaultSchedule(seed=13, spikes=((0, 8),)), **KW)
    for rid, rec in out.items():
        assert rec["status"] == "ok" and rec["served_remote"]
        np.testing.assert_array_equal(rec["tokens"], ref[rid]["tokens"])
    assert any(rec["queue_wait_ticks"] >= 3 for rec in out.values())
    assert e.stats["stream_compiles"] == 1
