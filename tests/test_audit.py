"""Decision-quality observability invariants (PR 9).

* the streaming reliability bins / ECE match a ``core/calibrate.p_histogram``
  NumPy oracle on the same decision stream (unit stream incl. bin-edge
  values, AND the ground-truthed records of a real serving run);
* audit-enabled greedy output is token-identical to disabled, with
  ``stream_compiles == 1`` and ONE ``_host_fetch`` per tick — in BOTH
  ``kv_dtype`` modes (the audit rides the existing sync);
* per-traffic-class offload rates agree with the result records'
  ``offloaded`` flags (``Request.tclass`` threading);
* completed escalations feed ``l_agree`` ground truth and the
  empirical-regret counters reconcile with the record stream;
* the SLO watchdog emits breaches as telemetry instant events, rendered in
  the Chrome trace, and ``hi_audit_*`` families (with ``# HELP``) appear in
  ``prometheus_text`` — whose histogram overflow bucket must NOT report a
  finite ``le`` edge (satellite fix).
"""
import numpy as np
import pytest

from repro.configs.base import HIConfig
from repro.configs.registry import ARCHS
from repro.core.calibrate import p_histogram
from repro.serving import engine as engine_mod
from repro.serving import trace_export
from repro.serving.audit import (GateAudit, ReliabilityBins, SLOThresholds,
                                 SLOWatchdog)
from repro.serving.batcher import Request
from repro.serving.engine import build_engine
from repro.serving.flight_recorder import FlightRecorder
from repro.serving.telemetry import Telemetry

STEPS = 3
KW = dict(buckets=(8, 16), num_slots=3, l_slots=2, page_size=8)

_STATE = {}


def _requests(n=7, tclass=False):
    rng = np.random.default_rng(42)
    reqs = []
    for i in range(n):
        ln = int(rng.integers(4, 16))
        reqs.append(Request(i, rng.integers(0, 500, ln).astype(np.int32),
                            max_new_tokens=STEPS,
                            tclass=("interactive", "batch")[i % 2]
                            if tclass else ""))
    return reqs


def _eng(kv_dtype="bf16"):
    if kv_dtype not in _STATE:
        cfg = ARCHS["qwen2-1.5b"].reduced()
        _STATE[kv_dtype] = build_engine(
            cfg, HIConfig(theta=0.6, capacity_factor=1.0),
            max_new_tokens=STEPS, cache_len=32)
    return _STATE[kv_dtype]


# ---------------------------------------------------------------------------
# streaming bins vs the p_histogram NumPy oracle
# ---------------------------------------------------------------------------

def test_reliability_bins_match_p_histogram_oracle():
    rng = np.random.default_rng(0)
    conf = rng.random(500)
    # include every edge case the bin rule must get right: exact bin edges,
    # 0.0, and 1.0 (np.histogram closes the last bin)
    conf = np.concatenate([conf, np.linspace(0.0, 1.0, 21), [0.0, 1.0]])
    ok = rng.random(conf.size) < conf          # roughly calibrated stream
    bins = ReliabilityBins(bins=20)
    for c, o in zip(conf, ok):
        bins.record(float(c), bool(o))
    oracle = p_histogram(conf, ok.astype(np.float32), bins=20)
    np.testing.assert_array_equal(bins.edges, oracle["edges"])
    np.testing.assert_array_equal(bins.correct, oracle["correct"])
    np.testing.assert_array_equal(bins.incorrect, oracle["incorrect"])
    # ECE against a direct NumPy evaluation of the definition
    n_b = bins.correct + bins.incorrect
    idx = np.clip(np.searchsorted(bins.edges, conf, side="right") - 1,
                  0, 19)
    conf_sum = np.bincount(idx, weights=conf, minlength=20)
    live = n_b > 0
    ece = np.sum(n_b[live] / conf.size
                 * np.abs(bins.correct[live] / n_b[live]
                          - conf_sum[live] / n_b[live]))
    assert bins.ece() == pytest.approx(float(ece))
    assert bins.count == conf.size


def test_plain_run_bins_match_oracle_on_recorded_stream():
    """The ground-truthed records of a run whose requests all escalate
    (theta above every confidence: each completed escalation yields one
    ``l_agree`` record), replayed through the oracle, must reproduce the
    audit's streaming bins exactly."""
    cfg = ARCHS["qwen2-1.5b"].reduced()
    eng = build_engine(cfg, HIConfig(theta=1.1, capacity_factor=1.0),
                       max_new_tokens=6, cache_len=48)
    aud = GateAudit(bins=20)
    rng = np.random.default_rng(1)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=6) for i in range(6)]
    out = eng.serve_stream(reqs, buckets=(8,), num_slots=2, page_size=8,
                           decode_block=4, audit=aud)
    truthed = [r for r in aud.records if r.ok is not None]
    assert len(truthed) == sum(r["served_remote"] for r in out.values()) \
        == len(reqs)
    assert {r.kind for r in truthed} == {"l_agree"}
    conf = np.array([r.conf for r in truthed])
    ok = np.array([r.ok for r in truthed], np.float32)
    oracle = p_histogram(conf, ok, bins=20)
    np.testing.assert_array_equal(aud.overall.correct, oracle["correct"])
    np.testing.assert_array_equal(aud.overall.incorrect, oracle["incorrect"])
    assert aud.outcomes == len(truthed)
    # regret counters reconcile with the raw stream
    wasted = sum(1 for r in truthed if r.offload and r.ok)
    missed = sum(1 for r in truthed if not r.offload and not r.ok)
    assert aud.wasted_offload == wasted and aud.missed_local == missed
    assert aud.regret_cost == pytest.approx(
        wasted * aud.beta + missed * (1 - aud.beta))


# ---------------------------------------------------------------------------
# audit on == audit off, one sync per tick, both kv_dtype modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_audit_token_identical_and_single_sync(kv_dtype, monkeypatch):
    eng = _eng(kv_dtype)
    base = eng.serve_stream(_requests(), validate=True, kv_dtype=kv_dtype,
                            **KW)
    syncs = {"n": 0}
    real = engine_mod._host_fetch

    def counting(x):
        syncs["n"] += 1
        return real(x)

    monkeypatch.setattr(engine_mod, "_host_fetch", counting)
    aud = GateAudit()
    ticks0 = eng.stats["stream_ticks"]
    on = eng.serve_stream(_requests(), validate=True, kv_dtype=kv_dtype,
                          audit=aud, **KW)
    assert syncs["n"] == eng.stats["stream_ticks"] - ticks0, \
        "the audit must ride the tick's ONE existing host fetch"
    assert eng.stats["stream_compiles"] == 1
    assert set(base) == set(on)
    for rid in base:
        np.testing.assert_array_equal(base[rid]["tokens"], on[rid]["tokens"])
        assert base[rid]["status"] == on[rid]["status"]
    assert aud.decisions > 0
    # plain mode: every completed escalation yields one agreement sample
    remote = sum(1 for r in on.values() if r["served_remote"])
    l_agree = [r for r in aud.records if r.kind == "l_agree"]
    assert len(l_agree) == remote == aud.outcomes


# ---------------------------------------------------------------------------
# traffic classes
# ---------------------------------------------------------------------------

def test_per_tclass_offload_rates_match_records():
    eng = _eng()
    aud = GateAudit()
    res = eng.serve_stream(_requests(tclass=True), validate=True, audit=aud,
                           **KW)
    reqs = _requests(tclass=True)
    by_class = {}
    for r in reqs:
        by_class.setdefault(r.tclass, []).append(res[r.request_id])
    assert set(aud.classes) == set(by_class)
    for t, recs in by_class.items():
        off = sum(1 for r in recs if r["offloaded"])
        assert aud.classes[t].requests == len(recs)
        assert aud.classes[t].offloaded == off
        assert aud.offload_rate(t) == pytest.approx(off / len(recs))
    total_off = sum(1 for r in res.values() if r["offloaded"])
    assert aud.offload_rate() == pytest.approx(total_off / len(res))
    assert aud.ece("no-such-class") == 0.0


# ---------------------------------------------------------------------------
# watchdog + exporters
# ---------------------------------------------------------------------------

def test_watchdog_breaches_reach_trace_and_recorder(tmp_path):
    eng = _eng()
    tel = Telemetry()
    aud = GateAudit()
    wd = SLOWatchdog(SLOThresholds(queue_depth=0, offload_rate_max=0.0,
                                   min_requests=1))
    fr = FlightRecorder(capacity=8)
    res = eng.serve_stream(_requests(), telemetry=tel, audit=aud,
                           watchdog=wd, flight_recorder=fr, validate=True,
                           **KW)
    assert any(r["offloaded"] for r in res.values()), \
        "need offloads for the drift threshold to trip"
    kinds = {b["kind"] for b in wd.breaches}
    assert "offload_rate" in kinds
    names = {n for _, n, _ in tel.events}
    assert any(n.startswith("slo_breach:offload_rate") for n in names)
    # breaches render as Chrome instant events on the scheduler track, and
    # audit aggregates become counter tracks via the tick gauges
    doc = trace_export.chrome_trace(tel)
    ev = doc["traceEvents"]
    assert any(e["ph"] == "i" and e.get("cat") == "slo" for e in ev)
    assert any(e["ph"] == "C" and e["name"] == "audit_ece" for e in ev)
    assert min(e["ts"] for e in ev if "ts" in e) >= 0.0
    # every breach froze a dump; snapshots carry the audit aggregates
    assert fr.dumps and fr.last_dump["reason"].startswith("slo_breach:")
    assert all("audit_ece" in s["gauges"] for s in fr.last_dump["ring"])
    assert all("serve_time" not in s["counters"]
               for s in fr.last_dump["ring"])


def test_prometheus_audit_families_and_overflow_bucket():
    eng = _eng()
    tel = Telemetry()
    aud = GateAudit()
    eng.serve_stream(_requests(tclass=True), telemetry=tel, audit=aud, **KW)
    txt = tel.prometheus_text()
    for key in ("# HELP hi_requests_total", "# HELP hi_gauge",
                "# HELP hi_audit_ece", "hi_audit_decisions_total",
                "hi_audit_outcomes_total",
                'hi_audit_regret_total{kind="wasted_offload"}',
                'hi_audit_ece{tclass="interactive"}',
                'hi_audit_offload_rate{tclass="batch"}',
                "hi_audit_theta_margin_count"):
        assert key in txt, f"missing Prometheus key: {key}"
    assert "hi_audit_reliability_total" in txt
    # satellite fix: the unbounded overflow bucket must fold into +Inf —
    # no finite ``le`` edge may exceed the last BOUNDED bucket's edge
    h = tel.hists["ttft"]
    h.record(1e6)                               # lands in the overflow bucket
    txt = tel.prometheus_text()
    finite_les = [float(line.split('le="')[1].split('"')[0])
                  for line in txt.splitlines()
                  if line.startswith("hi_ttft_seconds_bucket")
                  and "+Inf" not in line]
    assert finite_les, "bounded buckets must still be emitted"
    assert max(finite_les) <= h.upper_edge(h.n_buckets - 2)
    inf_line = [ln for ln in txt.splitlines()
                if ln.startswith('hi_ttft_seconds_bucket{le="+Inf"}')]
    assert inf_line and int(inf_line[0].split()[-1]) == h.count


def test_label_escaping():
    eng = _eng()
    tel = Telemetry()
    aud = GateAudit()
    reqs = _requests(3)
    for r in reqs:
        r.tclass = 'we"ird\nclass\\x'
    eng.serve_stream(reqs, telemetry=tel, audit=aud, **KW)
    txt = tel.prometheus_text()
    assert 'tclass="we\\"ird\\nclass\\\\x"' in txt
    assert 'we"ird\nclass' not in txt
