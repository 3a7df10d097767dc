"""Serving hot-path benchmark: continuous batching vs drained batches vs the
legacy token-by-token loop.

Three paths over the ISSUE's reference workload (reduced ``qwen2-1.5b``,
CPU):

* ``legacy``  — per-token scan prefill + NumPy routing (``serve_legacy``);
* ``drain``   — the device-resident cascade, whole (B, bucket) batches
  (``serve``): one executable per (batch, bucket), slots idle until the
  slowest sequence in the batch finishes, engine-wide max_new_tokens;
* ``stream``  — the continuous scheduler over the paged KV pool
  (``serve_stream``): slot-level admission, per-request output lengths, ONE
  executable across all buckets.

The stream-vs-drain comparison runs MIXED-length traffic in seeded
Poisson-arrival order (backlogged: arrival order = submission order, so the
drain batcher sees realistically mixed buckets per batch): prompt lengths
span the bucket ladder and per-request max_new_tokens is heterogeneous —
the regime continuous batching exists for.

The REPEATED-PREFIX scenario measures the prefix-sharing pool: a shared
system prompt + repeated user prompts (every repeat also replays its S→L
escalation against the L tier's own index), served with sharing ON vs OFF
at a calibrated ~40% offload rate.  Steady state (warm index) is what's
timed — the regime a production front-end with a fixed system prompt lives
in — and the prefill tokens saved per pass are reported alongside req/s.

The LONG-PROMPT scenario measures chunked prefill admission: mixed traffic
where a quarter of the prompts are ~16x longer than the rest, served with
``chunk_prefill`` ON vs OFF.  With chunking on, long prompts stream through
the chunk lane C tokens per tick (interleaved with decode) and the batched
admit lane shrinks to one chunk's width — time-to-first-token p50/p99 across
the whole trace is what's reported, plus req/s.

Results land in ``BENCH_serving.json`` so the perf trajectory is tracked
PR-over-PR.

The OUTAGE scenario measures fail-local resilience (``serving/faults.py``):
the calibrated mixed trace is replayed with an L-tier outage window sized
off the observed fault-free tick count.  Escalations failing into the
window open the circuit breaker; requests degrade to their S-tier answers
(``status="degraded_local"``) instead of stalling, and after the window +
cooldown the half-open probe restores remote serving.  Reported: req/s with
vs without the outage (throughput sustained), the degraded-local fraction,
the S-vs-L serve mix against the fault-free run (recovery of the offload
rate), and breaker open/close counts.

The KV-QUANT scenario measures the int8 paged-pool option (``kv_dtype``):
req/s on the calibrated mixed trace bf16 vs int8, the pool-byte footprint
(the int8 pages + per-page-per-head fp32 scales must fit in <= 0.55x the
bf16 bytes at the same slot/page config — asserted, not just reported), the
max concurrent slots a fixed HBM budget admits in each mode, and greedy
top-1 fidelity.  Fidelity is TEACHER-FORCED per-decision agreement (both
pools fed the bf16 argmax each step): free-running agreement compounds a
single early flip into total divergence, which measures trajectory
stability, not quantization quality.  On random-init weights the dense
families' top1-top2 logit margins (~1e-2) sit inside the int8 noise floor
(~1e-1 logit error), so the >= 99% agreement GATE runs on the hybrid
family, whose decisions are dominated by the full-precision recurrent path
while its shared-attention K/V pages really are int8-quantized; the dense
families' agreement is reported alongside.

The TELEMETRY scenario measures the collector's cost on the calibrated
mixed trace: req/s with the span/phase/histogram collector ON vs OFF (the
acceptance budget is <2% overhead; disabled costs nothing — the scheduler's
hooks are ``if self.tel`` guards on the host side of an already host-bound
tick loop), plus the latency histograms (TTFT/TPOT/queue-wait/escalation
p50/p95/p99) from an instrumented pass.  ``--trace-out PATH`` additionally
exports that pass as Chrome trace_event JSON — one track per slot per tier
with S→L flow arrows — loadable in chrome://tracing or Perfetto.

  PYTHONPATH=src python -m benchmarks.bench_serving [--out BENCH_serving.json]
  PYTHONPATH=src python -m benchmarks.bench_serving --smoke        # CI tier-1
  PYTHONPATH=src python -m benchmarks.bench_serving --smoke --trace-out t.json
  PYTHONPATH=src python -m benchmarks.bench_serving --chaos-smoke  # CI chaos
                    # gate: seeded fault schedules + per-tick pool invariants
  PYTHONPATH=src python -m benchmarks.bench_serving --telemetry-smoke
                    # gate: span completeness + <2% instrumented overhead
  PYTHONPATH=src python -m benchmarks.bench_serving --quant-smoke
                    # gate: int8 pool <= 0.55x bf16 bytes, >= 99% greedy
                    # top-1 agreement, 1 compiled shape per dtype
  PYTHONPATH=src python -m benchmarks.bench_serving --audit-smoke
                    # gate: decision-audit stream token-identical, bins ==
                    # p_histogram oracle, <2% overhead, ECE reported
  PYTHONPATH=src python -m benchmarks.bench_serving --mesh-smoke
                    # gate: data=4 S replicas >= 1.5x single-device req/s,
                    # nonzero transfer_overlap tick phase, 1 compiled shape

The MESH scenario measures the data-parallel tier split (scheduler
``mesh=``): R=4 S replicas shard_map'd over the ``data`` axis, each owning
a disjoint slot slice + its own paged-pool shard, escalations staged
through the double-buffered device transfer written at tick top.  It runs
in-process over the backend's devices when there are at least four, else in
a CPU-pinned subprocess with forced host devices (theta=0, the S-resident
regime — on a CI host the mesh "devices" share one core, so the L tier's
GSPMD replication would measure the host, not the design; the result's
``platform`` says which) and reports req/s vs
the single-device scheduler, tick counts, and the per-tick phase buckets
including ``transfer_overlap``.

Full runs append a compact per-run ``history`` entry (git rev, date, req/s
per scenario) into the output JSON instead of clobbering the trajectory —
cross-PR perf lives in ``BENCH_serving.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.configs.base import HIConfig
from repro.configs.registry import ARCHS
from repro.core.calibrate import p_histogram
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_zoo
from repro.serving.audit import GateAudit
from repro.serving.batcher import Batcher, Request, pad_to_bucket
from repro.serving.engine import build_engine
from repro.serving.faults import STATUSES, FaultSchedule, RetryPolicy
from repro.serving.flight_recorder import FlightRecorder
from repro.serving.telemetry import Telemetry
from repro.serving.trace_export import chrome_trace, write_chrome_trace

ARCH = "qwen2-1.5b"
REQUESTS = 32
BATCH = 8
MAX_NEW = 8
CACHE_LEN = 96
BUCKETS = (32, 64)
STREAM_BUCKETS = (16, 32, 64)
PAGE_SIZE = 16
NUM_SLOTS = 8


def _make_batches(cfg, seed: int = 0):
    rng = np.random.default_rng(seed)
    batcher = Batcher(batch_size=BATCH, buckets=BUCKETS)
    for i in range(REQUESTS):
        plen = int(rng.integers(16, 64))
        batcher.submit(Request(i, rng.integers(
            0, cfg.vocab_size, plen).astype(np.int32)))
    batches = []
    while batcher.queue:
        batches.append(batcher.next_batch())
    return batches


def _poisson_mixed_requests(cfg, n: int, max_new: int, seed: int = 0):
    """Mixed-length traffic in seeded Poisson-arrival order: prompt lengths
    span the bucket ladder, output lengths are heterogeneous (2..max_new).
    The exponential inter-arrival draws fix the ORDER (backlogged system:
    every request has arrived by t=0 of the measurement)."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0, n))
    order = np.argsort(arrivals, kind="stable")
    reqs = []
    for i in range(n):
        plen = int(rng.integers(8, STREAM_BUCKETS[-1]))
        steps = int(rng.integers(2, max_new + 1))
        reqs.append(Request(i, rng.integers(
            0, cfg.vocab_size, plen).astype(np.int32), max_new_tokens=steps))
    return [reqs[i] for i in order]


def _time_path(serve, batches, iters: int = 5) -> float:
    """Best wall seconds to drain the whole request set (post-warmup).

    min-of-N: both paths are deterministic compiled programs, so the minimum
    is the least noise-contaminated estimate on a shared CPU box."""
    for b in batches:                      # warm every (batch, bucket) shape
        serve(b.tokens)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for b in batches:
            serve(b.tokens)
        times.append(time.perf_counter() - t0)
    return min(times)


def _time_drain_mixed(eng, reqs, iters: int) -> float:
    """Drain the mixed trace through ``serve``: FIFO batching in arrival
    order (mixed buckets pad up; the engine's fixed max_new runs for all)."""
    def one_pass():
        batcher = Batcher(batch_size=BATCH, buckets=STREAM_BUCKETS)
        for r in reqs:
            batcher.submit(r)
        while batcher.queue:
            eng.serve(batcher.next_batch().tokens)
    one_pass()                             # warm all (batch, bucket) shapes
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        one_pass()
        times.append(time.perf_counter() - t0)
    return min(times)


def _time_stream_mixed(eng, reqs, iters: int, decode_block: int,
                       prefix_sharing: bool = False) -> float:
    def one_pass():
        eng.serve_stream(reqs, buckets=STREAM_BUCKETS, num_slots=NUM_SLOTS,
                         l_slots=NUM_SLOTS // 2, page_size=PAGE_SIZE,
                         decode_block=decode_block,
                         prefix_sharing=prefix_sharing)
    one_pass()                             # warm the (single) tick executable
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        one_pass()
        times.append(time.perf_counter() - t0)
    return min(times)


# repeated-prefix scenario: long shared system prompt + short generations —
# the prefill-bound regime prefix caching exists for (classification,
# extraction, templated chat); escalations replay against the L tier's index
REP_SYS_LEN = 224
REP_BUCKETS = (256,)
REP_MAX_NEW = 4
REP_DECODE_BLOCK = 3
REP_CACHE_LEN = 288


def _repeated_prefix_requests(cfg, n: int, seed: int = 0):
    """Shared-system-prompt traffic: every prompt starts with the same
    224-token system prefix; a handful of unique user prompts repeat through
    the trace (chat replays, retries, templated queries).  Repeats give full
    restores on BOTH tiers — every repeated escalation replays on the
    L tier's own index."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(0, cfg.vocab_size, REP_SYS_LEN).astype(np.int32)
    n_unique = max(2, n // 8)
    uniq = []
    for _ in range(n_unique):
        tail = rng.integers(0, cfg.vocab_size,
                            int(rng.integers(8, 31))).astype(np.int32)
        uniq.append(np.concatenate([sys_prompt, tail]))
    order = rng.permutation(n)
    return [Request(int(i), uniq[int(i) % n_unique],
                    max_new_tokens=REP_MAX_NEW) for i in order]


def _time_rep(eng, reqs, iters: int, sharing: bool) -> float:
    def one_pass():
        eng.serve_stream(reqs, buckets=REP_BUCKETS, num_slots=NUM_SLOTS,
                         l_slots=NUM_SLOTS // 2, page_size=PAGE_SIZE,
                         decode_block=REP_DECODE_BLOCK,
                         prefix_sharing=sharing)
    one_pass()          # warm: compiles the executable AND fills the index
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        one_pass()
        times.append(time.perf_counter() - t0)
    return min(times)


def _bench_repeated_prefix(cfg, n: int, iters: int):
    """Sharing-on vs sharing-off req/s on the repeated-prefix trace (steady
    state: warm index), plus the prefill tokens saved per pass."""
    reqs = _repeated_prefix_requests(cfg, n)
    # calibrate theta for ~40% offload with a sharing-off stream probe
    # (confidences are theta-independent)
    eng_p = build_engine(cfg, HIConfig(theta=0.0, capacity_factor=1.0),
                         max_new_tokens=REP_MAX_NEW, cache_len=REP_CACHE_LEN)
    probe = eng_p.serve_stream(reqs, buckets=REP_BUCKETS,
                               num_slots=NUM_SLOTS, l_slots=NUM_SLOTS // 2,
                               page_size=PAGE_SIZE,
                               decode_block=REP_DECODE_BLOCK,
                               prefix_sharing=False)
    theta = float(np.quantile(
        np.asarray([r["confidence"] for r in probe.values()]), 0.4))
    hi = HIConfig(theta=theta, capacity_factor=1.0)

    eng_off = build_engine(cfg, hi, max_new_tokens=REP_MAX_NEW,
                           cache_len=REP_CACHE_LEN)
    t_off = _time_rep(eng_off, reqs, iters, sharing=False)
    eng_on = build_engine(cfg, hi, max_new_tokens=REP_MAX_NEW,
                          cache_len=REP_CACHE_LEN)
    t_on = _time_rep(eng_on, reqs, iters, sharing=True)
    # prefill tokens saved in ONE steady-state (warm-index) pass
    saved0 = eng_on.stats["prefill_tokens_saved"]
    eng_on.serve_stream(reqs, buckets=REP_BUCKETS, num_slots=NUM_SLOTS,
                        l_slots=NUM_SLOTS // 2, page_size=PAGE_SIZE,
                        decode_block=REP_DECODE_BLOCK, prefix_sharing=True)
    sched = eng_on._stream[1]
    # padded (bucket) tokens are what admission actually prefills — the
    # denominator "tokens saved" is measured against
    prompt_tokens = sum(pad_to_bucket(len(r.prompt), REP_BUCKETS)
                        for r in reqs)
    return {
        "requests": n,
        "buckets": list(REP_BUCKETS),
        "system_prompt_len": REP_SYS_LEN,
        "max_new_tokens": REP_MAX_NEW,
        "num_slots": NUM_SLOTS,
        "page_size": PAGE_SIZE,
        "theta_calibrated": theta,
        "offload_frac": eng_on.stats["offloaded"]
        / max(eng_on.stats["requests"], 1),
        "sharing_rps": n / t_on,
        "no_sharing_rps": n / t_off,
        "sharing_speedup": t_off / t_on,
        "prefill_tokens_saved_per_pass":
            int(eng_on.stats["prefill_tokens_saved"] - saved0),
        "prompt_tokens_per_pass": prompt_tokens,
        "prefix_stats_cumulative": sched.prefix_stats,
        "sharing_compiled_shapes": int(eng_on.stats["stream_compiles"]),
    }


# long-prompt scenario: most traffic is short with heterogeneous output
# lengths (slots free at staggered ticks, so admission pressure is
# continuous), a quarter of prompts is ~16x longer — the admission-monopoly
# regime chunked prefill exists for: without chunking EVERY admission tick
# pays an (A, 512) prefill pass (shapes are static, shorts pad up) and a
# long admission stalls all decode for its duration
LONG_BUCKETS = (32, 512)
LONG_CHUNK = 128
LONG_CHUNK_WIDTH = 4
LONG_MAX_NEW = 16
LONG_DECODE_BLOCK = 3


def _long_prompt_requests(cfg, n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(384, 512)) if i % 4 == 0 \
            else int(rng.integers(8, 32))
        reqs.append(Request(i, rng.integers(
            0, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=int(rng.integers(2, LONG_MAX_NEW))))
    return [reqs[i] for i in rng.permutation(n)]


def _bench_long_prompt(cfg, n: int, iters: int):
    """TTFT p50/p99 + req/s with chunked prefill admission on vs off."""
    reqs = _long_prompt_requests(cfg, n)
    hi = HIConfig(theta=0.0, capacity_factor=1.0)   # S-only: isolate prefill

    def measure(chunked: bool):
        eng = build_engine(cfg, hi, max_new_tokens=LONG_MAX_NEW,
                           cache_len=LONG_BUCKETS[-1] + 16)
        kw = dict(buckets=LONG_BUCKETS, num_slots=NUM_SLOTS,
                  l_slots=NUM_SLOTS // 2, page_size=PAGE_SIZE,
                  decode_block=LONG_DECODE_BLOCK, prefix_sharing=False,
                  chunk_prefill=chunked, chunk_size=LONG_CHUNK,
                  chunk_width=LONG_CHUNK_WIDTH)
        eng.serve_stream(reqs, **kw)               # warm the tick executable
        best, ttfts = None, None
        for _ in range(iters):
            t0 = time.perf_counter()
            out = eng.serve_stream(reqs, **kw)
            dt = time.perf_counter() - t0
            if best is None or dt < best:
                best = dt
                ttfts = np.asarray([out[r.request_id]["ttft"] for r in reqs])
        return best, ttfts

    t_off, ttft_off = measure(False)
    t_on, ttft_on = measure(True)
    return {
        "requests": n,
        "buckets": list(LONG_BUCKETS),
        "chunk_size": LONG_CHUNK,
        "chunk_width": LONG_CHUNK_WIDTH,
        "long_fraction": 0.25,
        "chunked_rps": n / t_on,
        "unchunked_rps": n / t_off,
        "chunked_speedup": t_off / t_on,
        "ttft_p50_ms": {"chunked": float(np.percentile(ttft_on, 50) * 1e3),
                        "unchunked": float(np.percentile(ttft_off, 50) * 1e3)},
        "ttft_p99_ms": {"chunked": float(np.percentile(ttft_on, 99) * 1e3),
                        "unchunked": float(np.percentile(ttft_off, 99) * 1e3)},
    }


# outage scenario: a fast-failing retry policy so the breaker's open/close
# arc fits inside the trace (production would run longer timeouts)
OUTAGE_RETRY = dict(ack_timeout_ticks=2, max_retries=1,
                    breaker_threshold=2, breaker_cooldown_ticks=2)


def _bench_outage(cfg, reqs, iters: int):
    """Fail-local resilience on the mixed trace at a ~50% offload rate: an
    L outage window (sized off the observed fault-free tick count) opens
    the breaker, throughput is sustained on degraded-local answers, and
    remote serving recovers after the window.

    Half the slots and a small decode block stretch the trace over many
    admission waves — the tick axis needs room for a during-outage phase
    AND a post-recovery phase, or the breaker arc can't be observed."""
    kw = dict(buckets=STREAM_BUCKETS, num_slots=NUM_SLOTS // 2,
              l_slots=NUM_SLOTS // 4, page_size=PAGE_SIZE, decode_block=2)
    eng = build_engine(cfg, HIConfig(theta=0.0, capacity_factor=1.0),
                       max_new_tokens=MAX_NEW, cache_len=CACHE_LEN)
    probe = eng.serve_stream(reqs, **kw)       # warm + confidence probe
    # median confidence -> ~half the trace escalates: enough L traffic for
    # the breaker arc to be visible even in the smoke sizing
    theta = float(np.quantile(np.asarray(
        [r["confidence"] for r in probe.values()]), 0.5))
    eng.hi = HIConfig(theta=theta, capacity_factor=1.0)
    ticks0 = int(eng.stats["stream_ticks"])
    ref = eng.serve_stream(reqs, **kw)         # fault-free reference
    ticks = int(eng.stats["stream_ticks"]) - ticks0   # size the window off
    outage = (max(1, ticks // 6), max(3, ticks // 3))  # observed reality
    faults = FaultSchedule(seed=0, outages=(outage,))
    retry = RetryPolicy(**OUTAGE_RETRY)

    def timed(f=None, r=None):
        best, last = None, None
        for _ in range(iters):
            t0 = time.perf_counter()
            last = eng.serve_stream(reqs, faults=f, retry=r, **kw)
            dt = time.perf_counter() - t0
            if best is None or dt < best:
                best = dt
        return best, last

    t_free, _ = timed()
    opens0 = eng.stats["breaker_opens"]
    ticks0 = eng.stats["breaker_open_ticks"]
    retries0 = eng.stats["esc_retries"]
    t_out, out = timed(faults, retry)

    n = len(reqs)
    remote_ref = sum(r["served_remote"] for r in ref.values())
    remote = sum(r["served_remote"] for r in out.values())
    degraded = sum(r["status"] == "degraded_local" for r in out.values())
    # the recovery criterion proper: escalations CREATED after the window +
    # cooldown must all reach L (fault-free offload behaviour restored)
    recovered_after = outage[1] + OUTAGE_RETRY["breaker_cooldown_ticks"]
    post = [r for r in out.values()
            if r["esc_created_tick"] >= recovered_after]
    post_remote = (sum(r["served_remote"] for r in post) / len(post)
                   if post else None)
    return {
        "requests": n,
        "theta_calibrated": theta,
        "outage_window_ticks": list(outage),
        "fault_free_ticks": ticks,
        "retry_policy": dict(OUTAGE_RETRY),
        "fault_free_rps": n / t_free,
        "outage_rps": n / t_out,
        "throughput_sustained_frac": t_free / t_out,
        "offload_frac": sum(r["offloaded"] for r in out.values()) / n,
        "degraded_local_frac": degraded / n,
        # S-vs-L serve mix: during the window escalations fail local, after
        # it they land on L again — recovery shows up as remote serves
        # approaching the fault-free count
        "remote_frac_fault_free": remote_ref / n,
        "remote_frac_outage": remote / n,
        "remote_recovery_frac": remote / max(remote_ref, 1),
        "post_window_escalations": len(post),
        "post_window_remote_frac": post_remote,
        "breaker_opens": int(eng.stats["breaker_opens"] - opens0) / iters,
        "breaker_open_ticks":
            int(eng.stats["breaker_open_ticks"] - ticks0) / iters,
        "esc_retries": int(eng.stats["esc_retries"] - retries0) / iters,
        "stream_compiled_shapes": int(eng.stats["stream_compiles"]),
    }


def _bench_telemetry(cfg, reqs, theta: float, iters: int, decode_block: int,
                     trace_out: str | None = None):
    """Telemetry overhead on the calibrated mixed trace: req/s with the
    collector ON vs OFF (min-of-N, same engine, same compiled tick), plus
    the latency histograms from an instrumented pass and — when
    ``trace_out`` is given — the Chrome trace_event export of that pass."""
    hi = HIConfig(theta=theta, capacity_factor=1.0)
    eng = build_engine(cfg, hi, max_new_tokens=MAX_NEW, cache_len=CACHE_LEN)
    kw = dict(buckets=STREAM_BUCKETS, num_slots=NUM_SLOTS,
              l_slots=NUM_SLOTS // 2, page_size=PAGE_SIZE,
              decode_block=decode_block)
    eng.serve_stream(reqs, **kw)               # warm the tick executable

    def best(tel_factory):
        times = []
        for _ in range(iters):
            tel = tel_factory()
            t0 = time.perf_counter()
            eng.serve_stream(reqs, telemetry=tel, **kw)
            times.append(time.perf_counter() - t0)
        return min(times)

    t_off = best(lambda: None)
    t_on = best(Telemetry)
    # one final instrumented pass feeds the exporters (histograms + trace)
    tel = Telemetry()
    eng.serve_stream(reqs, telemetry=tel, **kw)
    doc = write_chrome_trace(tel, trace_out) if trace_out \
        else chrome_trace(tel)
    return {
        "requests": len(reqs),
        "enabled_rps": len(reqs) / t_on,
        "disabled_rps": len(reqs) / t_off,
        "overhead_frac": max(0.0, t_on / t_off - 1.0),
        "histograms": tel.histogram_summary(),
        "tick_phase_seconds": tel.phase_summary(),
        "trace_out": trace_out,
        "trace_events": len(doc["traceEvents"]),
        "stream_compiled_shapes": int(eng.stats["stream_compiles"]),
    }


# kv-quant scenario: the hybrid family carries the >= 99% agreement gate —
# its shared-attention pages are genuinely int8 while random-init decisions
# keep usable top-1 margins (see module docstring); qwen2 is reported
QUANT_GATE_ARCH = "zamba2-2.7b"
QUANT_PAGE = 8


def _teacher_forced_agreement(arch: str, slots: int, steps: int,
                              prompt_len: int = 16, seed: int = 1):
    """Per-decision greedy top-1 agreement between a bf16 and an int8 paged
    cache on the same prompts, teacher-forced on the bf16 argmax.  Returns
    (matching decisions, total decisions, max abs logit error)."""
    cfg = ARCHS[arch].reduced()
    params = model_zoo.init_params(jax.random.PRNGKey(0), cfg)
    npg = (prompt_len + steps) // QUANT_PAGE + 1
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (slots, prompt_len)),
                       jnp.int32)
    lens = jnp.full((slots,), prompt_len, jnp.int32)
    block = jnp.asarray(np.arange(1, slots * npg + 1,
                                  dtype=np.int32).reshape(slots, npg))
    caches, logits = {}, {}
    for dt in (jnp.bfloat16, jnp.int8):
        cache = model_zoo.init_paged_cache(cfg, slots, slots * npg + 1,
                                           QUANT_PAGE, dt)
        lg, cache = model_zoo.prefill_paged(
            params, cfg, toks, lens, jnp.arange(slots, dtype=jnp.int32),
            block, cache)
        caches[dt], logits[dt] = cache, lg
    lg_b, lg_q = logits[jnp.bfloat16], logits[jnp.int8]
    match = int(jnp.sum(jnp.argmax(lg_b, -1) == jnp.argmax(lg_q, -1)))
    total = slots
    max_err = float(jnp.max(jnp.abs(lg_b - lg_q)))
    pos = jnp.full((slots,), prompt_len, jnp.int32)
    tok = jnp.argmax(lg_b, -1)[:, None].astype(jnp.int32)
    for i in range(steps):
        lg_b, caches[jnp.bfloat16] = model_zoo.decode_step_paged(
            params, cfg, tok, pos + i, block, caches[jnp.bfloat16])
        lg_q, caches[jnp.int8] = model_zoo.decode_step_paged(
            params, cfg, tok, pos + i, block, caches[jnp.int8])
        match += int(jnp.sum(jnp.argmax(lg_b, -1) == jnp.argmax(lg_q, -1)))
        total += slots
        max_err = max(max_err, float(jnp.max(jnp.abs(lg_b - lg_q))))
        tok = jnp.argmax(lg_b, -1)[:, None].astype(jnp.int32)
    return match, total, max_err


def _pool_footprint(eng) -> dict:
    g = eng._stream[1].srt.pool.gauges()
    return {k: g[k] for k in ("kv_bytes_total", "bytes_per_slot", "kv_bits")}


def _bench_kv_quant(cfg, reqs, theta: float, iters: int,
                    decode_block: int) -> dict:
    """bf16 vs int8 pools on the calibrated mixed trace: req/s, pool bytes
    (the <= 0.55x footprint contract is ASSERTED here), slots admitted by a
    fixed HBM budget, and greedy fidelity (teacher-forced gate on the
    hybrid family + reported dense agreement)."""
    hi = HIConfig(theta=theta, capacity_factor=1.0)

    def measure(kv_dtype: str):
        eng = build_engine(cfg, hi, max_new_tokens=MAX_NEW,
                           cache_len=CACHE_LEN)
        kw = dict(buckets=STREAM_BUCKETS, num_slots=NUM_SLOTS,
                  l_slots=NUM_SLOTS // 2, page_size=PAGE_SIZE,
                  decode_block=decode_block, kv_dtype=kv_dtype)
        out = eng.serve_stream(reqs, **kw)         # warm the tick executable
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            out = eng.serve_stream(reqs, **kw)
            times.append(time.perf_counter() - t0)
        assert eng.stats["stream_compiles"] == 1, kv_dtype
        return min(times), out, _pool_footprint(eng)

    t16, out16, fp16 = measure("bf16")
    t8, out8, fp8 = measure("int8")
    ratio = fp8["kv_bytes_total"] / fp16["kv_bytes_total"]
    assert ratio <= 0.55, \
        f"int8 pool is {ratio:.3f}x bf16 bytes (contract: <= 0.55x)"
    # max concurrent slots a fixed HBM budget admits: budget = what the
    # bf16 config provisions for NUM_SLOTS slots
    budget = NUM_SLOTS * fp16["bytes_per_slot"]
    slots_at_budget = {"bf16": NUM_SLOTS,
                       "int8": int(budget // fp8["bytes_per_slot"])}
    # free-running sequence agreement (reported): compounding, see docstring
    agree_seq = float(np.mean([
        np.mean(np.asarray(out16[r.request_id]["tokens"]) ==
                np.asarray(out8[r.request_id]["tokens"]))
        for r in reqs
        if len(out16[r.request_id]["tokens"]) ==
        len(out8[r.request_id]["tokens"])]))
    # teacher-forced per-decision agreement: the gated fidelity metric
    g_match, g_total, g_err = _teacher_forced_agreement(
        QUANT_GATE_ARCH, slots=8, steps=16)
    d_match, d_total, d_err = _teacher_forced_agreement(ARCH, slots=8,
                                                        steps=16)
    return {
        "requests": len(reqs),
        "buckets": list(STREAM_BUCKETS),
        "num_slots": NUM_SLOTS,
        "page_size": PAGE_SIZE,
        "theta_calibrated": theta,
        "bf16_rps": len(reqs) / t16,
        "int8_rps": len(reqs) / t8,
        "int8_vs_bf16_rps": t16 / t8,
        "pool_bytes": {"bf16": fp16, "int8": fp8},
        "int8_bytes_ratio": ratio,
        "hbm_budget_bytes": budget,
        "slots_at_budget": slots_at_budget,
        "freerun_token_agreement": agree_seq,
        "teacher_forced_agreement": {
            QUANT_GATE_ARCH: {"rate": g_match / g_total,
                              "decisions": g_total,
                              "max_logit_err": g_err},
            ARCH: {"rate": d_match / d_total, "decisions": d_total,
                   "max_logit_err": d_err},
        },
    }


def run_quant_smoke() -> dict:
    """CI quantization gate (``--quant-smoke``): the int8 pool option must
    (1) fit pages + scales in <= 0.55x the bf16 pool bytes at the same
    slot/page config, (2) keep >= 99% teacher-forced greedy top-1 agreement
    on the smoke trace (gate family: hybrid — see module docstring), and
    (3) preserve the serving contract in BOTH dtypes: one compiled stream
    executable and per-tick pool invariants (scale-row accounting
    included).  Exits nonzero (via AssertionError) on any violation."""
    cfg = ARCHS[ARCH].reduced()
    reqs = _poisson_mixed_requests(cfg, 8, 4)
    kw = dict(buckets=STREAM_BUCKETS, num_slots=4, l_slots=2,
              page_size=PAGE_SIZE, validate=True)
    footprint = {}
    for kv_dtype in ("bf16", "int8"):
        eng = build_engine(cfg, HIConfig(theta=0.6, capacity_factor=1.0),
                           max_new_tokens=4, cache_len=CACHE_LEN)
        eng.serve_stream(reqs, kv_dtype=kv_dtype, **kw)
        assert eng.stats["stream_compiles"] == 1, \
            f"{kv_dtype}: expected 1 compiled shape"
        sched = eng._stream[1]
        sched.srt.pool.check_invariants()
        sched.lrt.pool.check_invariants()
        footprint[kv_dtype] = _pool_footprint(eng)
    ratio = (footprint["int8"]["kv_bytes_total"]
             / footprint["bf16"]["kv_bytes_total"])
    assert ratio <= 0.55, \
        f"int8 pool is {ratio:.3f}x bf16 bytes (contract: <= 0.55x)"
    match, total, max_err = _teacher_forced_agreement(QUANT_GATE_ARCH,
                                                      slots=8, steps=16)
    rate = match / total
    assert rate >= 0.99, \
        f"greedy top-1 agreement {match}/{total} = {rate:.4f} < 0.99"
    emit("serving_quant_smoke", 0.0,
         f"kv-quant gate PASS: int8 pool {ratio:.3f}x bf16 bytes, "
         f"teacher-forced agreement {match}/{total} ({rate:.1%}), max "
         f"logit err {max_err:.3f}, 1 compiled shape per dtype")
    return {"int8_bytes_ratio": ratio, "pool_bytes": footprint,
            "gate_arch": QUANT_GATE_ARCH,
            "teacher_forced_agreement": rate, "decisions": total,
            "max_logit_err": max_err, "stream_compiled_shapes": 1}


def run_telemetry_smoke(trace_out: str | None = None) -> dict:
    """CI telemetry gate (``--telemetry-smoke``): replay the smoke trace
    with the collector ON and assert the zero-cost contract — one compiled
    shape, a complete span tree per terminating request whose terminal
    status matches the result record, token-identical output to the
    uninstrumented run, and req/s within the 2% overhead budget.  Exits
    nonzero (via AssertionError) on any violation."""
    cfg = ARCHS[ARCH].reduced()
    eng = build_engine(cfg, HIConfig(theta=0.6, capacity_factor=1.0),
                       max_new_tokens=4, cache_len=CACHE_LEN)
    reqs = _poisson_mixed_requests(cfg, 16, 4)
    kw = dict(buckets=STREAM_BUCKETS, num_slots=4, l_slots=2,
              page_size=PAGE_SIZE)
    ref = eng.serve_stream(reqs, **kw)         # warm + reference tokens
    tel = Telemetry()
    out = eng.serve_stream(reqs, telemetry=tel, **kw)
    assert eng.stats["stream_compiles"] == 1, "telemetry changed a shape"
    assert set(tel.traces) == set(out), "span tree per terminating request"
    for rid, rec in out.items():
        tr = tel.traces[rid]
        assert tr.complete, f"request {rid}: dangling span"
        assert tr.status == rec["status"], f"request {rid}: status mismatch"
        np.testing.assert_array_equal(rec["tokens"], ref[rid]["tokens"])

    def best(tel_factory):
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            eng.serve_stream(reqs, telemetry=tel_factory(), **kw)
            times.append(time.perf_counter() - t0)
        return min(times)

    t_off = best(lambda: None)
    t_on = best(Telemetry)
    overhead = max(0.0, t_on / t_off - 1.0)
    assert overhead < 0.02, \
        f"telemetry overhead {overhead:.2%} exceeds the 2% budget"
    if trace_out:
        write_chrome_trace(tel, trace_out)
    emit("serving_telemetry_smoke", 0.0,
         f"telemetry gate PASS: {len(out)} span trees, overhead "
         f"{overhead:.2%} (< 2%), 1 compiled shape")
    return {"requests": len(out), "overhead_frac": overhead,
            "enabled_rps": len(reqs) / t_on,
            "disabled_rps": len(reqs) / t_off,
            "stream_compiled_shapes": 1, "trace_out": trace_out}


def run_audit_smoke(trace_out: str | None = None) -> dict:
    """CI decision-audit gate (``--audit-smoke``): replay the smoke trace
    with the :class:`GateAudit` stream ON and assert its zero-cost contract
    — one compiled shape, greedy output token-identical to audit-off,
    streaming reliability bins matching the ``core/calibrate.p_histogram``
    NumPy oracle on the recorded decision stream, the ``hi_audit_*``
    Prometheus families present, and req/s within the 2% overhead budget.
    The running ECE is reported.  Exits nonzero (via AssertionError) on any
    violation."""
    cfg = ARCHS[ARCH].reduced()
    eng = build_engine(cfg, HIConfig(theta=0.6, capacity_factor=1.0),
                       max_new_tokens=4, cache_len=CACHE_LEN)
    reqs = _poisson_mixed_requests(cfg, 16, 4)
    for r in reqs:
        r.tclass = ("interactive", "batch")[r.request_id % 2]
    kw = dict(buckets=STREAM_BUCKETS, num_slots=4, l_slots=2,
              page_size=PAGE_SIZE)
    ref = eng.serve_stream(reqs, **kw)         # warm + reference tokens
    aud = GateAudit()
    tel = Telemetry()
    out = eng.serve_stream(reqs, audit=aud, telemetry=tel, **kw)
    assert eng.stats["stream_compiles"] == 1, "the audit changed a shape"
    for rid, rec in out.items():
        np.testing.assert_array_equal(rec["tokens"], ref[rid]["tokens"])
    assert aud.decisions > 0, "the gate stream recorded nothing"
    truthed = [r for r in aud.records if r.ok is not None]
    assert truthed, "completed escalations must yield ground truth"
    oracle = p_histogram(np.array([r.conf for r in truthed]),
                         np.array([r.ok for r in truthed], np.float32),
                         bins=aud.overall.bins)
    np.testing.assert_array_equal(aud.overall.correct, oracle["correct"])
    np.testing.assert_array_equal(aud.overall.incorrect,
                                  oracle["incorrect"])
    txt = tel.prometheus_text()
    assert "hi_audit_ece" in txt and "hi_audit_decisions_total" in txt

    def best(extra):
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            eng.serve_stream(reqs, **extra(), **kw)
            times.append(time.perf_counter() - t0)
        return min(times)

    t_off = best(dict)
    t_on = best(lambda: {"audit": GateAudit()})
    overhead = max(0.0, t_on / t_off - 1.0)
    assert overhead < 0.02, \
        f"audit overhead {overhead:.2%} exceeds the 2% budget"
    if trace_out:
        write_chrome_trace(tel, trace_out)
    emit("serving_audit_smoke", 0.0,
         f"audit gate PASS: {aud.decisions} decisions, {aud.outcomes} "
         f"ground-truthed, ECE {aud.ece():.4f}, offload rate "
         f"{aud.offload_rate():.2f}, overhead {overhead:.2%} (< 2%), "
         f"bins == p_histogram oracle, 1 compiled shape")
    return {"requests": len(out), "decisions": aud.decisions,
            "outcomes": aud.outcomes, "ece": aud.ece(),
            "offload_rate": aud.offload_rate(),
            "regret_cost": aud.regret_cost,
            "overhead_frac": overhead,
            "enabled_rps": len(reqs) / t_on,
            "disabled_rps": len(reqs) / t_off,
            "stream_compiled_shapes": 1, "trace_out": trace_out}


def run_chaos_smoke(dump_out: str | None = None) -> dict:
    """CI chaos gate (``--chaos-smoke``): replay the smoke trace under
    seeded loss / outage / jitter schedules with PER-TICK pool invariants
    (``validate=True``) and assert the no-corruption property — every
    request terminates with exactly one valid-status record, S answers are
    token-identical to the fault-free run, degraded requests answer with
    their S tokens, no page leaks, one compiled shape.  A
    :class:`FlightRecorder` rides every faulted run; the outage schedule
    must freeze a breaker-open postmortem, written to ``dump_out`` (CI
    uploads it as a workflow artifact when this gate fails).  Exits nonzero
    (via AssertionError) on any violation."""
    cfg = ARCHS[ARCH].reduced()
    eng = build_engine(cfg, HIConfig(theta=0.6, capacity_factor=1.0),
                       max_new_tokens=4, cache_len=CACHE_LEN)
    reqs = _poisson_mixed_requests(cfg, 8, 4)
    kw = dict(buckets=STREAM_BUCKETS, num_slots=4, l_slots=2,
              page_size=PAGE_SIZE, validate=True)
    ref = eng.serve_stream(reqs, **kw)
    schedules = [
        ("loss", FaultSchedule(seed=1, loss_prob=1.0),
         RetryPolicy(ack_timeout_ticks=1, max_retries=1)),
        ("outage", FaultSchedule(seed=2, outages=((1, 5),)),
         RetryPolicy(ack_timeout_ticks=2, max_retries=1,
                     breaker_threshold=2, breaker_cooldown_ticks=4)),
        ("jitter", FaultSchedule(seed=3, delay_ticks=1, delay_jitter=2),
         RetryPolicy(ack_timeout_ticks=6)),
    ]
    fr = FlightRecorder(capacity=16, path=dump_out)
    summary = {}
    for name, faults, retry in schedules:
        out = eng.serve_stream(reqs, faults=faults, retry=retry,
                               flight_recorder=fr, **kw)
        assert set(out) == {r.request_id for r in reqs}, name
        for rid, rec in out.items():
            assert rec["status"] in STATUSES, (name, rid, rec["status"])
            np.testing.assert_array_equal(rec["s_tokens"],
                                          ref[rid]["s_tokens"])
            if not rec["offloaded"] or rec["served_remote"]:
                np.testing.assert_array_equal(rec["tokens"],
                                              ref[rid]["tokens"])
            else:
                np.testing.assert_array_equal(rec["tokens"],
                                              rec["s_tokens"])
        sched = eng._stream[1]
        sched.srt.pool.check_invariants()
        sched.lrt.pool.check_invariants()
        assert not sched.srt.pool.held_slots, name
        assert not sched.lrt.pool.held_slots, name
        counts = {}
        for rec in out.values():
            counts[rec["status"]] = counts.get(rec["status"], 0) + 1
        summary[name] = counts
    assert eng.stats["stream_compiles"] == 1, "faults changed compiled shapes"
    opens = [d for d in fr.dumps if d["reason"] == "breaker_open"]
    assert opens, "the outage schedule must freeze a breaker-open dump"
    summary["stream_compiled_shapes"] = 1
    summary["flight_recorder_dumps"] = [d["reason"] for d in fr.dumps]
    summary["dump_out"] = dump_out
    emit("serving_chaos_smoke", 0.0,
         "chaos gate PASS: " + "; ".join(
             f"{k} {v}" for k, v in summary.items() if isinstance(v, dict))
         + f"; {len(fr.dumps)} flight-recorder dump(s)"
         + (f" -> {dump_out}" if dump_out else ""))
    return summary


def _calibrate_theta(eng, reqs, quantile: float = 0.25) -> float:
    """Paper §4 theta* calibration, serving-style: probe the S-tier's
    confidence distribution on the actual traffic through ``eng`` (theta is
    a runtime operand — confidences don't depend on it, and the probe doubles
    as executable warm-up) and place the threshold at the target offload
    fraction.  Both schedulers then see the SAME (paper regime) escalation
    rate — the drain path's static L capacity runs every batch regardless,
    which is exactly the cost continuous batching sheds."""
    confs = []
    batcher = Batcher(batch_size=BATCH, buckets=STREAM_BUCKETS)
    for r in reqs:
        batcher.submit(r)
    while batcher.queue:
        confs.extend(eng.serve(batcher.next_batch().tokens)["confidence"])
    return float(np.quantile(np.asarray(confs), quantile))


# -- mesh-sharded tier-split serving -----------------------------------------
# the data-parallel S-replica bench runs in-process when the backend has
# enough devices (a 4-chip host); otherwise in a CPU-pinned subprocess with
# forced host devices (XLA_FLAGS must be set before jax import, so the
# parent — already holding an initialized jax — re-execs this module)
MESH_DATA = 4                  # S replicas on the `data` axis
MESH_SLOTS = 4                 # decode slots per replica
MESH_STEPS = 8
MESH_REQUESTS = 48


def _mesh_worker(smoke: bool) -> dict:
    """Runs over ``MESH_DATA`` real devices, or INSIDE the forced-multi-device
    CPU subprocess (``--mesh-worker``): time the mesh-sharded scheduler
    (``serve_stream(mesh=...)``, data=4) vs the single-device scheduler on
    the same host, same workload.

    The workload is theta=0 (every request finishes on its S replica): on a
    CI host whose "devices" are forced slices of ONE core, the GSPMD
    replication of the L tier across mesh devices serializes and would
    measure the host, not the design — the S-resident regime is the paper's
    common case and is where data parallelism pays.  The escalation staging
    path still runs every tick (the double-buffer copy + shard_map lanes are
    structural), so the ``transfer_overlap`` phase bucket is reported from
    the same run."""
    from repro.launch.mesh import make_serving_mesh

    assert len(jax.devices()) >= MESH_DATA, \
        f"worker needs >= {MESH_DATA} devices, got {len(jax.devices())}"
    cfg = ARCHS[ARCH].reduced()
    iters = 2 if smoke else 3
    kw = dict(buckets=(16,), num_slots=MESH_SLOTS, page_size=PAGE_SIZE)
    hi = HIConfig(theta=0.0, capacity_factor=1.0)

    def reqs():
        rng = np.random.default_rng(0)
        return [Request(i, rng.integers(0, cfg.vocab_size, 12)
                        .astype(np.int32), max_new_tokens=MESH_STEPS)
                for i in range(MESH_REQUESTS)]

    def measure(mesh):
        eng = build_engine(cfg, hi, max_new_tokens=MESH_STEPS, cache_len=64)
        eng.serve_stream(reqs(), mesh=mesh, **kw)       # compile + warm
        best, ticks, tel = float("inf"), 0, None
        for _ in range(iters):
            t = Telemetry()
            tick0 = eng.stats["stream_ticks"]   # counter is cumulative
            t0 = time.perf_counter()
            eng.serve_stream(reqs(), mesh=mesh, telemetry=t, **kw)
            dt = time.perf_counter() - t0
            if dt < best:
                best = dt
                ticks = eng.stats["stream_ticks"] - tick0
                tel = t
        assert eng.stats["stream_compiles"] == 1
        return best, int(ticks), tel

    t_base, ticks_base, _ = measure(None)
    t_mesh, ticks_mesh, tel = measure(make_serving_mesh(MESH_DATA, 1))
    phase_ms = {}
    for tick in tel.ticks:
        for phase, t0, t1 in tick.segments:
            phase_ms[phase] = phase_ms.get(phase, 0.0) + (t1 - t0) * 1e3
    return {
        "mesh_shape": {"data": MESH_DATA, "model": 1},
        "requests": MESH_REQUESTS,
        "max_new_tokens": MESH_STEPS,
        "slots_per_replica": MESH_SLOTS,
        "theta": 0.0,
        "single_rps": MESH_REQUESTS / t_base,
        "mesh_rps": MESH_REQUESTS / t_mesh,
        "mesh_speedup": t_base / t_mesh,
        "single_ticks": ticks_base,
        "mesh_ticks": ticks_mesh,
        "phase_ms_per_tick": {k: v / max(ticks_mesh, 1)
                              for k, v in phase_ms.items()},
        "stream_compiled_shapes": 1,
        "platform": jax.devices()[0].platform,
    }


def _bench_mesh(smoke: bool) -> dict:
    """Run the mesh scenario over this process's devices when there are at
    least ``MESH_DATA`` of them — one process holds every chip, so no child
    ever competes for them.  Otherwise re-exec this module PINNED TO THE CPU
    backend (``JAX_PLATFORMS=cpu``) with forced host devices and
    ``--mesh-worker``, and parse the marker line it prints; the result's
    ``platform`` is then ``"cpu"``."""
    if len(jax.devices()) >= MESH_DATA:
        return _mesh_worker(smoke)
    root = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count="
                        f"{MESH_DATA}").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(root / "src"), str(root),
                    env.get("PYTHONPATH", "")) if p)
    cmd = [sys.executable, "-m", "benchmarks.bench_serving", "--mesh-worker"]
    if smoke:
        cmd.append("--smoke")
    out = subprocess.run(cmd, env=env, cwd=str(root), capture_output=True,
                         text=True, timeout=1200)
    for line in out.stdout.splitlines():
        if line.startswith("MESH_BENCH_JSON:"):
            return json.loads(line[len("MESH_BENCH_JSON:"):])
    raise RuntimeError("mesh bench worker produced no result:\n"
                       + out.stdout[-2000:] + out.stderr[-2000:])


def run_mesh_smoke() -> dict:
    """CI mesh gate (``--mesh-smoke``): the data-parallel mesh path must
    (1) serve >= 1.5x the single-device req/s at data=4 on the S-resident
    workload, (2) spend measurable wall time in the ``transfer_overlap``
    phase (the escalation staging copy is issued at tick top, overlapping
    S-side compute), and (3) keep ONE compiled stream executable.  Exits
    nonzero (via AssertionError) on any violation."""
    r = _bench_mesh(smoke=True)
    assert r["mesh_speedup"] >= 1.5, \
        f"mesh speedup {r['mesh_speedup']:.2f}x < 1.5x at data={MESH_DATA}"
    overlap = r["phase_ms_per_tick"].get("transfer_overlap", 0.0)
    assert overlap > 0.0, "transfer_overlap phase absent from tick buckets"
    assert r["stream_compiled_shapes"] == 1
    emit("serving_mesh_smoke", 0.0,
         f"mesh gate PASS: {r['mesh_rps']:.1f} req/s at data={MESH_DATA} vs "
         f"{r['single_rps']:.1f} single-device ({r['mesh_speedup']:.2f}x >= "
         f"1.5x), ticks {r['single_ticks']} -> {r['mesh_ticks']}, "
         f"transfer_overlap {overlap:.3f}ms/tick, 1 compiled shape")
    return r


def _prefill_decode_split(cfg, bucket: int, iters: int = 10):
    """Per-batch prefill vs decode milliseconds for the batched path."""
    params = model_zoo.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (BATCH, bucket)), jnp.int32)
    cache0 = model_zoo.init_cache(cfg, BATCH, CACHE_LEN)

    prefill = jax.jit(lambda p, t, c: model_zoo.prefill(p, cfg, t, c))

    def decode(p, logits, cache):
        def body(carry, _):
            cache, logits = carry
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            logits, cache = model_zoo.decode_step(p, cfg, tok[:, None], cache)
            return (cache, logits), tok
        (_, _), toks = jax.lax.scan(body, (cache, logits), None,
                                    length=MAX_NEW)
        return toks
    decode = jax.jit(decode)

    logits, cache = prefill(params, tokens, cache0)
    jax.block_until_ready(decode(params, logits, cache))

    def med(fn, *args):
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2] * 1e3

    return med(prefill, params, tokens, cache0), \
        med(decode, params, logits, cache)


def run(out_path: str = "BENCH_serving.json", smoke: bool = False,
        trace_out: str | None = None) -> dict:
    global REQUESTS, MAX_NEW
    iters = 1 if smoke else 5
    if smoke:
        REQUESTS, MAX_NEW = 6, 4

    cfg = ARCHS[ARCH].reduced()
    hi = HIConfig(theta=0.6, capacity_factor=0.5)
    batches = _make_batches(cfg)
    bucket = max(b.bucket for b in batches)

    eng_new = build_engine(cfg, hi, max_new_tokens=MAX_NEW,
                           cache_len=CACHE_LEN)
    eng_old = build_engine(cfg, hi, max_new_tokens=MAX_NEW,
                           cache_len=CACHE_LEN)
    t_new = _time_path(eng_new.serve, batches, iters)
    t_old = _time_path(eng_old.serve_legacy, batches, iters)

    prefill_ms, decode_ms = _prefill_decode_split(cfg, bucket,
                                                  iters=3 if smoke else 10)

    # -- continuous vs drain on mixed-length Poisson-order traffic ----------
    # calibrated theta (~25% offload, the paper's operating regime);
    # capacity_factor 1.0 keeps escalation semantics identical between the
    # two schedulers (the stream path has no drop policy — it queues)
    reqs = _poisson_mixed_requests(cfg, REQUESTS, MAX_NEW)
    decode_block = MAX_NEW - 1
    eng_drain = build_engine(cfg, HIConfig(theta=0.0, capacity_factor=1.0),
                             max_new_tokens=MAX_NEW, cache_len=CACHE_LEN)
    theta = _calibrate_theta(eng_drain, reqs)     # probe + warm-up in one
    hi_mixed = HIConfig(theta=theta, capacity_factor=1.0)
    eng_drain.hi = hi_mixed                       # theta is a runtime operand
    eng_stream = build_engine(cfg, hi_mixed, max_new_tokens=MAX_NEW,
                              cache_len=CACHE_LEN)
    t_drain = _time_drain_mixed(eng_drain, reqs, iters)
    t_stream = _time_stream_mixed(eng_stream, reqs, iters, decode_block)

    # -- repeated-prefix traffic: prefix-sharing pool on vs off -------------
    repeated = _bench_repeated_prefix(cfg, REQUESTS, iters)

    # -- long-prompt admission: chunked prefill on vs off -------------------
    long_prompt = _bench_long_prompt(cfg, REQUESTS, iters)

    # -- L-tier outage: breaker -> fail-local -> recovery -------------------
    outage = _bench_outage(cfg, reqs, iters)

    # -- quantized KV pool: bf16 vs int8 footprint / throughput / fidelity --
    kv_quant = _bench_kv_quant(cfg, reqs, theta, iters, decode_block)

    # -- telemetry collector: overhead on vs off + Chrome trace export ------
    telemetry = _bench_telemetry(cfg, reqs, theta, iters, decode_block,
                                 trace_out=trace_out)

    # -- mesh-sharded tier split: data=4 S replicas vs single device --------
    mesh = _bench_mesh(smoke)

    result = {
        "arch": ARCH,
        "requests": REQUESTS,
        "batch": BATCH,
        "max_new_tokens": MAX_NEW,
        "buckets": list(BUCKETS),
        "new_rps": REQUESTS / t_new,
        "legacy_rps": REQUESTS / t_old,
        "speedup": t_old / t_new,
        "prefill_ms_per_batch": prefill_ms,
        "decode_ms_per_batch": decode_ms,
        "compiled_shapes": int(eng_new.stats["compiles"]),
        "mixed_poisson": {
            "requests": REQUESTS,
            "buckets": list(STREAM_BUCKETS),
            "max_new_tokens": [2, MAX_NEW],
            "num_slots": NUM_SLOTS,
            "l_slots": NUM_SLOTS // 2,
            "page_size": PAGE_SIZE,
            "decode_block": decode_block,
            "theta_calibrated": theta,
            "offload_frac": eng_stream.stats["offloaded"]
            / max(eng_stream.stats["requests"], 1),
            "drain_rps": REQUESTS / t_drain,
            "stream_rps": REQUESTS / t_stream,
            "stream_vs_drain_speedup": t_drain / t_stream,
            "drain_compiled_shapes": int(eng_drain.stats["compiles"]),
            "stream_compiled_shapes": int(
                eng_stream.stats["stream_compiles"]),
            "stream_ticks": int(eng_stream.stats["stream_ticks"]),
        },
        "repeated_prefix": repeated,
        "long_prompt": long_prompt,
        "outage": outage,
        "kv_quant": kv_quant,
        "telemetry": telemetry,
        "mesh": mesh,
        "smoke": smoke,
        "backend": jax.default_backend(),
    }
    # -- longitudinal history: append this run instead of clobbering --------
    # each entry pins the git rev + date + headline req/s per scenario so
    # successive CI runs accumulate a regression series in one JSON file
    path = pathlib.Path(out_path)
    history = []
    if path.exists():
        try:
            prev = json.loads(path.read_text())
            history = list(prev.get("history", []))
        except (json.JSONDecodeError, OSError):
            history = []
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=pathlib.Path(__file__).resolve().parent,
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        rev = "unknown"
    history.append({
        "rev": rev,
        "date": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "smoke": smoke,
        "rps": {
            "new": result["new_rps"],
            "legacy": result["legacy_rps"],
            "stream": result["mixed_poisson"]["stream_rps"],
            "drain": result["mixed_poisson"]["drain_rps"],
            "prefix_sharing": repeated["sharing_rps"],
            "chunked_prefill": long_prompt["chunked_rps"],
            "outage": outage["outage_rps"],
            "kv_int8": kv_quant["int8_rps"],
            "mesh": mesh["mesh_rps"],
        },
    })
    result["history"] = history
    path.write_text(json.dumps(result, indent=2) + "\n")

    m = result["mixed_poisson"]
    emit("serving_new", t_new / REQUESTS * 1e6,
         f"{result['new_rps']:.1f} req/s device-resident cascade")
    emit("serving_legacy", t_old / REQUESTS * 1e6,
         f"{result['legacy_rps']:.1f} req/s token-by-token loop")
    emit("serving_speedup", 0.0,
         f"{result['speedup']:.2f}x end-to-end; prefill {prefill_ms:.1f}ms "
         f"vs decode {decode_ms:.1f}ms per batch -> {path}")
    emit("serving_stream", t_stream / REQUESTS * 1e6,
         f"{m['stream_rps']:.1f} req/s continuous (paged, "
         f"{m['stream_compiled_shapes']} compiled shape) vs "
         f"{m['drain_rps']:.1f} drained ({m['drain_compiled_shapes']} "
         f"shapes): {m['stream_vs_drain_speedup']:.2f}x on mixed traffic")
    r = repeated
    emit("serving_prefix_sharing", 0.0,
         f"{r['sharing_rps']:.1f} req/s shared-prefix pool vs "
         f"{r['no_sharing_rps']:.1f} without: {r['sharing_speedup']:.2f}x, "
         f"{r['prefill_tokens_saved_per_pass']}/{r['prompt_tokens_per_pass']}"
         f" prefill tokens saved/pass")
    lp = long_prompt
    emit("serving_chunked_prefill", 0.0,
         f"TTFT p50 {lp['ttft_p50_ms']['chunked']:.0f}ms chunked vs "
         f"{lp['ttft_p50_ms']['unchunked']:.0f}ms whole-prompt (p99 "
         f"{lp['ttft_p99_ms']['chunked']:.0f} vs "
         f"{lp['ttft_p99_ms']['unchunked']:.0f}ms); "
         f"{lp['chunked_rps']:.1f} vs {lp['unchunked_rps']:.1f} req/s")
    ot = outage
    emit("serving_outage", 0.0,
         f"L outage ticks {ot['outage_window_ticks']}: "
         f"{ot['outage_rps']:.1f} req/s vs {ot['fault_free_rps']:.1f} "
         f"fault-free ({ot['throughput_sustained_frac']:.2f}x sustained), "
         f"{ot['degraded_local_frac']:.2f} degraded-local, remote serve "
         f"{ot['remote_frac_outage']:.2f} vs {ot['remote_frac_fault_free']:.2f}"
         f" fault-free, post-window escalations "
         f"{ot['post_window_remote_frac'] if ot['post_window_remote_frac'] is not None else 'n/a'}"
         f" remote ({ot['post_window_escalations']}), "
         f"breaker opened {ot['breaker_opens']:.0f}x")
    kq = kv_quant
    gate = kq["teacher_forced_agreement"][QUANT_GATE_ARCH]
    emit("serving_kv_quant", 0.0,
         f"int8 pool {kq['int8_bytes_ratio']:.3f}x bf16 bytes "
         f"({kq['slots_at_budget']['int8']} vs "
         f"{kq['slots_at_budget']['bf16']} slots at the bf16 HBM budget); "
         f"{kq['int8_rps']:.1f} vs {kq['bf16_rps']:.1f} req/s; "
         f"teacher-forced agreement {gate['rate']:.1%} "
         f"({QUANT_GATE_ARCH}, {gate['decisions']} decisions)")
    tm = telemetry
    emit("serving_telemetry", 0.0,
         f"{tm['enabled_rps']:.1f} req/s instrumented vs "
         f"{tm['disabled_rps']:.1f} off ({tm['overhead_frac']:.2%} "
         f"overhead), {tm['trace_events']} trace events"
         + (f" -> {tm['trace_out']}" if tm["trace_out"] else ""))
    ms = mesh
    emit("serving_mesh", 0.0,
         f"{ms['mesh_rps']:.1f} req/s at data={ms['mesh_shape']['data']} vs "
         f"{ms['single_rps']:.1f} single-device ({ms['mesh_speedup']:.2f}x), "
         f"ticks {ms['single_ticks']} -> {ms['mesh_ticks']}, "
         f"transfer_overlap "
         f"{ms['phase_ms_per_tick'].get('transfer_overlap', 0.0):.3f}ms/tick")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload, 1 iteration — the CI tier-1 mode")
    ap.add_argument("--chaos-smoke", action="store_true",
                    help="fault-injection gate: seeded loss/outage/jitter "
                         "schedules with per-tick pool invariants; asserts "
                         "the no-corruption property instead of timing")
    ap.add_argument("--telemetry-smoke", action="store_true",
                    help="telemetry gate: span-tree completeness, terminal "
                         "statuses matching result records, one compiled "
                         "shape, and req/s overhead under the 2%% budget")
    ap.add_argument("--audit-smoke", action="store_true",
                    help="decision-audit gate: audit-on output token-"
                         "identical to off with one compiled shape, "
                         "streaming bins matching the p_histogram oracle, "
                         "hi_audit_* Prometheus families present, and "
                         "req/s overhead under the 2%% budget")
    ap.add_argument("--dump-out", default=None, metavar="PATH",
                    help="chaos-smoke: write the flight recorder's last "
                         "postmortem dump here (CI uploads it as an "
                         "artifact when the gate fails)")
    ap.add_argument("--quant-smoke", action="store_true",
                    help="kv-quant gate: int8 pool bytes <= 0.55x bf16 at "
                         "the same slot/page config, >= 99%% teacher-forced "
                         "greedy top-1 agreement, one compiled shape and "
                         "clean pool invariants in both dtypes")
    ap.add_argument("--mesh-smoke", action="store_true",
                    help="mesh gate: data=4 S replicas serve >= 1.5x the "
                         "single-device req/s on the S-resident workload, "
                         "the transfer_overlap tick phase is nonzero, one "
                         "compiled shape (runs a forced-multi-device "
                         "subprocess)")
    ap.add_argument("--mesh-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the instrumented pass's Chrome trace_event "
                         "JSON here (load in chrome://tracing or Perfetto)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.mesh_worker:
        print("MESH_BENCH_JSON:" + json.dumps(_mesh_worker(args.smoke)))
        return
    if args.chaos_smoke:
        r = run_chaos_smoke(dump_out=args.dump_out)
    elif args.quant_smoke:
        r = run_quant_smoke()
    elif args.telemetry_smoke:
        r = run_telemetry_smoke(trace_out=args.trace_out)
    elif args.audit_smoke:
        r = run_audit_smoke(trace_out=args.trace_out)
    elif args.mesh_smoke:
        r = run_mesh_smoke()
    else:
        r = run(args.out, smoke=args.smoke, trace_out=args.trace_out)
    print(json.dumps(r, indent=2))


if __name__ == "__main__":
    main()
