"""HI serving engine: the paper's ED/ES cascade over LM requests.

The S-tier (reduced variant of the same family) prefills + decodes every
request; per-request confidence (mean token confidence, fused hi_gate when
``use_kernel``) drives the paper's threshold rule; complex requests escalate
to the L-tier through the static-capacity router.  On a pod mesh the
escalation gather is the ED→ES offload link (DESIGN.md §2).

Dispatch-count model (the serving hot path is device-resident)
--------------------------------------------------------------
One ``serve()`` call is ONE compiled XLA program per (batch, bucket) shape:

* prefill      = 1 batched pass over the whole (B, S) prompt (not O(S)
  sequential ``decode_step`` dispatches),
* decode       = ``max_new_tokens`` steps inside a single ``lax.scan``,
* cascade      = 2 tiers: the S-tier generate, the on-device route/gather,
  and the L-tier generate all live in the SAME jitted function, so the S→L
  escalation never materialises NumPy arrays.

Host synchronisation happens exactly once per call, *after* the cascade, via
the module-level ``_host_fetch`` (tests monkeypatch it to assert the single
sync point).  Per-shape executables are AOT-compiled and cached in
``HIEngine._exec`` so bucket switching never silently retraces, and both
tiers' cache buffers are donated (``donate_argnums``) so XLA reuses the
allocations across requests.

Continuous batching (``serve_stream``)
--------------------------------------
``serve()`` drains whole batches: a finished sequence idles its row until the
slowest one in the batch completes, and every (batch, bucket) pair costs one
executable + one donated cache pair.  ``serve_stream()`` replaces both with
the ``serving/scheduler.py`` + ``serving/kv_pool.py`` subsystem:

* cache   = ONE donated page-pool allocation per tier
  (``model_zoo.init_paged_cache``), indexed by an int32 block table — the
  bucket disappears from every device shape.  Pages are REFCOUNTED and
  content-addressed: a rolling chain hash per page of prompt tokens indexes
  every previously-served prompt prefix, admission aliases the longest hit
  read-only into the new slot's block row, and a small donated prefix cache
  (``model_zoo.init_prefix_cache``) keeps last-position logits + recurrent
  state rows so a FULL-prompt repeat restores without any prefill compute;
* tick    = ONE dispatch of ONE AOT-compiled program for ALL buckets:
  copy-on-write page duplications (an appending slot never writes a shared
  page), batched admission prefill of ONLY the uncached suffixes for up to
  ``admit_width`` queued requests (``lax.cond``, skipped at runtime when
  nothing is admitted — or when every admission is a full-prefix restore) +
  ``decode_block`` fused decode steps for every slot of BOTH tiers at
  per-slot positions (idle tiers skip the decode the same way);
* sync    = exactly one ``_host_fetch`` per tick (the drain discipline at
  tick granularity);
* admission = ``batcher.AdmissionQueue`` feeds a slot the moment a sequence
  finishes (EOS / per-request max-new-tokens) or escalates S→L; the L queue
  drops escalations past their per-request ``latency_budget``
  (arXiv:2112.11413 — the S answer stands, counted in ``stats['dropped']``).

So the dispatch-count model becomes: ``serve()`` = 1 program per
(batch, bucket); ``serve_stream()`` = 1 program per TICK, 1 compiled shape
TOTAL (prefix sharing and chunked prefill add runtime operands and
build-time lanes, never a shape), with greedy outputs
token-identical to ``serve()`` on the same bucketized traffic — sharing on
or off, chunking on or off (asserted by tests/test_scheduler.py,
tests/test_prefix_cache.py, tests/test_chunk_lane.py).  Because the L
tier's pool and index persist across escalations, a re-escalated prompt
skips the L prefill entirely — the HI analogue of not redoing work the S
tier already paid for.

Chunked token lane (``chunk_prefill``)
--------------------------------------
Chunked prefill rides ONE primitive, ``model_zoo.forward_chunk_paged`` — a
multi-token paged pass (C tokens per slot at per-slot positions, K/V
through the scalar-prefetched block table, intra-chunk causal masking) that
generalises ``prefill_paged`` / ``decode_step_paged``: prompts longer than
``chunk_size`` skip the admit lane and stream through a (chunk_width,
chunk_size) chunk lane, C tokens per tick, interleaved with every other
slot's decode — the long-prompt TTFT win measured by ``bench_serving.py``'s
``long_prompt`` scenario.  The tick stays ONE AOT-compiled executable with
ONE host sync — ``stats['stream_compiles']`` == 1 with everything enabled.

Observability (``serving/telemetry.py``)
----------------------------------------
``serve_stream(..., telemetry=Telemetry())`` threads the serving telemetry
collector through the scheduler: per-request span trees (``queued →
admitted → prefill_chunk[i] → decode_block[j] → escalate_attempt[k] →
l_verify → terminal``), per-tick phase buckets (``fault_tick /
build_operands / dispatch / host_fetch / postprocess``) + pool gauges,
streaming latency histograms, a Prometheus text snapshot, and Perfetto-
loadable Chrome-trace export (``serving/trace_export.py``) — all host-side
bookkeeping, so the compile and sync invariants above are untouched;
disabled (the default) it costs one branch per hook.  ``HIEngine.stats``
and ``ContinuousScheduler.stats`` are now dict VIEWS over the same typed
counters (``telemetry.EngineStatsView`` reads the live scheduler's fields
instead of copy-and-zeroing them), so the mirrored fault counters can
never diverge.

``benchmarks/bench_serving.py`` measures this path against the legacy
token-by-token loop (kept below as :func:`_decode_loop` + ``serve_legacy``)
and the drained batch path under mixed-length Poisson traffic, and writes
requests/sec + the prefill/decode split to ``BENCH_serving.json``.

This module is deliberately generic over family — it only needs the
model_zoo API — and is exercised end-to-end on CPU with reduced configs by
``examples/serve_cascade.py`` and the integration tests.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import HIConfig, ModelConfig
from repro.core.confidence import confidence as _confidence
from repro.core import router as router_mod
from repro.models import model_zoo
from repro.serving import sampler
from repro.serving.telemetry import EngineCounters, EngineStatsView

# The engine's single device→host sync point.  Kept as a module-level
# indirection so tests can wrap it and count synchronisations per serve().
_host_fetch = jax.device_get


@dataclass
class TierModel:
    cfg: ModelConfig
    params: Any


def _decode_loop(params, cfg: ModelConfig, tokens: jnp.ndarray,
                 cache_len: int, steps: int, metric: str,
                 use_kernel: bool = False):
    """LEGACY path: token-by-token prefill + greedy decode.

    Kept as the reference for the prefill-equivalence tests and as the
    baseline ``benchmarks/bench_serving.py`` measures the batched path
    against.  Returns (generated (B, steps), mean confidence (B,)).
    """
    b, s = tokens.shape
    cache = model_zoo.init_cache(cfg, b, cache_len)

    def prefill_body(carry, t):
        cache, _ = carry
        logits, cache = model_zoo.decode_step(params, cfg, t[:, None], cache)
        return (cache, logits), None

    (cache, logits), _ = jax.lax.scan(prefill_body,
                                      (cache, jnp.zeros((b, cfg.vocab_size))),
                                      tokens.T)

    def gen_body(carry, _):
        cache, logits = carry
        conf = _confidence(logits, metric)
        tok = sampler.greedy(logits)
        logits, cache = model_zoo.decode_step(params, cfg, tok[:, None], cache)
        return (cache, logits), (tok, conf)

    (_, _), (toks, confs) = jax.lax.scan(gen_body, (cache, logits), None,
                                         length=steps)
    return toks.T, confs.mean(axis=0)


def _generate(params, cfg: ModelConfig, tokens: jnp.ndarray, cache, *,
              steps: int, metric: str, theta, use_kernel: bool = False,
              seeds=None, temperature=0.0):
    """Batched prefill + decode, fully on device.

    ``cache`` is overwritten by the prefill (callers donate it).  Sampling is
    greedy when ``temperature`` (a TRACED scalar — changing it never
    retraces) is <= 0; otherwise categorical with PER-REQUEST keys derived
    from ``seeds`` (B,) and the in-request token index, so a request's
    continuation is reproducible across batch compositions and matches the
    continuous scheduler token for token.  Returns (generated (B, steps),
    mean confidence (B,), cache).
    """
    logits, cache = model_zoo.prefill(params, cfg, tokens, cache,
                                      use_kernel=use_kernel)
    if seeds is None:
        seeds = jnp.zeros((tokens.shape[0],), jnp.int32)

    def gen_body(carry, i):
        cache, logits = carry
        if use_kernel:
            from repro.kernels import ops as kops
            conf = kops.hi_gate(logits, theta, metric=metric)[0]
        else:
            conf = _confidence(logits, metric)
        keys = sampler.request_keys(seeds, i)
        tok = sampler.sample(keys, logits, temperature)
        logits, cache = model_zoo.decode_step(params, cfg, tok[:, None], cache)
        return (cache, logits), (tok, conf)

    (cache, _), (toks, confs) = jax.lax.scan(gen_body, (cache, logits),
                                             jnp.arange(steps))
    return toks.T, confs.mean(axis=0), cache


def _make_cascade(s_cfg: ModelConfig, l_cfg: ModelConfig, hi: HIConfig,
                  steps: int, capacity: int, use_kernel: bool):
    """Build the single jitted S→L cascade for one (batch, bucket) shape.

    Everything between the two tier forwards — confidence, threshold,
    route/gather, scatter-merge, agreement stats — stays on device; the
    caller pulls the result dict once, asynchronously, at the end.
    """

    def cascade(s_params, l_params, tokens, theta, temperature, seeds,
                s_cache, l_cache):
        s_toks, s_conf, s_cache = _generate(
            s_params, s_cfg, tokens, s_cache, steps=steps, metric=hi.metric,
            theta=theta, use_kernel=use_kernel, seeds=seeds,
            temperature=temperature)
        offload = s_conf < theta
        decision = router_mod.route(offload, s_conf, capacity)
        complex_tokens = router_mod.gather(tokens, decision)
        l_toks, _, l_cache = _generate(
            l_params, l_cfg, complex_tokens, l_cache, steps=steps,
            metric=hi.metric, theta=theta, use_kernel=use_kernel,
            seeds=seeds[decision.indices], temperature=temperature)
        merged = router_mod.scatter_merge(s_toks, l_toks, decision)
        agree = router_mod.agreement(s_toks, l_toks, decision)
        out = {
            "tokens": merged,
            "s_tokens": s_toks,
            "confidence": s_conf,
            "offloaded": decision.offload_mask,
            "served_remote": decision.served_remote,
            "dropped": decision.dropped,
            "l_indices": decision.indices,
            "l_valid": decision.valid,
            "l_agree": agree,
        }
        return out, s_cache, l_cache

    return cascade


class HIEngine:
    """Two-tier cascade engine with a device-resident hot path.

    ``online_policy`` (paper ref [27], Moothedath et al.): when set, theta is
    tuned online from the L-tier's feedback on offloaded requests — S-tier
    agreement with the L-tier output is the correctness proxy (the ED never
    sees ground truth).  The engine then uses policy.theta instead of the
    static hi.theta; theta is a *traced* scalar so policy updates never force
    a recompile.
    """

    def __init__(self, s_tier: TierModel, l_tier: TierModel, hi: HIConfig,
                 cache_len: int = 128, max_new_tokens: int = 8,
                 online_policy=None, use_kernel: bool = False,
                 temperature: float = 0.0):
        self.s = s_tier
        self.l = l_tier
        self.hi = hi
        self.online_policy = online_policy
        self.cache_len = cache_len
        self.max_new_tokens = max_new_tokens
        self.use_kernel = use_kernel
        self.temperature = temperature
        # (batch, bucket) -> [compiled executable, s_cache, l_cache]
        self._exec: Dict[Tuple[int, int], list] = {}
        self._legacy = None
        self._stream = None          # (key, ContinuousScheduler) lazy cache
        # ONE authority per counter: keys the continuous scheduler also
        # counts are read LIVE through the view (engine total = retired base
        # + attached scheduler), instead of the old copy-and-zero mirroring
        # that kept two divergence-prone stores.  Dict API unchanged.
        self.counters = EngineCounters()
        self.stats: EngineStatsView = EngineStatsView(self.counters)

    # -- executable cache ---------------------------------------------------

    def _executable(self, b: int, s: int) -> list:
        """AOT-compile (once) the cascade for a (batch, bucket) shape and
        allocate the donated per-shape cache buffers."""
        key = (b, s)
        ent = self._exec.get(key)
        if ent is not None:
            return ent
        if s + self.max_new_tokens > self.cache_len:
            raise ValueError(
                f"bucket {s} + max_new_tokens {self.max_new_tokens} exceeds "
                f"cache_len {self.cache_len}")
        cap = router_mod.capacity_for(b, self.hi.capacity_factor)
        fn = jax.jit(_make_cascade(self.s.cfg, self.l.cfg, self.hi,
                                   self.max_new_tokens, cap, self.use_kernel),
                     donate_argnums=(6, 7))
        s_cache = model_zoo.init_cache(self.s.cfg, b, self.cache_len)
        l_cache = model_zoo.init_cache(self.l.cfg, cap, self.cache_len)
        spec = partial(jax.tree.map,
                       lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype))
        with warnings.catch_warnings():
            # buffer donation is a no-op on the CPU backend; stay quiet there
            warnings.filterwarnings("ignore", message=".*[Dd]onat")
            compiled = fn.lower(
                spec(self.s.params), spec(self.l.params),
                jax.ShapeDtypeStruct((b, s), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((b,), jnp.int32),
                spec(s_cache), spec(l_cache)).compile()
        self.stats["compiles"] += 1
        ent = [compiled, s_cache, l_cache]
        self._exec[key] = ent
        return ent

    # -- serving ------------------------------------------------------------

    def serve(self, tokens: np.ndarray,
              seeds: np.ndarray = None) -> Dict[str, np.ndarray]:
        """tokens: (B, S) prompt batch -> generations + offload accounting.

        One compiled-program dispatch; host sync happens exactly once, after
        the full cascade, via ``_host_fetch``.  ``seeds`` (B,) int32
        per-request sampling seeds (used when ``self.temperature > 0``;
        typically the request ids, so sampled continuations match the
        continuous path's).
        """
        b, s = tokens.shape
        ent = self._executable(b, s)
        theta = jnp.asarray(
            self.online_policy.theta if self.online_policy is not None
            else self.hi.theta, jnp.float32)
        seeds = (jnp.zeros((b,), jnp.int32) if seeds is None
                 else jnp.asarray(seeds, jnp.int32))
        t0 = time.perf_counter()
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=".*[Dd]onat")
            out, ent[1], ent[2] = ent[0](
                self.s.params, self.l.params,
                jnp.asarray(tokens, jnp.int32), theta,
                jnp.asarray(self.temperature, jnp.float32), seeds,
                ent[1], ent[2])
        host = _host_fetch(out)       # the single device→host sync point
        t1 = time.perf_counter()

        if self.online_policy is not None:
            # L-tier agreement on served requests is the correctness proxy
            served = host["l_valid"]
            if served.any():
                self.online_policy.update(
                    host["confidence"][host["l_indices"][served]],
                    host["l_agree"][served])

        self.stats["requests"] += b
        self.stats["offloaded"] += int(host["offloaded"].sum())
        self.stats["dropped"] += int(host["dropped"])
        self.stats["serve_time"] += t1 - t0
        return {k: host[k] for k in ("tokens", "s_tokens", "confidence",
                                     "offloaded", "served_remote")}

    def serve_legacy(self, tokens: np.ndarray) -> Dict[str, np.ndarray]:
        """Pre-batched-prefill reference path: per-token scan prefill, NumPy
        routing round-trip, and a host sync per tier.  Benchmarked against
        ``serve`` by ``benchmarks/bench_serving.py``; not used in production.
        """
        if self._legacy is None:
            self._legacy = (
                jax.jit(partial(_decode_loop, cfg=self.s.cfg,
                                cache_len=self.cache_len,
                                steps=self.max_new_tokens,
                                metric=self.hi.metric)),
                jax.jit(partial(_decode_loop, cfg=self.l.cfg,
                                cache_len=self.cache_len,
                                steps=self.max_new_tokens,
                                metric=self.hi.metric)))
        s_step, l_step = self._legacy
        b = tokens.shape[0]
        cap = router_mod.capacity_for(b, self.hi.capacity_factor)
        t0 = time.perf_counter()
        s_out, s_conf = s_step(self.s.params, tokens=jnp.asarray(tokens))
        s_out.block_until_ready()
        theta = (self.online_policy.theta if self.online_policy is not None
                 else self.hi.theta)
        offload = np.asarray(s_conf) < theta
        decision = router_mod.route(jnp.asarray(offload), jnp.asarray(s_conf),
                                    cap)
        complex_tokens = jnp.asarray(tokens)[decision.indices]
        l_out, _ = l_step(self.l.params, tokens=complex_tokens)
        l_out.block_until_ready()
        merged = router_mod.scatter_merge(s_out, l_out, decision)
        t1 = time.perf_counter()
        if self.online_policy is not None:
            served_idx = np.asarray(decision.indices)[np.asarray(decision.valid)]
            if len(served_idx):
                s_sub = np.asarray(s_out)[served_idx]
                l_sub = np.asarray(l_out)[np.asarray(decision.valid)]
                agree = (s_sub == l_sub).all(axis=-1)
                self.online_policy.update(np.asarray(s_conf)[served_idx],
                                          agree)
        self.stats["requests"] += b
        self.stats["offloaded"] += int(offload.sum())
        self.stats["dropped"] += int(decision.dropped)
        self.stats["serve_time"] += t1 - t0
        return {
            "tokens": np.asarray(merged),
            "s_tokens": np.asarray(s_out),
            "confidence": np.asarray(s_conf),
            "offloaded": np.asarray(decision.offload_mask),
            "served_remote": np.asarray(decision.served_remote),
        }

    def serve_stream(self, requests, *, buckets=(32, 64), num_slots: int = 8,
                     l_slots: int = None, page_size: int = 16,
                     admit_width: int = None, decode_block: int = 4,
                     prefix_sharing: bool = True, prefix_entries: int = None,
                     chunk_prefill: bool = False, chunk_size: int = 8,
                     chunk_width: int = 2, kv_dtype: str = "bf16",
                     mesh=None, faults=None,
                     retry=None, validate: bool = False, telemetry=None,
                     audit=None, watchdog=None,
                     flight_recorder=None) -> Dict[int, Dict[str, np.ndarray]]:
        """Continuous-batching entry point: serve ``requests`` (an iterable of
        ``batcher.Request``) through slot-level admission over the paged KV
        pools instead of drained (B, bucket) batches.

        Requests are bucketized by the same ladder the drain path uses, so
        greedy outputs are token-identical to ``serve`` on the same traffic
        for ANY ``admit_width`` (batched admission prefill) / ``decode_block``
        (fused decode steps per tick); unlike the drain path, a finished or
        escalated sequence's slot is re-admitted IMMEDIATELY, per-request
        ``max_new_tokens`` / ``temperature`` / ``eos_id`` are honoured, and
        ONE executable serves every bucket (``stats['stream_compiles']``
        stays at 1).

        ``prefix_sharing`` (default on) enables the pools' content-addressed
        prefix reuse: prompts are chain-hashed at submit, admission aliases
        the longest cached prefix (refcounted, copy-on-write) and prefills
        only the uncached suffix; a repeated prompt — including an S→L
        escalation replay — restores pages + state + logits without running
        the admit lane.  The scheduler (and its pools and indexes) persists
        across ``serve_stream`` calls with the same configuration, so reuse
        is cross-call.  ``stats['prefill_tokens_saved']`` counts the skipped
        prompt positions; outputs stay token-identical to sharing-off.
        Requests carrying a ``latency_budget`` are dropped from the L queue
        once past their deadline (``stats['dropped']``, record flag
        ``dropped`` — the S answer stands).

        ``chunk_prefill`` routes prompts longer than ``chunk_size`` through
        the scheduler's chunked-prefill lane — ingested ``chunk_size`` tokens
        per tick, interleaved with every other slot's decode, instead of
        monopolizing the admit lane (the long-prompt TTFT win measured by
        ``bench_serving.py``); greedy outputs are token-identical with
        chunking on or off.

        ``kv_dtype`` selects the page-pool storage format for both tiers:
        ``"bf16"`` (default, bitwise-identical to the unquantized build) or
        ``"int8"`` — quantized pages with per-page-per-head scales and
        dequantization fused into the page-gather kernels, roughly halving
        KV bytes per slot at a small greedy-fidelity cost (tolerance-based
        rather than bitwise equivalence).  Still one executable and one
        host sync per tick in either mode.

        ``mesh`` (a jax ``Mesh`` with axes ``("data", "model")``, e.g. from
        ``launch.mesh.make_serving_mesh``) turns on mesh-sharded tier-split
        serving: the S tier becomes ``data`` data-parallel replicas (each
        owning its own slot slice + paged-pool shard, run under
        ``shard_map``), the L tier's params and KV pages shard over
        ``model``, and S→L escalation tokens route through a donated
        double-buffered device staging buffer dispatched at tick top so the
        transfer overlaps the same tick's S-side compute (the modelled DCN
        hop costs one tick of L-admission latency, never critical-path
        time).  Still ONE compiled executable and ONE host fetch per tick
        per host; at a (1, 1) debug mesh greedy outputs are token-identical
        to ``mesh=None``.  The mesh participates in the scheduler cache key
        by identity.

        Failure semantics: ``faults`` (a ``serving.faults.FaultSchedule``)
        injects deterministic, seeded ED↔ES transport faults — escalation
        delivery delay, loss, L-tier outage windows, latency spikes — and
        ``retry`` (a ``serving.faults.RetryPolicy``) sets the resilience
        knobs: capped exponential backoff for lost/timed-out escalations, the
        consecutive-failure circuit breaker (closed → open → half-open; open
        = FAIL-LOCAL: the L queue pauses and the gate's traced theta operand
        drops to ``FAIL_LOCAL_THETA`` so nothing offloads — no recompile),
        and the admission retry cap.  Every record carries ``status`` ∈
        {``ok``, ``degraded_local``, ``dropped``, ``rejected``} plus
        ``escalation_retries`` / ``queue_wait_ticks`` / ``esc_created_tick``
        (-1 = never escalated; the outage bench slices the trace into
        during/after-window phases with it); degradation NEVER
        changes compiled shapes (``stats['stream_compiles']`` stays 1 under
        any schedule — fault state is per-run, not part of the scheduler
        cache key).  ``validate=True`` asserts ``KVPool.check_invariants``
        on both tiers after every tick (chaos tests).

        ``telemetry`` (a ``serving.telemetry.Telemetry``) installs the
        observability collector for this call: per-request span trees
        (``queued → admitted → prefill_chunk[i] → decode_block[j] →
        escalate_attempt[k] → l_verify → terminal``), per-tick phase timing
        (``fault_tick / build_operands / dispatch / host_fetch /
        postprocess``) and pool gauges — all host-side, so
        ``stats['stream_compiles']`` and the one-sync-per-tick discipline
        are unchanged; ``None`` (default) keeps the zero-overhead disabled
        path.  Export via ``telemetry.prometheus_text()`` /
        ``histogram_summary()`` or ``serving.trace_export.chrome_trace``.

        ``audit`` (a ``serving.audit.GateAudit``) installs the
        decision-quality layer with the same contract: every gate decision
        the scheduler absorbs (admit / chunk / decode / request) is
        recorded with its theta-IN-EFFECT, completed escalations feed
        ground-truth outcomes, and the
        streaming aggregates (reliability bins, per-``tclass`` ECE +
        offload rate, theta margins, empirical regret) ride the existing
        single host fetch — zero extra syncs, token-identical outputs.
        ``watchdog`` (a ``serving.audit.SLOWatchdog``) evaluates SLO /
        drift thresholds once per tick; ``flight_recorder`` (a
        ``serving.flight_recorder.FlightRecorder``) keeps a bounded ring
        of tick snapshots and dumps a deterministic postmortem JSON on
        watchdog breach, breaker-open, invariant failure, or a stall.

        Returns per-request result records keyed by request_id.
        """
        from repro.serving.batcher import AdmissionQueue
        from repro.serving.scheduler import ContinuousScheduler

        requests = list(requests)
        mesh_key = None if mesh is None else (tuple(sorted(mesh.shape.items())),
                                              id(mesh))
        key = (tuple(sorted(buckets)), num_slots, l_slots, page_size,
               admit_width, decode_block, prefix_sharing, prefix_entries,
               chunk_prefill, chunk_size, chunk_width, kv_dtype, mesh_key)
        if self._stream is None or self._stream[0] != key:
            sched = ContinuousScheduler(
                self.s, self.l, self.hi, max_prompt_len=max(buckets),
                max_new_tokens=self.max_new_tokens, num_slots=num_slots,
                l_slots=l_slots, page_size=page_size,
                admit_width=admit_width, decode_block=decode_block,
                use_kernel=self.use_kernel, temperature=self.temperature,
                prefix_sharing=prefix_sharing,
                prefix_entries=prefix_entries,
                chunk_prefill=chunk_prefill, chunk_size=chunk_size,
                chunk_width=chunk_width, kv_dtype=kv_dtype, mesh=mesh)
            self._stream = (key, sched)
            self.stats["stream_compiles"] += sched.stats["compiles"]
        sched = self._stream[1]
        # engine totals read the scheduler's typed counters LIVE through the
        # view (attach folds a replaced scheduler's totals into the base
        # first) — no per-key copy-and-zero, so the two can never diverge
        self.stats.attach(sched)
        sched.set_default_temperature(self.temperature)
        sched.set_audit(audit)
        sched.set_telemetry(telemetry)
        sched.set_watchdog(watchdog)
        sched.set_flight_recorder(flight_recorder)
        from repro.serving.faults import NO_FAULTS, RetryPolicy
        sched.set_faults(faults if faults is not None else NO_FAULTS,
                         retry if retry is not None else RetryPolicy(),
                         validate)
        queue = AdmissionQueue(buckets=buckets,
                               page_size=page_size if prefix_sharing else None)
        for r in requests:
            queue.submit(r)
        theta = (self.online_policy.theta if self.online_policy is not None
                 else self.hi.theta)
        return sched.run(queue, theta=theta)

    def summary(self) -> Dict[str, float]:
        n = max(self.stats["requests"], 1)
        return {
            **self.stats,
            "offload_frac": self.stats["offloaded"] / n,
            "drop_frac": self.stats["dropped"] / n,
        }


def build_engine(cfg: ModelConfig, hi: HIConfig, rng=None, dtype=jnp.float32,
                 cache_len: int = 128, max_new_tokens: int = 8,
                 use_kernel: bool = False,
                 temperature: float = 0.0) -> HIEngine:
    """Construct an S/L cascade for one architecture family: L = reduced
    assigned config (CPU-runnable), S = its s_variant."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(rng)
    l_cfg = cfg
    s_cfg = cfg.s_variant(hi.s_scale)
    l_params = model_zoo.init_params(k1, l_cfg, dtype)
    s_params = model_zoo.init_params(k2, s_cfg, dtype)
    return HIEngine(TierModel(s_cfg, s_params), TierModel(l_cfg, l_params),
                    hi, cache_len=cache_len, max_new_tokens=max_new_tokens,
                    use_kernel=use_kernel, temperature=temperature)
