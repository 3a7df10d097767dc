"""Continuous-batching scheduler over the paged KV pool — ONE chunked token
lane per tick.

The drain-path ``HIEngine.serve`` admits a whole (B, bucket) batch, runs the
cascade, and only then admits the next batch: a finished sequence's slot idles
until the SLOWEST sequence in its batch finishes, and every bucket compiles
its own executable with its own donated cache.  This module replaces batch
draining with SLOT-level admission (Orca-style iteration scheduling; see the
online-HI line of work, arXiv:2304.00891, for the per-sample admission model):

* Each tier owns ``num_slots`` decode slots backed by ONE :class:`KVPool`.
* Every scheduler *tick* is ONE device dispatch of one AOT-compiled program —
  the SAME program regardless of prompt bucket — and host sync happens
  exactly once per tick, post-cascade, through the engine's ``_host_fetch``.

What one tick contains (the chunk-lane dispatch-count model)
------------------------------------------------------------
All lanes below live in the SAME compiled executable; build-time flags decide
which lanes are traced, runtime ``lax.cond`` operands skip idle ones.  Per
tier, in order:

1. **COW lane** (sharing): the admission plan's copy-on-write page
   duplications, so appends never touch a shared page.
2. **Admit lane**: batched (A, S_max) prefill of up to ``admit_width`` queued
   requests + prefix-cache save/restore (``lax.cond``-skipped when idle or
   when every admission is a full-prefix restore).
3. **Chunk-prefill lane** (``chunk_prefill``): ONE
   ``model_zoo.forward_chunk_paged`` pass over a dedicated
   (``chunk_width``, ``chunk_size``) lane — the host schedules up to W
   still-prefilling slots per tick, each fed its next C prompt tokens at its
   own position — so a long prompt is ingested C tokens per tick INTERLEAVED
   with decode instead of monopolizing the admit lane (whose compiled width
   shrinks to ~one chunk when chunking is on and sharing off, since no
   long prompt ever reaches it); the slot that consumes its last chunk
   samples token 0 from the chunk's final live logits and joins decode in
   the same tick.  Recurrent families commit their state to exactly the
   chunk's live token count via the lane's boundary snapshots
   (``select_stage`` / ``scatter_chunk_slots``).
4. **Decode lane**: ``decode_block`` fused decode steps for every
   decoding slot at per-slot positions (a ``lax.scan``).  It reads K/V for
   the tier's occupied slots only: each attention layer gathers their pages
   at the narrowest width of a ladder (:func:`decode_widths`) that holds
   them, picked per tick by a runtime operand.

The single host sync sits after ALL of the above: one ``_host_fetch`` of the
tick's token/confidence outputs.  ``stats['compiles']`` stays at 1 no matter
which lanes are enabled — chunking adds operands and a build-time lane, never
a shape.

Prefix sharing (``prefix_entries > 0``) changes admission, not decode: see
PR 3's notes.  Chunk-prefilled admissions still READ cached prefixes (they
start at ``plan.start``) but register nothing — their pages fill over many
ticks, and a same-tick alias would read unwritten pages.

The L-tier admission queue additionally enforces the time-constrained
offloading drop policy (Fresa & Champati, arXiv:2112.11413): an escalation
whose request has outlived its ``latency_budget`` is dropped — the S-tier
answer stands, ``stats["dropped"]`` counts it, and the result record is
flagged.

Failure semantics (``serving/faults.py``)
-----------------------------------------
The S→L escalation now crosses a simulated ED↔ES transport
(:class:`~repro.serving.faults.EscalationLink`, driven by a seeded
:class:`~repro.serving.faults.FaultSchedule` set via :meth:`set_faults`)
instead of being appended directly to the L queue.  All of it is HOST-side:
fault injection, retries, and degradation change per-tick operand VALUES
only, never a compiled shape — ``stats['compiles']`` stays at 1 with any
fault schedule (empty ticks that merely advance timers dispatch the same
executable with every lane cond-skipped).

* Every request terminates with exactly ONE result record carrying one of
  the :data:`~repro.serving.faults.STATUSES`: ``ok`` (served locally or
  remotely), ``degraded_local`` (the escalation failed — loss/timeout
  retries exhausted, budget expiry during retry, L outage, open breaker, or
  L admission starvation — and the S answer stands), ``dropped``
  (arXiv:2112.11413 budget expiry in the L queue), or ``rejected``
  (admission gave up: page demand unsatisfiable for
  ``RetryPolicy.admit_retry_limit`` fruitless ticks — the bounded
  replacement for the old "scheduler stalled" RuntimeError).
* Lost / timed-out escalations retry with capped exponential backoff
  (``RetryPolicy``); a retry whose ``latency_budget`` has already expired
  gives up as ``degraded_local``.
* A :class:`~repro.serving.faults.CircuitBreaker` watches consecutive L-path
  failures: closed → open (FAIL-LOCAL: L admission paused, resends held, the
  tick's theta OPERAND becomes ``FAIL_LOCAL_THETA`` so the gate stops
  offloading — no recompile) → half-open (one probe escalation re-admitted;
  success closes, failure re-opens).  ``stats['breaker_open_ticks']`` /
  ``stats['breaker_opens']`` count it.
* Leak-free cancellation: an escalation abandoned at ANY point (lost,
  expired, outage-aborted mid-decode, or pending at drain) releases its
  L-tier slot and KV pages through ``KVPool.free`` — ``check_invariants()``
  holds after every tick (``validate=True``) and the pools' ``held_slots``
  are empty after drain.

Mesh-sharded tier split (``mesh=``)
-----------------------------------
Passing a ``jax.sharding.Mesh`` with ``("data", "model")`` axes (see
``launch/mesh.py``) turns the tick into the paper's ED/ES split at
datacenter scale, still ONE compiled executable and ONE host fetch per
tick:

* **S tier — R data-parallel replicas.** ``shard_map`` over ``data`` runs
  the UNCHANGED per-tier tick body once per replica; each replica owns a
  disjoint ``num_slots`` slot slice, its own host allocator
  (:class:`_TierRuntime` ``S0..S{R-1}``), and its own shard of ONE stacked
  donated ``(R, ...)`` pool pytree (``P("data")``).  Host-side admission
  round-robins requests across replicas; per-replica operands are built as
  raw numpy (``tick_inputs(raw=True)``), stacked, and shipped with one
  sharded ``device_put`` per tree.
* **L tier — one GSPMD-sharded instance.** Params via
  ``sharding/specs.param_shardings`` and paged KV pools via
  ``paged_cache_shardings`` shard the K/V-head dimension over ``model``;
  the L tick body itself is untouched (XLA partitions it).
* **Overlapped escalation transfer.** Escalations cross the mesh through a
  donated double-buffered staging tensor ``(2, admit_width, S_max)``: the
  write half is filled by ``dynamic_update_slice`` at TICK TOP (no S-side
  consumer, so the copy overlaps the same tick's prefill/decode — the
  ``transfer_overlap`` telemetry phase), and the L admit lane reads last
  tick's half, gathering per-admission rows — the host's ``admit_tokens``
  copy is ZEROED on the mesh path, so the device transfer is load-bearing.
  The resulting +1-tick L admission latency is the modelled ED→ES DCN hop;
  a ``ready`` gate holds an escalation in the L queue until its staged row
  is readable.
* A ``(1, 1)`` debug mesh is semantics-free: greedy outputs are bitwise
  the single-device path's in both ``kv_dtype`` modes
  (tests/test_mesh_serving.py).  Faults/retry/breaker machinery is
  host-side and unchanged (fault TIMING shifts by the DCN hop, as a real
  hop would).  ``use_kernel`` with ``model > 1`` is explicitly rejected.

Outputs are TOKEN-IDENTICAL to the drain path on the same bucketized
prompts, for ANY ``admit_width``/``decode_block``, with prefix sharing ON or
OFF and chunked prefill ON or OFF (the chunk lane's per-position math is the
decode step's — tests/test_chunk_lane.py asserts greedy-token identity per
family, bitwise logits for the recurrent families whose chunk IS a scan of
the per-token step).  One caveat: MoE routed dispatch is
batch-coupled (capacity drops depend on co-resident rows), so MoE equality
is exact only up to routing-drop determinism — with the generous decode-path
``capacity_factor`` drops are absent on this reference; see
``moe.prefill_paged``.
"""
from __future__ import annotations

import bisect
import re
import time
import warnings
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import HIConfig, ModelConfig
from repro.core.confidence import confidence as _confidence
from repro.models import model_zoo
from repro.serving import sampler
from repro.serving.batcher import AdmissionQueue, AdmittedRequest
from repro.serving.faults import (NO_FAULTS, CircuitBreaker, Escalation,
                                  EscalationLink, FaultSchedule,
                                  FAIL_LOCAL_THETA, RetryPolicy)
from repro.serving.kv_pool import AdmitPlan, KVPool
from repro.serving.telemetry import SchedCounters, StatsView, Telemetry

# The lanes of one tier's tick, each under ``jax.named_scope(<tier>)`` then
# ``jax.named_scope(<lane>)``: the compiled tick's instructions carry
# ``.../<tier>/<lane>/...`` in their ``op_name`` metadata, which
# :meth:`ContinuousScheduler.lane_of_ops` reads back.  ``cow`` copy-on-write
# page copies; ``admit`` the admission prefill with its gate, sampling and
# first-token scatter; ``prefix`` the prefix-cache snapshot save/restore;
# ``chunk`` the chunk-prefill lane; ``decode`` the fused decode scan.
LANES = ("cow", "admit", "prefix", "chunk", "decode")
TIERS = ("S", "L")
_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%?([^\s=]+) = .*metadata=\{[^}]*op_name="([^"]*)"')


@contextmanager
def _lane(tier: str, lane: str):
    with jax.named_scope(tier), jax.named_scope(lane):
        yield


def decode_widths(num_slots: int) -> tuple:
    """The decode lane's width ladder for a tier of ``num_slots`` slots: a
    quarter, a half and all of them, each rounded up to a multiple of 8 (a
    sublane tile) and capped at ``num_slots``, without duplicates.  Each
    tick reads K/V at the narrowest width that holds the tier's occupied
    slots."""
    return tuple(sorted({min(8 * -(-num_slots // (8 * d)), num_slots)
                         for d in (4, 2, 1)}))


def _tier_tick_fn(tier: str, cfg: ModelConfig, metric: str,
                  use_kernel: bool, decode_block: int, sharing: bool,
                  chunk: int = 0):
    """Device-side per-tier tick: COW copies + batched cond-prefill +
    prefix-cache save/restore + chunk-prefill lane + K fused decode steps of
    the ``tier`` ("S" or "L"), each lane under its ``<tier>/<lane>`` scope
    (:data:`LANES`).  The decode lane's attention reads K/V for the
    occupied slots only, at the width the host picked from
    :func:`decode_widths`.

    ``chunk`` is a BUILD-time switch: with ``chunk == 0`` the chunk lane is
    not traced at all, which keeps greedy outputs bitwise stable with it
    off."""

    lane = partial(_lane, tier)

    def conf_of(logits, theta):
        if use_kernel:
            from repro.kernels import ops as kops
            return kops.hi_gate(logits, theta, metric=metric)[0]
        return _confidence(logits, metric)

    def admit_and_prefix(params, tin, pool):
        """COW + batched admission prefill + prefix-cache save/restore.
        Returns (admission logits0 (A, V), core, prefix-or-None)."""
        core = pool["core"]
        a = tin["admit_tokens"].shape[0]

        if sharing:
            # copy-on-write duplications first: prefill reads and decode
            # appends must see the private copies.  Skipped at runtime on the
            # (common) no-COW tick — the kernel path in particular streams
            # the whole page pool, which would tax every steady-state tick.
            with lane("cow"):
                core = jax.lax.cond(
                    tin["any_cow"],
                    lambda c: model_zoo.cow_pages(cfg, c, tin["cow_src"],
                                                  tin["cow_dst"],
                                                  use_kernel=use_kernel),
                    lambda c: c, core)

        def admit(core):
            return model_zoo.prefill_paged(
                params, cfg, tin["admit_tokens"], tin["admit_len"],
                tin["admit_slot"], tin["admit_blocks"], core,
                use_kernel=use_kernel,
                start=tin["admit_start"] if sharing else None)

        def skip(core):
            return jnp.zeros((a, cfg.vocab_size), jnp.float32), core

        # skipped when nothing is admitted — or (sharing) when every
        # admission this tick is a full-prefix restore
        with lane("admit"):
            logits0, core = jax.lax.cond(tin["any_prefill"], admit, skip,
                                         core)
        prefix = None
        if sharing:
            with lane("prefix"):
                prefix = pool["prefix"]
                # full restores read their admission logits + recurrent
                # state from the PRE-SAVE prefix cache: a restore's entry
                # was filled in an earlier tick, and reading before this
                # tick's saves keeps a same-tick eviction that recycles the
                # restore's row (LRU under row pressure) from corrupting the
                # restored state
                logits0 = jnp.where(tin["restore_mask"][:, None],
                                    prefix["logits"][tin["restore_row"]],
                                    logits0)
                core = model_zoo.snapshot_restore(cfg, core, prefix,
                                                  tin["restore_row"],
                                                  tin["restore_slot"])
                # computing admissions persist their logits + recurrent
                # state into their reserved rows (sentinel rows drop);
                # computing slots are disjoint from restored slots, so the
                # gather below is unaffected by the restore scatter above
                prefix = dict(prefix, logits=prefix["logits"].at[
                    tin["save_row"]].set(logits0, mode="drop"))
                prefix = model_zoo.snapshot_save(cfg, core, prefix,
                                                 tin["save_row"],
                                                 tin["admit_slot"])
        return logits0, core, prefix

    def chunk_lane(params, theta, tin, core):
        """Chunked prefill: one multi-token pass over a DEDICATED W-row lane
        (W = chunk_width slots scheduled by the host this tick, W <<
        num_slots) feeding each its next C prompt tokens.  Reads route
        through the scheduled slots' full block rows, writes through their
        write-masked rows; recurrent state commits to exactly ``chunk_keep``
        inputs via the lane's boundary snapshots and scatters back at the
        scheduled slot ids (sentinel rows drop).  Returns the per-ROW last
        live position's sampled token + confidence (consumed where
        ``chunk_fin``)."""

        def go(core):
            mini = model_zoo.gather_chunk_slots(cfg, core, tin["chunk_slot"])
            logits_c, mini, staged = model_zoo.forward_chunk_paged(
                params, cfg, tin["chunk_tokens"], tin["chunk_pos"],
                tin["chunk_block"], mini, use_kernel=use_kernel,
                write_block=tin["chunk_wblock"])
            sel = model_zoo.select_stage(cfg, staged, tin["chunk_keep"])
            core = model_zoo.scatter_chunk_slots(cfg, core, mini, sel,
                                                 tin["chunk_slot"])
            idx = jnp.maximum(tin["chunk_keep"] - 1, 0)
            last = jnp.take_along_axis(logits_c, idx[:, None, None],
                                       axis=1)[:, 0]
            return last, core

        def skip(core):
            w = tin["chunk_slot"].shape[0]
            return jnp.zeros((w, cfg.vocab_size), jnp.float32), core

        logits_c, core = jax.lax.cond(tin["any_chunk"], go, skip, core)
        conf_c = conf_of(logits_c, theta)
        keys = sampler.request_keys(tin["chunk_seed"], 0)
        tok_c = sampler.sample(keys, logits_c, tin["chunk_temp"])
        return tok_c, conf_c, core

    def tick(params, theta, tin, pool):
        logits0, core, prefix = admit_and_prefix(params, tin, pool)
        with lane("admit"):
            conf0 = conf_of(logits0, theta)
            keys0 = sampler.request_keys(tin["admit_seed"], 0)
            tok0 = sampler.sample(keys0, logits0, tin["admit_temp"])  # (A,)
            # admitted slots decode their own first tokens in the same
            # tick; padded admission rows carry an out-of-range slot ->
            # dropped
            last0 = tin["last_tok"].at[tin["admit_slot"]].set(tok0,
                                                              mode="drop")
        out = {"admit_tok": tok0, "admit_conf": conf0}
        if chunk:
            with lane("chunk"):
                tok_c, conf_c, core = chunk_lane(params, theta, tin, core)
                n_slots = tin["last_tok"].shape[0]
                fin_slot = jnp.where(tin["chunk_fin"], tin["chunk_slot"],
                                     n_slots)
                last0 = last0.at[fin_slot].set(tok_c, mode="drop")
            out["chunk_tok"] = tok_c
            out["chunk_conf"] = conf_c
        block = tin["block"]
        b = block.shape[0]
        wb = tin["decode_wblock"] if chunk else \
            (tin["wblock"] if sharing else None)
        # K/V is read only for the tier's occupied slots: each layer's
        # page gather and attention run over the first w entries of the
        # host's ``decode_slot`` list, w the narrowest width of the ladder
        # that holds them (every other row's step reads nothing)
        rows = (tin["decode_slot"], tin["decode_branch"], decode_widths(b))

        def body(carry, kk):
            last, core = carry
            logits, core = model_zoo.decode_step_paged(
                params, cfg, last[:, None], tin["pos"] + kk, block, core,
                use_kernel=use_kernel, write_block=wb, rows=rows)
            confs_k = conf_of(logits, theta)
            keys = sampler.request_keys(tin["seeds"], tin["tok_idx"] + kk)
            toks_k = sampler.sample(keys, logits, tin["temps"])
            return (toks_k, core), (toks_k, confs_k)

        def decode(core):
            pre_d = model_zoo.chunk_stage(cfg, core) if chunk else None
            (_, core), (toks, confs) = jax.lax.scan(
                body, (last0, core), jnp.arange(decode_block))
            if chunk:
                # slots still mid chunk-prefill took garbage decode steps:
                # their page writes were null-masked, restore their state
                core = model_zoo.restore_stage(cfg, core, pre_d,
                                               ~tin["decode_live"])
            return toks, confs, core

        def idle(core):
            # this tier has no decoding slots this tick (e.g. everything is
            # still chunk-prefilling): skip the scan entirely
            return (jnp.zeros((decode_block, b), jnp.int32),
                    jnp.zeros((decode_block, b), jnp.float32), core)

        with lane("decode"):
            toks, confs, core = jax.lax.cond(tin["any_live"], decode, idle,
                                             core)
        out.update({"toks": toks, "confs": confs})       # toks (K, B)
        out_pool = {"core": core, "prefix": prefix} if sharing \
            else {"core": core}
        return out, out_pool

    return tick


@dataclass
class _Active:
    """One request occupying a decode slot."""
    adm: AdmittedRequest
    steps: int
    tokens: List[int] = field(default_factory=list)
    confs: List[float] = field(default_factory=list)
    first_tok: float = 0.0                       # monotonic first-emit time
    hit_eos: bool = False

    def emit(self, tok: int, conf: float) -> None:
        if self.done:
            return
        if not self.tokens:
            self.first_tok = time.monotonic()
        self.tokens.append(int(tok))
        self.confs.append(float(conf))
        eos = self.adm.request.eos_id
        if eos is not None and int(tok) == eos:
            self.hit_eos = True

    @property
    def done(self) -> bool:
        return self.hit_eos or len(self.tokens) >= self.steps

    @property
    def ttft(self) -> float:
        return self.first_tok - self.adm.submit_time


class _TierRuntime:
    """Host-side slot state for one tier (numpy mirrors of tick operands)."""

    def __init__(self, cfg: ModelConfig, num_slots: int, max_context: int,
                 page_size: int, admit_width: int, dtype,
                 prefix_entries: int = 0, max_prompt_len: int = 0,
                 num_pages: Optional[int] = None, chunk_size: int = 0,
                 chunk_width: int = 2, name: str = "S",
                 alloc: bool = True):
        if num_pages is None:
            # sharing headroom: beyond every slot's full context, enough
            # pages to RETAIN prefix_entries full prompts without evicting
            # under load
            num_pages = num_slots * (max_context // page_size) + 1
            num_pages += prefix_entries * (-(-max_prompt_len // page_size))
        # ``alloc=False``: this runtime is one DATA-axis replica of the
        # mesh-sharded scheduler — it keeps the full host-side allocator
        # (its own free list, block table, refcounts: the per-shard free
        # lists) but its device buffers are ShapeDtypeStructs; the real
        # allocation is one stacked (R, ...) donated tree the scheduler owns.
        self.pool = KVPool(cfg, num_slots, max_context, page_size,
                           num_pages=num_pages, dtype=dtype,
                           prefix_entries=prefix_entries, alloc=alloc)
        self.sharing = prefix_entries > 0
        self.name = name               # tier label for telemetry tracks
        self.num_slots = num_slots
        self.admit_width = admit_width
        self.chunk_size = chunk_size
        self.chunk_width = max(1, min(chunk_width, num_slots))
        self.chunk_sched: List = []    # (slot, keep, fin) rows THIS tick
        self.default_temp = 0.0      # engine-level fallback (Request wins)
        self.slot_req: List[Optional[_Active]] = [None] * num_slots
        self.last_tok = np.zeros((num_slots,), np.int32)
        self.pos = np.zeros((num_slots,), np.int32)
        self.seeds = np.zeros((num_slots,), np.int32)
        self.tok_idx = np.zeros((num_slots,), np.int32)
        self.temps = np.zeros((num_slots,), np.float32)
        self.chunk_fed = np.zeros((num_slots,), np.int32)   # prompt tokens fed
        self.chunk_left = np.zeros((num_slots,), np.int32)  # 0 = decoding
        self.admitted: List[int] = []    # slots admitted THIS tick, row order
        self.plans: List[AdmitPlan] = []  # aligned admission plans
        self.widths = decode_widths(num_slots)
        self.decode_rows = 0             # decode lane width of the last tick

    @property
    def busy(self) -> int:
        return sum(r is not None for r in self.slot_req)

    def free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slot_req):
            if r is None:
                return i
        return None

    def admit(self, adm: AdmittedRequest, steps: int, decode_block: int,
              tick: int) -> Optional[int]:
        """Claim a slot + pages for ``adm``; returns the slot index, or
        ``None`` if no capacity this tick (callers MUST test ``is None`` —
        slot 0 is falsy).  With sharing, the pool aliases the longest cached
        prefix and the returned plan carries start / restore / save / COW
        decisions.  With ``chunk_size`` set, a prompt whose uncached
        remainder exceeds one chunk skips the admit lane: its pages are
        claimed now and its tokens flow through the chunk-prefill lane C per
        tick."""
        slot = self.free_slot()
        # decode writes reach bucket + steps - 2, plus <= K-1 overrun steps
        context = adm.bucket + max(steps - 1, 1) + (decode_block - 1)
        if slot is None:
            return None
        chunked = bool(self.chunk_size) and adm.bucket > self.chunk_size
        if self.sharing:
            plan = self.pool.admit_prefix(slot, context, adm.bucket,
                                          adm.page_hashes, adm.full_hash,
                                          tick, register=not chunked)
            if plan is None:
                return None
            if plan.is_restore:
                chunked = False          # full hit: restoring beats chunking
        else:
            if not self.pool.can_alloc(context):
                return None
            self.pool.alloc(slot, context)
            plan = AdmitPlan(slot=slot)
        self.slot_req[slot] = _Active(adm, steps)
        self.pos[slot] = adm.bucket
        self.seeds[slot] = adm.request.request_id
        self.tok_idx[slot] = 1                 # token 0 comes from the prefill
        self.temps[slot] = (adm.request.temperature
                            if adm.request.temperature > 0
                            else self.default_temp)
        self.last_tok[slot] = 0                # replaced on-device by tok0
        if chunked and adm.bucket - plan.start > self.chunk_size:
            self.chunk_fed[slot] = plan.start
            self.chunk_left[slot] = adm.bucket - plan.start
        else:
            self.admitted.append(slot)
            self.plans.append(plan)
        return slot

    def release(self, slot: int) -> _Active:
        rec = self.slot_req[slot]
        self.slot_req[slot] = None
        self.pool.free(slot)
        self.pos[slot] = 0
        self.tok_idx[slot] = 0
        self.temps[slot] = 0.0
        self.last_tok[slot] = 0
        self.chunk_fed[slot] = 0
        self.chunk_left[slot] = 0
        return rec

    def tick_inputs(self, s_max: int, raw: bool = False) -> Dict:
        """This tick's operand dict.  ``raw=True`` returns NUMPY leaves
        (copies where state arrays are exposed) instead of device arrays —
        the mesh dispatch path stacks R replicas' operands host-side and
        ships each leaf with ONE sharded ``device_put``, which beats R
        per-leaf ``jnp.stack`` + reshard by a wide margin per tick."""
        a = self.admit_width
        tokens = np.zeros((a, s_max), np.int32)
        lens = np.ones((a,), np.int32)
        slots = np.full((a,), self.num_slots, np.int32)    # drop sentinel
        blocks = np.zeros((a, self.pool.n_pages_per_slot), np.int32)
        seeds = np.zeros((a,), np.int32)
        temps = np.zeros((a,), np.float32)
        for row, slot in enumerate(self.admitted):
            adm = self.slot_req[slot].adm
            tokens[row, : adm.bucket] = adm.tokens
            lens[row] = adm.bucket
            slots[row] = slot
            blocks[row] = self.pool.block[slot]
            seeds[row] = self.seeds[slot]
            temps[row] = self.temps[slot]
        out = {
            "last_tok": self.last_tok,
            "pos": self.pos,
            "block": self.pool.block,
            "seeds": self.seeds,
            "tok_idx": self.tok_idx,
            "temps": self.temps,
            "admit_tokens": tokens,
            "admit_len": lens,
            "admit_slot": slots,
            "admit_blocks": blocks,
            "admit_seed": seeds,
            "admit_temp": temps,
        }
        if self.chunk_size:
            c, w = self.chunk_size, self.chunk_width
            npg = self.pool.n_pages_per_slot
            base = self.pool.write_block() if self.sharing else self.pool.block
            ctoks = np.zeros((w, c), np.int32)
            cslot = np.full((w,), self.num_slots, np.int32)  # drop sentinel
            cpos = np.zeros((w,), np.int32)
            ckeep = np.zeros((w,), np.int32)
            cfin = np.zeros((w,), bool)
            cblock = np.zeros((w, npg), np.int32)
            cwb = np.zeros((w, npg), np.int32)
            cseed = np.zeros((w,), np.int32)
            ctemp = np.zeros((w,), np.float32)
            dlive = np.zeros((self.num_slots,), bool)
            self.chunk_sched = []
            for slot in range(self.num_slots):
                rec = self.slot_req[slot]
                if rec is None:
                    continue
                left = int(self.chunk_left[slot])
                if left == 0:
                    dlive[slot] = True
                    continue
                row = len(self.chunk_sched)
                if row == w:
                    continue               # lane full: this slot waits a tick
                keep = min(c, left)
                fed = int(self.chunk_fed[slot])
                seg = rec.adm.tokens[fed:fed + c]
                ctoks[row, : len(seg)] = seg
                cslot[row] = slot
                cpos[row] = fed
                ckeep[row] = keep
                cfin[row] = keep == left
                cblock[row] = self.pool.block[slot]
                cwb[row] = base[slot]
                cseed[row] = self.seeds[slot]
                ctemp[row] = self.temps[slot]
                dlive[slot] = cfin[row]    # joins decode the same tick
                self.chunk_sched.append((slot, keep, bool(cfin[row])))
            out.update({
                "chunk_tokens": ctoks,
                "chunk_slot": cslot,
                "chunk_pos": cpos,
                "chunk_keep": ckeep,
                "chunk_fin": cfin,
                "any_chunk": np.asarray(bool(ckeep.any())),
                "chunk_block": cblock,
                "chunk_wblock": cwb,
                "chunk_seed": cseed,
                "chunk_temp": ctemp,
                "decode_live": dlive,
                "decode_wblock": np.where(dlive[:, None], base,
                                          0).astype(np.int32),
            })
            out["any_live"] = np.asarray(bool(dlive.any()))
        else:
            out["any_live"] = np.asarray(self.busy > 0)
        # the decode lane's rows: occupied slots first, then the sentinel,
        # read at widths[decode_branch]
        live = np.flatnonzero([r is not None for r in self.slot_req])
        dslot = np.full((self.num_slots,), self.num_slots, np.int32)
        dslot[: len(live)] = live
        k = bisect.bisect_left(self.widths, len(live))
        self.decode_rows = self.widths[k] if out["any_live"] else 0
        out["decode_slot"] = dslot
        out["decode_branch"] = np.asarray(k, np.int32)
        if not self.sharing:
            out["any_prefill"] = np.asarray(bool(self.admitted))
            return self._finish_inputs(out, raw)
        entries = self.pool.prefix_entries
        starts = np.zeros((a,), np.int32)
        restore_mask = np.zeros((a,), bool)
        restore_row = np.zeros((a,), np.int32)
        restore_slot = np.full((a,), self.num_slots, np.int32)
        save_row = np.full((a,), entries, np.int32)        # drop sentinel
        cow_src = np.zeros((a,), np.int32)
        cow_dst = np.zeros((a,), np.int32)
        any_prefill = False
        for row, (slot, plan) in enumerate(zip(self.admitted, self.plans)):
            starts[row] = plan.start
            if plan.is_restore:
                restore_mask[row] = True
                restore_row[row] = plan.restore_row
                restore_slot[row] = slot
            else:
                any_prefill = True
                if plan.save_row >= 0:
                    save_row[row] = plan.save_row
            if plan.cow is not None:
                cow_src[row], cow_dst[row] = plan.cow
        out.update({
            "any_prefill": np.asarray(any_prefill),
            "any_cow": np.asarray(bool(cow_dst.any())),
            "admit_start": starts,
            "restore_mask": restore_mask,
            "restore_row": restore_row,
            "restore_slot": restore_slot,
            "save_row": save_row,
            "cow_src": cow_src,
            "cow_dst": cow_dst,
            "wblock": self.pool.write_block(),
        })
        return self._finish_inputs(out, raw)

    @staticmethod
    def _finish_inputs(out: Dict, raw: bool) -> Dict:
        if raw:
            # numpy leaves; live state arrays (pos / seeds / block ...) are
            # copied so the caller's host-side stacking can never alias a
            # runtime that mutates between build and dispatch
            return {k: np.array(v) for k, v in out.items()}
        return {k: jnp.asarray(v) for k, v in out.items()}

    def pool_operand(self) -> Dict:
        if self.sharing:
            return {"core": self.pool.buffers,
                    "prefix": self.pool.prefix_buffers}
        return {"core": self.pool.buffers}

    def store_pool(self, pool: Dict) -> None:
        self.pool.buffers = pool["core"]
        if self.sharing:
            self.pool.prefix_buffers = pool["prefix"]


class ContinuousScheduler:
    """Slot-level admission over paged pools for BOTH cascade tiers.

    One instance = one AOT-compiled tick executable (``stats['compiles']``
    stays at 1 no matter how many prompt buckets flow through — the paged
    pool removed the bucket from every device shape, and prefix sharing
    and chunked prefill add only runtime operands and build-time lanes).
    ``admit_width`` batches admission prefills like the drain path batches
    prompts; ``decode_block`` fuses that many decode steps per tick.
    ``prefix_sharing`` turns on the pool's content-addressed prefix reuse.
    ``chunk_prefill`` routes prompts longer than ``chunk_size`` through the
    chunk lane (C tokens per tick, interleaved with decode).
    ``kv_dtype="int8"`` stores both tiers' KV pages quantized (int8 with
    per-page-per-head scales, dequantization fused into the page-gather
    kernels) at roughly half the pool bytes; the default ``"bf16"`` keeps
    every bitwise invariant of the unquantized build.

    Telemetry (``serving/telemetry.py``)
    ------------------------------------
    :meth:`set_telemetry` installs a collector; ``None`` (the default)
    disables it — every hook site is a single ``is None`` branch, and NO
    telemetry work touches the device: ``stats['compiles']`` stays 1 and the
    tick keeps its single ``_host_fetch`` sync with telemetry on or off.
    Enabled, the collector records

    * a span tree per request: ``queued → admitted → prefill_chunk[i] →
      decode_block[j] → l_queued ⊃ escalate_attempt[k] → l_verify →
      terminal`` with the terminal ``status``, TTFT, TPOT, queue-wait
      ticks, and retry counts
      (terminal hooks sit exactly where records reach their FINAL status:
      ``_finish_s`` un-escalated, ``_finish_l``, ``_degrade``,
      ``_drop_expired``, ``_reject``);
    * per-tick wall-time phases (``fault_tick`` — fault machinery + slot
      admission, ``build_operands``, ``dispatch``, ``host_fetch``,
      ``postprocess``, ``observe``), each also a ``sched.<phase>``
      profiler annotation inside the tick's ``sched.tick`` step, plus pool /
      breaker / queue gauges sampled once per tick from host state the
      scheduler already holds (``KVPool.gauges``, breaker ``state_id``,
      queue depths) — no extra device traffic.

    The compiled tick's lanes run under ``<tier>/<lane>`` named scopes
    (:data:`LANES`); :meth:`lane_of_ops` maps its instructions, and so the
    profiler's device events, to them.

    ``serving/trace_export.py`` renders the collector as Perfetto-loadable
    Chrome ``trace_event`` JSON (slot tracks per tier, S→L flow arrows).
    """

    def __init__(self, s_tier, l_tier, hi: HIConfig, *, max_prompt_len: int,
                 max_new_tokens: int, num_slots: int = 8,
                 l_slots: Optional[int] = None, page_size: int = 16,
                 admit_width: Optional[int] = None, decode_block: int = 4,
                 use_kernel: bool = False, temperature: float = 0.0,
                 cache_dtype=jnp.bfloat16, prefix_sharing: bool = False,
                 prefix_entries: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 chunk_prefill: bool = False, chunk_size: int = 8,
                 chunk_width: int = 2, kv_dtype: str = "bf16", mesh=None):
        if max_prompt_len % page_size:
            raise ValueError(f"max_prompt_len {max_prompt_len} must be a "
                             f"multiple of page_size {page_size}")
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype must be 'bf16' or 'int8', "
                             f"got {kv_dtype!r}")
        if kv_dtype == "int8":
            # quantized page pools: int8 pages + per-page-per-head fp32
            # scales, dequant fused into the page-gather kernels.  bf16 (the
            # default) keeps every bitwise invariant of the unquantized build.
            cache_dtype = jnp.int8
        self.kv_dtype = kv_dtype
        if chunk_prefill and chunk_size < 1:
            raise ValueError(f"chunk_size {chunk_size} must be >= 1")
        if mesh is not None:
            for ax in ("data", "model"):
                if ax not in mesh.shape:
                    raise ValueError(
                        f"serving mesh needs axes ('data', 'model'), got "
                        f"{tuple(mesh.shape)}")
            if use_kernel and mesh.shape["model"] > 1:
                raise NotImplementedError(
                    "use_kernel with model>1: the L tier's Pallas page-"
                    "gather cannot be GSPMD-partitioned over the model axis "
                    "(the S tier's kernels run per-shard under shard_map and "
                    "are fine at any data size)")
        self._mesh = mesh
        self.s = s_tier
        self.l = l_tier
        self.hi = hi
        self.max_prompt_len = max_prompt_len
        self.max_new_tokens = max_new_tokens
        self.decode_block = max(1, decode_block)
        self.prefix_sharing = prefix_sharing
        self.chunk = int(chunk_size) if chunk_prefill else 0
        l_slots = l_slots if l_slots is not None else max(2, num_slots // 2)
        admit_width = admit_width if admit_width is not None else num_slots
        page = page_size
        raw_ctx = max_prompt_len + max_new_tokens + self.decode_block - 1
        max_context = -(-raw_ctx // page) * page
        s_entries = (prefix_entries if prefix_entries is not None
                     else 2 * num_slots) if prefix_sharing else 0
        l_entries = (prefix_entries if prefix_entries is not None
                     else 2 * l_slots) if prefix_sharing else 0
        n_rep = 1 if mesh is None else int(mesh.shape["data"])
        self.srts: List[_TierRuntime] = [
            _TierRuntime(s_tier.cfg, num_slots, max_context, page,
                         admit_width, cache_dtype,
                         prefix_entries=s_entries,
                         max_prompt_len=max_prompt_len,
                         num_pages=num_pages, chunk_size=self.chunk,
                         chunk_width=chunk_width,
                         name="S" if mesh is None else f"S{r}",
                         alloc=mesh is None)
            for r in range(n_rep)]
        self.lrt = _TierRuntime(l_tier.cfg, l_slots, max_context, page,
                                min(admit_width, l_slots), cache_dtype,
                                prefix_entries=l_entries,
                                max_prompt_len=max_prompt_len,
                                num_pages=num_pages, chunk_size=self.chunk,
                                chunk_width=chunk_width, name="L")
        self.set_default_temperature(temperature)
        # with chunking on (and no prefix hits routing long prompts back to
        # the admit lane), every admit-lane prompt is <= chunk_size: the
        # batched admission pass shrinks from (A, max_prompt_len) to
        # (A, ~chunk_size) — the long-prompt traffic stops taxing every
        # admission tick, which is the TTFT win bench_serving measures
        self._admit_s_max = max_prompt_len
        if self.chunk and not prefix_sharing:
            self._admit_s_max = min(max_prompt_len,
                                    -(-self.chunk // page) * page)
        # ONE authoritative counter store (typed); ``stats`` keeps the
        # historical dict API as a read/write view over it — HIEngine reads
        # the same fields live instead of copy-and-zeroing them
        self.counters = SchedCounters()
        self.stats: StatsView = StatsView(self.counters)
        # telemetry collector (None = disabled: every hook site is a single
        # ``is None`` branch — the zero-overhead default)
        self.tel: Optional[Telemetry] = None
        # decision-quality observability (serving/audit.py + flight_recorder
        # .py) — same contract as telemetry: host-side, None by default,
        # never part of the compile key
        self.aud = None                      # GateAudit
        self.wd = None                       # SLOWatchdog
        self.fr = None                       # FlightRecorder
        self._opens_seen = 0                 # breaker-open dump edge detect
        self._run_theta = float(hi.theta)    # the run's CALIBRATED theta
        self._eff_theta = float(hi.theta)    # theta IN EFFECT this tick
        # fault-injection state (host-side; set_faults replaces per run —
        # never part of the compile key, so changing it never recompiles)
        self.faults: FaultSchedule = NO_FAULTS
        self.policy: RetryPolicy = RetryPolicy()
        self.validate = False
        self._link: Optional[EscalationLink] = None
        self._breaker: Optional[CircuitBreaker] = None
        self._esc_meta: Dict[int, Escalation] = {}
        self._probe: Optional[int] = None
        self._tick0 = 0
        # escalation transfer staging (mesh mode): rows the L admit lane may
        # read THIS tick (written last tick) and rows being written this tick
        self._staged: Dict[int, int] = {}
        self._staged_next: Dict[int, int] = {}
        self._stage_tokens = np.zeros((0, 0), np.int32)
        self._stage_wix = 0

        s_tick = _tier_tick_fn("S", s_tier.cfg, hi.metric, use_kernel,
                               self.decode_block, self.srt.sharing,
                               chunk=self.chunk)
        l_tick = _tier_tick_fn("L", l_tier.cfg, hi.metric, use_kernel,
                               self.decode_block, self.lrt.sharing,
                               chunk=self.chunk)

        def tick(s_params, l_params, theta, s_in, l_in, s_pool, l_pool):
            s_out, s_pool = s_tick(s_params, theta, s_in, s_pool)
            l_out, l_pool = l_tick(l_params, theta, l_in, l_pool)
            return {"s": s_out, "l": l_out}, s_pool, l_pool

        spec = partial(jax.tree.map,
                       lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype))
        s_in0 = self.srt.tick_inputs(self._admit_s_max)
        l_in0 = self.lrt.tick_inputs(self._admit_s_max)
        if mesh is None:
            with warnings.catch_warnings():
                warnings.filterwarnings("ignore", message=".*[Dd]onat")
                self._exec = jax.jit(tick, donate_argnums=(5, 6)).lower(
                    spec(self.s.params), spec(self.l.params),
                    jax.ShapeDtypeStruct((), jnp.float32),
                    spec(s_in0), spec(l_in0),
                    spec(self.srt.pool_operand()),
                    spec(self.lrt.pool_operand())).compile()
        else:
            self._build_mesh_exec(mesh, s_tick, l_tick, spec, s_in0, l_in0)
        self.counters.compiles += 1

    @property
    def srt(self) -> _TierRuntime:
        """Replica 0's runtime — THE runtime on the single-device path (the
        historical attribute; mesh-unaware callers and tests keep working)."""
        return self.srts[0]

    def lane_of_ops(self) -> Dict[str, str]:
        """Instruction name (without ``%``) -> lane (``"L/decode"``, ...) of
        every instruction of the compiled tick whose ``op_name`` metadata
        lies under a lane scope (:data:`LANES`).  The profiler's device
        events carry the same instruction names, so this map attributes
        device time to lanes; instructions XLA adds without metadata (its
        buffer copies, say) belong to no lane."""
        out: Dict[str, str] = {}
        for line in self._exec.as_text().splitlines():
            m = _OP_NAME.match(line)
            if m is None:
                continue
            parts = m.group(2).split("/")
            for tier, lane in zip(parts, parts[1:]):
                if tier in TIERS and lane in LANES:
                    out[m.group(1)] = f"{tier}/{lane}"
                    break
        return out

    def _build_mesh_exec(self, mesh, s_tick, l_tick, spec, s_in0, l_in0
                         ) -> None:
        """Compile the mesh-aware tick: ``shard_map`` the S tier over
        ``data`` (one replica per shard, running the UNMODIFIED per-tier
        tick on its own slot slice + pool shard), GSPMD-shard the L tier's
        params and KV pages over ``model``, and thread the donated
        double-buffered escalation staging buffer through the same single
        executable.  Still ONE compile, ONE host fetch per tick."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.sharding import specs as sh

        n_rep = len(self.srts)
        ns_rep = NamedSharding(mesh, P())
        ns_data = NamedSharding(mesh, P("data"))
        self._ns_rep, self._ns_data = ns_rep, ns_data

        # one-time placement: S params replicated, L params model-sharded by
        # the existing partition rules, L pool pages model-sharded on the
        # KV-head dim, S pools ONE stacked (R, ...) zero tree over ``data``
        self._s_params = jax.device_put(self.s.params, ns_rep)
        l_param_sh = sh.param_shardings(self.l.params, mesh, fsdp=False)
        self._l_params = jax.device_put(self.l.params, l_param_sh)
        l_core_sh = sh.paged_cache_shardings(self.l.cfg, mesh,
                                             self.lrt.pool.buffers)
        self.lrt.pool.buffers = jax.device_put(self.lrt.pool.buffers,
                                               l_core_sh)
        l_pool_sh = {"core": l_core_sh}
        if self.lrt.sharing:
            self.lrt.pool.prefix_buffers = jax.device_put(
                self.lrt.pool.prefix_buffers, ns_rep)
            l_pool_sh["prefix"] = jax.tree.map(
                lambda _: ns_rep, self.lrt.pool.prefix_buffers)
        self._s_pool = jax.tree.map(
            lambda s: jax.device_put(
                jnp.zeros((n_rep,) + s.shape, s.dtype), ns_data),
            self.srt.pool_operand())
        t_rows = self.lrt.admit_width
        self._stage = {"buf": jax.device_put(
            jnp.zeros((2, t_rows, self._admit_s_max), jnp.int32), ns_rep)}
        self._stage_tokens = np.zeros((t_rows, self._admit_s_max), np.int32)

        def s_body(s_params, theta, s_in, s_pool):
            tin = jax.tree.map(lambda a: a[0], s_in)
            pool = jax.tree.map(lambda a: a[0], s_pool)
            out, pool = s_tick(s_params, theta, tin, pool)
            return (jax.tree.map(lambda a: a[None], out),
                    jax.tree.map(lambda a: a[None], pool))

        # check_vma=False: the body is replicated over the (unused) model
        # axis; replication checking can't see that through the squeezes
        s_sharded = jax.shard_map(s_body, mesh=mesh,
                                  in_specs=(P(), P(), P("data"), P("data")),
                                  out_specs=(P("data"), P("data")),
                                  check_vma=False)

        def tick(s_params, l_params, theta, s_in, l_in, s_pool, l_pool,
                 stage):
            # tick top: copy this tick's escalation rows into the WRITE half
            # of the staging buffer.  Nothing on the S side depends on it,
            # so XLA schedules the transfer alongside the S lanes — the
            # S->L hop never sits on the critical path.  The admit lane
            # reads the OTHER half: rows staged LAST tick.
            with _lane("L", "admit"):
                wix = l_in["stage_wix"]
                buf = jax.lax.dynamic_update_slice(
                    stage["buf"], l_in["stage_tokens"][None], (wix, 0, 0))
                read = jax.lax.dynamic_slice(
                    buf, (1 - wix, 0, 0), (1,) + buf.shape[1:])[0]
                l_in = dict(l_in, admit_tokens=read[l_in["stage_row"]])
            s_out, s_pool = s_sharded(s_params, theta, s_in, s_pool)
            l_out, l_pool = l_tick(l_params, theta, l_in, l_pool)
            return ({"s": s_out, "l": l_out}, s_pool, l_pool, {"buf": buf})

        stack = partial(jax.tree.map, lambda a: jax.ShapeDtypeStruct(
            (n_rep,) + a.shape, a.dtype))
        l_in_spec = dict(
            spec(l_in0),
            stage_tokens=jax.ShapeDtypeStruct(
                (t_rows, self._admit_s_max), jnp.int32),
            stage_row=jax.ShapeDtypeStruct((t_rows,), jnp.int32),
            stage_wix=jax.ShapeDtypeStruct((), jnp.int32))
        stage_sh = {"buf": ns_rep}
        in_sh = (ns_rep, l_param_sh, ns_rep, ns_data, ns_rep,
                 ns_data, l_pool_sh, stage_sh)
        out_sh = ({"s": ns_data, "l": ns_rep}, ns_data, l_pool_sh, stage_sh)
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=".*[Dd]onat")
            self._exec = jax.jit(
                tick, donate_argnums=(5, 6, 7), in_shardings=in_sh,
                out_shardings=out_sh).lower(
                    spec(self.s.params), spec(self.l.params),
                    jax.ShapeDtypeStruct((), jnp.float32),
                    stack(s_in0), l_in_spec,
                    stack(self.srt.pool_operand()),
                    spec(self.lrt.pool_operand()),
                    spec(self._stage)).compile()

    def set_faults(self, faults: Optional[FaultSchedule] = None,
                   policy: Optional[RetryPolicy] = None,
                   validate: Optional[bool] = None) -> None:
        """Install the fault schedule / retry policy / per-tick invariant
        checking for subsequent ``run`` calls.  Host-side only: the compiled
        tick executable is untouched (fault windows are RUN-relative ticks —
        each ``run`` re-anchors tick 0, so a schedule replays identically on
        a reused scheduler)."""
        if faults is not None:
            self.faults = faults
        if policy is not None:
            self.policy = policy
        if validate is not None:
            self.validate = bool(validate)

    def set_telemetry(self, tel: Optional[Telemetry]) -> None:
        """Install (``Telemetry``) or remove (``None``) the telemetry
        collector for subsequent ``run`` calls.  Host-side only — never part
        of the compile key, so toggling it never recompiles; disabled is the
        zero-overhead default (each hook is one ``is None`` branch)."""
        self.tel = tel
        if tel is not None:
            tel.counters = self.counters
            tel.audit = self.aud

    def set_audit(self, aud) -> None:
        """Install (``GateAudit``) or remove (``None``) the gate audit
        stream.  Host-side only — the confidences it consumes already come
        back in the tick's single ``_host_fetch``, so enabling it adds zero
        syncs and never recompiles (``stream_compiles == 1`` holds)."""
        self.aud = aud
        if self.tel is not None:
            self.tel.audit = aud

    def set_watchdog(self, wd) -> None:
        """Install (``SLOWatchdog``) or remove (``None``) the once-per-tick
        SLO evaluation.  Breaches emit telemetry instant events and trigger
        the flight recorder when those collectors are installed."""
        self.wd = wd

    def set_flight_recorder(self, fr) -> None:
        """Install (``FlightRecorder``) or remove (``None``) the bounded
        tick-snapshot ring.  Dumps fire on watchdog breach, breaker-open,
        ``check_invariants`` failure, and the idle-tick stall bound."""
        self.fr = fr

    def set_default_temperature(self, temperature: float) -> None:
        """Engine-level sampling temperature used for requests that don't set
        their own (Request.temperature > 0 wins) — keeps ``serve_stream``
        consistent with ``serve``'s engine-wide temperature."""
        for rt in self.srts:
            rt.default_temp = float(temperature)
        self.lrt.default_temp = float(temperature)

    @property
    def prefix_stats(self) -> Dict[str, int]:
        """Cumulative prefix-cache counters summed over both tiers: hits /
        full_hits / tokens_saved / cow_copies / evictions."""
        agg: Dict[str, int] = {}
        for rt in (*self.srts, self.lrt):
            for k, v in rt.pool.stats.items():
                agg[k] = agg.get(k, 0) + v
        return agg

    # -- host loop ----------------------------------------------------------

    def _dispatch(self, theta_j):
        """Build the tick operands, run the ONE compiled executable, store
        the donated pools back, and host-fetch the outputs (the tick's single
        sync)."""
        from repro.serving import engine as engine_mod   # _host_fetch hook

        if self._mesh is not None:
            return self._dispatch_mesh(theta_j)
        tel = self.tel
        s_in = self.srt.tick_inputs(self._admit_s_max)
        l_in = self.lrt.tick_inputs(self._admit_s_max)
        if tel is not None:
            tel.phase("dispatch")
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=".*[Dd]onat")
            out, s_pool, l_pool = \
                self._exec(self.s.params, self.l.params, theta_j,
                           s_in, l_in, self.srt.pool_operand(),
                           self.lrt.pool_operand())
        self.srt.store_pool(s_pool)
        self.lrt.store_pool(l_pool)
        if tel is not None:
            tel.phase("host_fetch")
        host = engine_mod._host_fetch(out)   # the tick's single sync
        if tel is not None:
            tel.phase("postprocess")
        self.counters.ticks += 1
        return host

    def _prepare_stage(self, l_queue, cur: int) -> None:
        """Stage up to ``lrt.admit_width`` head-of-queue escalations for the
        NEXT tick's L admit lane: their (padded) prompt tokens are copied
        into the staging buffer's write half inside THIS tick's dispatch, so
        the transfer overlaps this tick's S-side compute.  An escalation is
        re-staged every tick until admitted (its row may move); the host
        remembers rid -> row for the gate in ``_try_admit``."""
        t_rows, s_max = self._stage_tokens.shape
        tokens = np.zeros((t_rows, s_max), np.int32)
        nxt: Dict[int, int] = {}
        for i, adm in enumerate(l_queue):
            if i >= t_rows:
                break
            n = min(adm.bucket, s_max)
            tokens[i, :n] = adm.tokens[:n]
            nxt[adm.request.request_id] = i
        self._stage_tokens = tokens
        self._staged_next = nxt
        self._stage_wix = cur % 2

    def _dispatch_mesh(self, theta_j):
        """Mesh-mode tick dispatch: stage the escalation transfer operands
        FIRST (tick top — the device copy into the write half has no S-side
        consumers, so it overlaps the same tick's prefill/decode), then stack
        the per-replica S operands over ``data`` and run the one executable.
        Still exactly ONE compile and ONE host fetch per tick per host."""
        from repro.serving import engine as engine_mod   # _host_fetch hook

        tel = self.tel
        t_rows = self.lrt.admit_width
        rows = np.zeros((t_rows,), np.int32)
        for row, slot in enumerate(self.lrt.admitted):
            rid = self.lrt.slot_req[slot].adm.request.request_id
            rows[row] = self._staged[rid]   # gate guarantees membership
        stage_in = jax.device_put(
            {"stage_tokens": self._stage_tokens, "stage_row": rows,
             "stage_wix": np.asarray(self._stage_wix, np.int32)},
            self._ns_rep)
        if tel is not None:
            tel.phase("build_operands")
        # raw numpy leaves stacked host-side, then ONE sharded transfer per
        # tree — per-leaf jnp.stack + reshard dominated the tick wall time
        s_raw = [rt.tick_inputs(self._admit_s_max, raw=True)
                 for rt in self.srts]
        s_in = jax.device_put(
            jax.tree.map(lambda *xs: np.stack(xs), *s_raw), self._ns_data)
        l_in = self.lrt.tick_inputs(self._admit_s_max, raw=True)
        # the device-side staging buffer is the authoritative token source
        # for the L admit lane — zero the host copy so the transfer path is
        # load-bearing, not decorative
        l_in["admit_tokens"] = np.zeros_like(l_in["admit_tokens"])
        l_in = jax.device_put(l_in, self._ns_rep)
        l_in.update(stage_in)
        theta_j = jax.device_put(np.asarray(theta_j), self._ns_rep)
        if tel is not None:
            tel.phase("dispatch")
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=".*[Dd]onat")
            out, s_pool, l_pool, stage = self._exec(
                self._s_params, self._l_params, theta_j, s_in, l_in,
                self._s_pool, self.lrt.pool_operand(), self._stage)
        self._s_pool = s_pool
        self.lrt.store_pool(l_pool)
        self._stage = stage
        self._staged = self._staged_next   # write half becomes readable
        if tel is not None:
            tel.phase("host_fetch")
        host = engine_mod._host_fetch(out)   # the tick's single sync
        if tel is not None:
            tel.phase("postprocess")
        self.counters.ticks += 1
        return host

    def _gauges(self, l_queue_len: int = 0) -> Dict[str, float]:
        """Per-tick telemetry gauges — all host state the scheduler already
        holds, so sampling costs no device traffic."""
        g: Dict[str, float] = {}
        for rt in (*self.srts, self.lrt):
            for k, v in rt.pool.gauges().items():
                g[f"{k}@{rt.name}"] = v
            g[f"busy_slots@{rt.name}"] = rt.busy
            g[f"decode_rows@{rt.name}"] = rt.decode_rows
        g["l_queue_depth"] = l_queue_len
        if self._mesh is not None:
            # transfer staging buffer: rows readable this tick (occupancy of
            # the read half) + the ping-pong write index — flight-recorder
            # snapshots carry these alongside the per-replica @S{r} gauges
            g["stage_occupancy"] = len(self._staged)
            g["stage_wix"] = self._stage_wix
            g["replicas"] = len(self.srts)
        if self._link is not None:
            g["esc_in_flight"] = self._link.pending
        if self._breaker is not None:
            g["breaker_state"] = self._breaker.state_id
        return g

    def _observe_tick(self, l_queue_len: int = 0) -> None:
        """End-of-tick observability fan-out: telemetry gauges (audit
        aggregates merged in — they become Chrome counter tracks), SLO
        watchdog evaluation, flight-recorder snapshot + dump triggers, then
        the telemetry tick's end (all of this is its ``observe`` phase).
        All host-side over state the tick already produced; with every
        collector ``None`` this is one branch."""
        tel, aud, wd, fr = self.tel, self.aud, self.wd, self.fr
        if tel is None and wd is None and fr is None:
            return
        tick = self.counters.ticks - 1       # the tick just dispatched
        gauges = self._gauges(l_queue_len)
        if aud is not None:
            gauges.update(aud.gauge_values())
        breaches = [] if wd is None else \
            wd.evaluate(tick, tel=tel, audit=aud, gauges=gauges)
        if tel is not None:
            for b in breaches:
                tel.instant(f"slo_breach:{b['kind']}", **b)
        if fr is not None:
            self._record_flight(tick, gauges, breaches)
        if tel is not None:
            tel.end_tick(gauges)

    def _record_flight(self, tick: int, gauges: Dict[str, float],
                       breaches: List[Dict[str, Any]]) -> None:
        tel, fr = self.tel, self.fr
        # snapshot fields are deterministic functions of the request trace +
        # fault schedule; serve_time is wall clock and would break the
        # byte-identical dump guarantee
        counters = {k: v for k, v in self.stats.items() if k != "serve_time"}
        snap: Dict[str, Any] = {"tick": tick, "gauges": gauges,
                                "counters": counters}
        if fr.include_timings and tel is not None \
                and tel.current_tick is not None:
            # the phases up to ``observe``, which this snapshot is part of
            snap["phase_seconds"] = {
                p: round(t1 - t0, 9)
                for p, t0, t1 in tel.current_tick.segments}
        fr.record(snap)
        for b in breaches:
            fr.trigger(f"slo_breach:{b['kind']}", tick, b)
        if self._breaker is not None \
                and self._breaker.opens > self._opens_seen:
            self._opens_seen = self._breaker.opens
            fr.trigger("breaker_open", tick,
                       {"opens": self._breaker.opens,
                        "opened_tick": self._breaker.opened_tick})

    def run(self, queue: AdmissionQueue, *, theta: Optional[float] = None
            ) -> Dict[int, Dict[str, Any]]:
        """Drain ``queue`` through the slots; returns per-request records
        keyed by request_id: tokens / s_tokens / confidence / offloaded /
        served_remote / dropped / status / escalation_retries /
        queue_wait_ticks / ttft (mirroring ``HIEngine.serve``'s fields).
        Every submitted
        request terminates with exactly one record whose ``status`` is one of
        ``faults.STATUSES`` — under ANY fault schedule installed via
        :meth:`set_faults`.  Ticks that only advance host-side timers
        (backoff, breaker cooldown, fault windows, admission retries)
        dispatch the same compiled executable with every lane skipped, so
        ``stats['compiles']`` stays at 1.

        ``stats['serve_time']`` accounting is SINGLE-ENTRY: one
        ``try/finally`` brackets the whole drain, so every exit path (normal
        completion, the stall RuntimeError)
        adds the elapsed time exactly once — the old per-path additions
        could in principle double-book (tests/test_telemetry.py regresses
        this)."""
        t0 = time.perf_counter()
        try:
            return self._run(queue, theta)
        finally:
            self.counters.serve_time += time.perf_counter() - t0

    def _run(self, queue: AdmissionQueue, theta: Optional[float]
             ) -> Dict[int, Dict[str, Any]]:
        theta = float(self.hi.theta if theta is None else theta)
        theta_j = jnp.asarray(theta, jnp.float32)
        results: Dict[int, Dict[str, Any]] = {}
        tel = self.tel
        self._run_theta = theta
        self._eff_theta = theta
        # per-run fault state: run-relative tick 0 anchors here, so a seeded
        # FaultSchedule replays identically on a reused scheduler
        theta_fail_j = jnp.asarray(FAIL_LOCAL_THETA, jnp.float32)
        self._tick0 = self.counters.ticks
        self._link = EscalationLink(self.faults, self.policy)
        self._breaker = CircuitBreaker(self.policy)
        self._esc_meta = {}
        self._probe = None
        self._opens_seen = 0
        # mesh mode: the staging pipeline re-anchors per run — nothing from
        # an earlier run's buffer halves is readable
        self._staged = {}
        self._staged_next = {}
        stall, idle = self._stall_limit(), 0
        l_queue: deque = deque()
        while (len(queue) or l_queue or any(rt.busy for rt in self.srts)
               or self.lrt.busy or self._link.pending):
            if tel is not None:   # fault machinery + admission
                tel.begin_tick(self.counters.ticks, "fault_tick")
            cur = self.counters.ticks - self._tick0
            state = self._breaker.state_at(cur)
            if state == CircuitBreaker.OPEN:
                self.counters.breaker_open_ticks += 1
            else:
                if state == CircuitBreaker.CLOSED:
                    self._probe = None
            self._fault_tick(cur, l_queue, results)
            for rt in self.srts:
                self._try_admit(rt, queue,
                                on_give_up=lambda adm: self._reject(adm,
                                                                    results))
            self._drop_expired(l_queue, results, cur)
            # mesh mode gates L admission on the staging pipeline: only
            # escalations whose tokens were staged LAST tick (readable from
            # the buffer's read half this tick) may admit — the +1 tick is
            # the modelled DCN hop, paid off the critical path
            ready = None if self._mesh is None else \
                (lambda adm: adm.request.request_id in self._staged)
            self._try_admit(self.lrt, l_queue, limit=self._l_admit_limit(cur),
                            on_give_up=lambda adm: self._l_give_up(adm, cur,
                                                                   results),
                            ready=ready)
            if self._mesh is not None:
                self._prepare_stage(l_queue, cur)
            for slot in range(self.lrt.num_slots):
                rec = self.lrt.slot_req[slot]
                if rec is None:
                    continue
                esc = self._esc_meta.get(rec.adm.request.request_id)
                if esc is not None and esc.l_admit_tick < 0:
                    esc.l_admit_tick = cur
                    if self._breaker.state == CircuitBreaker.HALF_OPEN \
                            and self._probe is None:
                        self._probe = esc.rid
            if tel is not None:
                tel.phase("build_operands" if self._mesh is None
                          else "transfer_overlap")
            s_busy = any(rt.busy for rt in self.srts)
            if not (len(queue) or l_queue or s_busy or self.lrt.busy
                    or self._link.pending):
                if tel is not None:
                    tel.drop_tick()    # nothing dispatched
                break                  # everything left resolved host-side
            if (s_busy or self.lrt.busy
                    or any(rt.admitted for rt in self.srts)
                    or self.lrt.admitted):
                idle = 0
            else:
                # a pure timer tick: backoff / cooldown / fault window /
                # admission retry.  Legitimate and bounded — the limit only
                # trips on a genuinely unbounded schedule or policy.
                idle += 1
                if idle > stall:
                    if self.fr is not None:
                        self.fr.trigger("stall", cur, {
                            "idle_ticks": idle, "queue": len(queue),
                            "l_queue": len(l_queue),
                            "in_flight": self._link.pending})
                    raise RuntimeError(
                        f"scheduler stalled: {idle} consecutive idle ticks "
                        f"with work pending (queue={len(queue)}, "
                        f"l_queue={len(l_queue)}, "
                        f"in_flight={self._link.pending})")
            open_now = self._breaker.state == CircuitBreaker.OPEN
            self._eff_theta = FAIL_LOCAL_THETA if open_now else theta
            host = self._dispatch(theta_fail_j if open_now else theta_j)
            if self._mesh is None:
                self._absorb(self.srt, host["s"],
                             lambda rec: self._finish_s(rec, theta, results))
            else:
                # host["s"] leaves carry the stacked replica axis: each
                # replica absorbs its own slice of the fetched outputs
                for r, rt in enumerate(self.srts):
                    self._absorb(rt,
                                 jax.tree.map(lambda a, _r=r: a[_r],
                                              host["s"]),
                                 lambda rec: self._finish_s(rec, theta,
                                                            results))
            self._absorb(self.lrt, host["l"],
                         lambda rec: self._finish_l(rec, results))
            if self.validate:
                try:
                    for rt in self.srts:
                        rt.pool.check_invariants()
                    self.lrt.pool.check_invariants()
                except AssertionError as e:
                    if self.fr is not None:
                        self.fr.trigger("invariant_failure", cur,
                                        {"error": str(e)})
                    raise
            if tel is not None:
                tel.phase("observe")
            self._observe_tick(len(l_queue))

        self.counters.esc_lost += self._link.lost
        self.counters.breaker_opens += self._breaker.opens
        return results

    # -- fault machinery (host-side; see serving/faults.py) -----------------

    def _stall_limit(self) -> int:
        """Upper bound on CONSECUTIVE idle (timer-only) ticks any bounded
        schedule + policy can produce; past it the run is genuinely stuck."""
        p = self.policy
        span = max([b for _, b in self.faults.outages + self.faults.spikes],
                   default=0)
        return (p.admit_retry_limit + p.breaker_cooldown_ticks
                + (p.max_retries + 2) * (p.ack_timeout_ticks
                                         + p.backoff_cap_ticks) + span + 64)

    def _l_admit_limit(self, cur: int) -> Optional[int]:
        """How many escalations L admission may take this tick: 0 while the
        L tier is paused (outage / spike / open breaker), 1 while half-open
        with no probe outstanding, unlimited when closed."""
        if self.faults.l_paused(cur):
            return 0
        state = self._breaker.state
        if state == CircuitBreaker.OPEN:
            return 0
        if state == CircuitBreaker.HALF_OPEN:
            return 0 if self._probe is not None else 1
        return None

    def _fault_tick(self, cur: int, l_queue: deque, results: Dict) -> None:
        """Advance the transport sim one tick: outage aborts first (busy L
        slots release their pages through ``KVPool.free`` — leak-free — and
        queued escalations fail), then arrivals / ack timeouts, then due
        resends (held while the breaker is open)."""
        link = self._link
        if self.faults.in_outage(cur):
            for slot in range(self.lrt.num_slots):
                if self.lrt.slot_req[slot] is not None:
                    rec = self.lrt.release(slot)
                    if self.tel is not None:
                        self.tel.req_l_release(rec.adm.request.request_id,
                                               "outage_abort")
                    self._esc_failed(
                        self._esc_meta[rec.adm.request.request_id], cur,
                        results)
            while l_queue:
                adm = l_queue.popleft()
                self._esc_failed(self._esc_meta[adm.request.request_id],
                                 cur, results)
        arrived, failed = link.step(cur)
        for esc in arrived:
            if self.tel is not None:
                self.tel.req_esc_end(esc.rid, "arrived")
            l_queue.append(esc.adm)
        for esc in failed:
            self._esc_failed(esc, cur, results)
        if self._breaker.state != CircuitBreaker.OPEN:
            for esc in link.due_resends(cur):
                link.take(esc)
                if self._budget_expired(esc.adm):
                    self._degrade(esc, cur, results)  # too late to retry
                else:
                    link.send(esc, cur)
                    if self.tel is not None:
                        self.tel.req_esc_send(esc.rid, -1, esc.attempt)

    @staticmethod
    def _budget_expired(adm: AdmittedRequest) -> bool:
        budget = adm.request.latency_budget
        return (budget is not None
                and time.monotonic() - adm.submit_time > budget)

    def _esc_failed(self, esc, cur: int, results: Dict) -> None:
        """One escalation attempt failed (lost, timed out, or outage-
        aborted): count it against the breaker, then retry with capped
        exponential backoff — or give up when retries are exhausted or the
        latency budget says the answer would arrive too late."""
        self._breaker.record_failure(cur)
        if self.tel is not None:
            self.tel.req_esc_end(esc.rid, "failed")
        if self._probe == esc.rid:
            self._probe = None
        if esc.attempt >= self.policy.max_retries \
                or self._budget_expired(esc.adm):
            self._degrade(esc, cur, results)
        else:
            self._link.schedule_retry(esc, cur)
            self.counters.esc_retries += 1

    def _degrade(self, esc, cur: int, results: Dict) -> None:
        """Give up on the escalation: the S-tier answer (already recorded)
        stands, flagged ``status='degraded_local'``."""
        self._esc_meta.pop(esc.rid, None)
        self.counters.degraded_local += 1
        rec = results[esc.rid]
        rec["status"] = "degraded_local"
        rec["escalation_retries"] = esc.attempt
        rec["queue_wait_ticks"] = max(cur - esc.created_tick, 0)
        if self.tel is not None:
            self.tel.req_terminal(esc.rid, rec)

    def _l_give_up(self, adm: AdmittedRequest, cur: int,
                   results: Dict) -> None:
        """L-tier admission starvation past the retry cap: the S answer
        exists, so degrade rather than reject."""
        esc = self._esc_meta.get(adm.request.request_id)
        if esc is not None:
            self._degrade(esc, cur, results)

    def _reject(self, adm: AdmittedRequest, results: Dict) -> None:
        """Bounded admission backpressure: after ``admit_retry_limit``
        fruitless ticks the request fails outright with
        ``status='rejected'`` — the bounded replacement for the old
        "scheduler stalled" RuntimeError, which an unsatisfiable page demand
        (prompt larger than the whole pool) used to hit."""
        self.counters.requests += 1
        self.counters.rejected += 1
        warnings.warn(
            f"request {adm.request.request_id} rejected: admission failed "
            f"{adm.admit_retries} ticks running (bucket {adm.bucket} needs "
            "more free pages than the pool can produce) — raise num_pages / "
            "num_slots or shrink the prompt", RuntimeWarning, stacklevel=3)
        results[adm.request.request_id] = {
            "tokens": np.zeros((0,), np.int32),
            "s_tokens": np.zeros((0,), np.int32),
            "confidence": 0.0,
            "offloaded": False,
            "served_remote": False,
            "dropped": False,
            "status": "rejected",
            "escalation_retries": 0,
            "queue_wait_ticks": 0,
            "esc_created_tick": -1,
            "ttft": float("nan"),
        }
        if self.tel is not None:
            self.tel.req_terminal(adm.request.request_id,
                                  results[adm.request.request_id])

    # -- admission / completion -------------------------------------------

    def _try_admit(self, rt: _TierRuntime, queue, limit: Optional[int] = None,
                   on_give_up=None, ready=None) -> None:
        """Admit up to ``admit_width`` queued requests into free slots.
        ``queue`` is the AdmissionQueue (S tier) or the escalation deque
        (L tier); both speak the same popleft/appendleft head interface.
        ``limit`` caps this tick's admissions (0 = the L tier is paused —
        outage / spike / open breaker; 1 = the half-open probe).  ``ready``
        (mesh mode) gates on the staging pipeline: admission stops at the
        first head entry whose tokens are not yet readable from the
        transfer buffer.  A request that keeps failing admission hands off
        to ``on_give_up`` after ``RetryPolicy.admit_retry_limit`` fruitless
        ticks instead of re-queueing forever (bounded backpressure)."""
        rt.admitted = []
        rt.plans = []
        if limit == 0:
            return
        tick = self.counters.ticks
        cap = rt.admit_width if limit is None else min(rt.admit_width, limit)
        admitted = 0
        while admitted < cap and len(queue):
            if rt.free_slot() is None:
                break
            if ready is not None and not ready(queue[0]):
                break
            adm = queue.popleft()
            steps = min(adm.request.max_new_tokens, self.max_new_tokens)
            slot = rt.admit(adm, steps, self.decode_block, tick)
            if slot is None:
                adm.admit_retries += 1
                if on_give_up is not None and \
                        adm.admit_retries > self.policy.admit_retry_limit:
                    on_give_up(adm)     # head cleared: try the next request
                    continue
                queue.appendleft(adm)   # no pages this tick: retry next tick
                break
            if self.tel is not None:
                self.tel.req_admitted(rt.name, slot, adm.request.request_id,
                                      adm.submit_time,
                                      chunked=bool(rt.chunk_left[slot]))
            admitted += 1

    def _drop_expired(self, l_queue: deque, results: Dict,
                      cur: int = 0) -> None:
        """arXiv:2112.11413 drop policy: an escalation whose request has
        outlived its latency budget is dropped from the L queue — the S-tier
        answer (already recorded) stands, flagged ``dropped``.  Nothing else
        to release: the L-tier prefix lookup and page claim both happen at
        ADMISSION (``rt.admit``), so a QUEUED escalation holds no L-side
        resources — the drop touches the record and counters only
        (tests/test_faults.py asserts pool invariants under repeated
        drops)."""
        if not l_queue:
            return
        now = time.monotonic()
        kept: List[AdmittedRequest] = []
        while l_queue:
            adm = l_queue.popleft()
            budget = adm.request.latency_budget
            if budget is not None and now - adm.submit_time > budget:
                self.counters.dropped += 1
                esc = self._esc_meta.pop(adm.request.request_id, None)
                rec = results.get(adm.request.request_id)
                if rec is not None:
                    rec["dropped"] = True
                    rec["status"] = "dropped"
                    if esc is not None:
                        rec["escalation_retries"] = esc.attempt
                        rec["queue_wait_ticks"] = max(
                            cur - esc.created_tick, 0)
                    if self.tel is not None:
                        self.tel.req_terminal(adm.request.request_id, rec)
            else:
                kept.append(adm)
        l_queue.extend(kept)

    def _absorb_chunk(self, rt: _TierRuntime, out) -> None:
        """Advance the tick's scheduled chunk-prefill rows; finishing rows
        emit their chunk-sampled token 0."""
        for row, (slot, keep, fin) in enumerate(rt.chunk_sched):
            rt.chunk_fed[slot] += keep
            rt.chunk_left[slot] -= keep
            if self.tel is not None:
                self.tel.req_chunk(rt.name, slot,
                                   rt.slot_req[slot].adm.request.request_id,
                                   fed=keep, keep=int(rt.chunk_left[slot]))
            if fin:
                rec = rt.slot_req[slot]
                if self.aud is not None and not rec.done:
                    self.aud.decision(
                        rid=rec.adm.request.request_id, tier=rt.name,
                        tclass=rec.adm.request.tclass, kind="chunk",
                        conf=float(out["chunk_conf"][row]),
                        theta=self._eff_theta)
                rec.emit(out["chunk_tok"][row], out["chunk_conf"][row])

    def _absorb(self, rt: _TierRuntime, out: Dict[str, np.ndarray],
                finish) -> None:
        aud = self.aud
        for row, slot in enumerate(rt.admitted):
            rec = rt.slot_req[slot]
            if aud is not None and not rec.done:
                aud.decision(rid=rec.adm.request.request_id, tier=rt.name,
                             tclass=rec.adm.request.tclass, kind="admit",
                             conf=float(out["admit_conf"][row]),
                             theta=self._eff_theta)
            rec.emit(out["admit_tok"][row], out["admit_conf"][row])
        if self.chunk:
            self._absorb_chunk(rt, out)
        k_steps = out["toks"].shape[0]
        for slot in range(rt.num_slots):
            rec = rt.slot_req[slot]
            if rec is None:
                continue
            if self.chunk and rt.chunk_left[slot] > 0:
                continue               # still chunk-prefilling: no decode
            for k in range(k_steps):
                if aud is not None and not rec.done:
                    aud.decision(rid=rec.adm.request.request_id,
                                 tier=rt.name,
                                 tclass=rec.adm.request.tclass,
                                 kind="decode",
                                 conf=float(out["confs"][k][slot]),
                                 theta=self._eff_theta)
                rec.emit(out["toks"][k][slot], out["confs"][k][slot])
            rt.last_tok[slot] = int(out["toks"][k_steps - 1][slot])
            rt.tok_idx[slot] += k_steps
            rt.pos[slot] += k_steps
            if self.tel is not None:
                self.tel.req_decode(rt.name, slot,
                                    rec.adm.request.request_id, k_steps)
            if rec.done:
                finish(rt.release(slot))

    def _finish_s(self, rec: _Active, theta: float, results: Dict) -> None:
        """S decode finished: record the local answer, and when the gate
        fires send the escalation across the (possibly faulty) ED↔ES link.
        ``offloaded`` records INTENT (``conf < theta`` with the REAL theta)
        even in fail-local mode — the degradation is visible in ``status``,
        not hidden by a rewritten gate decision."""
        conf = float(np.mean(np.asarray(rec.confs, np.float32)))
        rid = rec.adm.request.request_id
        self.counters.requests += 1
        if self.aud is not None:
            # the request-level escalation decision: REAL theta (intent
            # semantics, matching ``offloaded`` — fail-local degradation is
            # visible in ``status``, not a rewritten gate decision)
            self.aud.decision(rid=rid, tier="S",
                              tclass=rec.adm.request.tclass, kind="request",
                              conf=conf, theta=theta)
        results[rid] = {
            "tokens": np.asarray(rec.tokens, np.int32),
            "s_tokens": np.asarray(rec.tokens, np.int32),
            "confidence": conf,
            "offloaded": conf < theta,
            "served_remote": False,
            "dropped": False,
            "status": "ok",
            "escalation_retries": 0,
            "queue_wait_ticks": 0,
            "esc_created_tick": -1,      # -1 = never escalated
            "ttft": rec.ttft,
        }
        if conf >= theta:
            if self.tel is not None:      # never escalates: final status
                self.tel.req_terminal(rid, results[rid])
            return
        self.counters.offloaded += 1
        cur = self.counters.ticks - self._tick0
        results[rid]["esc_created_tick"] = cur
        esc = Escalation(rec.adm, rid, cur)
        if self._breaker.state == CircuitBreaker.OPEN:
            # fail-local: the breaker is open, nothing crosses the link —
            # the request degrades immediately (no retries to burn)
            self._degrade(esc, cur, results)
            return
        rec.adm.admit_retries = 0   # L admission gets a fresh retry budget
        self._esc_meta[rid] = esc
        self._link.send(esc, cur)
        if self.tel is not None:
            self.tel.req_escalated(rid)
            self.tel.req_esc_send(rid, -1, esc.attempt)

    def _finish_l(self, rec: _Active, results: Dict) -> None:
        rid = rec.adm.request.request_id
        out = results[rid]
        out["tokens"] = np.asarray(rec.tokens, np.int32)
        out["served_remote"] = True
        out["status"] = "ok"
        if self.aud is not None:
            # plain-mode ground truth: one agreement sample per completed
            # escalation — did the S answer match what L produced?
            st, lt = out["s_tokens"], out["tokens"]
            m = min(len(st), len(lt))
            ok = m > 0 and bool(np.array_equal(st[:m], lt[:m]))
            self.aud.outcome(rid=rid, tier="L",
                             tclass=rec.adm.request.tclass,
                             conf=out["confidence"], theta=self._run_theta,
                             ok=ok, kind="l_agree")
        esc = self._esc_meta.pop(rid, None)
        if esc is not None:
            cur = self.counters.ticks - self._tick0
            out["escalation_retries"] = esc.attempt
            out["queue_wait_ticks"] = max(
                (esc.l_admit_tick if esc.l_admit_tick >= 0 else cur)
                - esc.created_tick, 0)
            self._breaker.record_success()
            if self._probe == rid:
                self._probe = None
        if self.tel is not None:
            self.tel.req_terminal(rid, out)
