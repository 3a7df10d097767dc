"""Uniform model API across all families.

Every family exposes:
  init_params(rng, cfg, dtype)                        -> params pytree
  forward(params, cfg, batch, remat)                  -> logits (B, S, V) fp32
  loss(params, cfg, batch, remat)                     -> (scalar, metrics)
  init_cache(cfg, batch, seq_len)                     -> decode cache pytree
  decode_step(params, cfg, token, cache)              -> (logits (B, V), cache)

``batch`` is a dict: tokens (B, S) int32, labels (B, S) int32, and the
modality-stub inputs where applicable: frames (B, F, D) for encdec,
patches (B, P, D) for vlm.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (DENSE, ENCDEC, HYBRID, MOE, SSM, VLM,
                                ModelConfig)
from repro.models import hybrid, mamba2, moe, transformer, whisper

Params = Dict[str, Any]


def init_params(rng, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    if cfg.family in (DENSE, VLM):
        return transformer.init_decoder(rng, cfg, dtype)
    if cfg.family == MOE:
        return moe.init_decoder(rng, cfg, dtype)
    if cfg.family == SSM:
        return mamba2.init_model(rng, cfg, dtype)
    if cfg.family == HYBRID:
        return hybrid.init_model(rng, cfg, dtype)
    if cfg.family == ENCDEC:
        return whisper.init_model(rng, cfg, dtype)
    raise ValueError(cfg.family)


def init_params_spec(cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    """ShapeDtypeStruct pytree of the params — no allocation (dry-run)."""
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(lambda r: init_params(r, cfg, dtype), rng)


def forward(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray], *,
            remat: bool = False, use_kernel: bool = False,
            last_only: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits, aux_loss) — aux_loss is 0 for non-MoE families."""
    zero = jnp.zeros((), jnp.float32)
    if cfg.family == DENSE:
        return transformer.forward(params, cfg, batch["tokens"], remat=remat,
                                   last_only=last_only), zero
    if cfg.family == VLM:
        return transformer.forward(params, cfg, batch["tokens"], remat=remat,
                                   last_only=last_only,
                                   patch_embeds=batch["patches"]), zero
    if cfg.family == MOE:
        return moe.forward(params, cfg, batch["tokens"], remat=remat,
                           last_only=last_only)
    if cfg.family == SSM:
        return mamba2.forward(params, cfg, batch["tokens"], remat=remat,
                              use_kernel=use_kernel, last_only=last_only), zero
    if cfg.family == HYBRID:
        return hybrid.forward(params, cfg, batch["tokens"], remat=remat,
                              use_kernel=use_kernel, last_only=last_only), zero
    if cfg.family == ENCDEC:
        return whisper.forward(params, cfg, batch["frames"], batch["tokens"],
                               remat=remat, last_only=last_only), zero
    raise ValueError(cfg.family)


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """logits (B, S, V) fp32, labels (B, S) -> mean NLL."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def loss(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray], *,
         remat: bool = False, use_kernel: bool = False
         ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    logits, aux = forward(params, cfg, batch, remat=remat, use_kernel=use_kernel)
    labels = batch["labels"]
    if cfg.family == VLM:
        # logits cover [patches | text]; loss only on the text positions
        logits = logits[:, -labels.shape[1]:, :]
    nll = cross_entropy(logits, labels)
    total = nll + cfg.router_aux_coef * aux
    return total, {"nll": nll, "aux": aux}


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=jnp.bfloat16):
    if cfg.family in (DENSE, VLM):
        return transformer.init_cache(cfg, batch, seq_len, dtype)
    if cfg.family == MOE:
        return moe.init_cache(cfg, batch, seq_len, dtype)
    if cfg.family == SSM:
        return mamba2.init_cache(cfg, batch, seq_len, dtype)
    if cfg.family == HYBRID:
        return hybrid.init_cache(cfg, batch, seq_len, dtype)
    if cfg.family == ENCDEC:
        return whisper.init_cache(cfg, batch, seq_len, dtype)
    raise ValueError(cfg.family)


def cache_spec(cfg: ModelConfig, batch: int, seq_len: int, dtype=jnp.bfloat16):
    return jax.eval_shape(lambda: init_cache(cfg, batch, seq_len, dtype))


def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray, cache, *,
            use_kernel: bool = False, patch_embeds=None):
    """Batched prompt ingestion: ONE forward-style pass over the whole (B, S)
    prompt that writes the decode cache — replacing O(S) sequential
    ``decode_step`` dispatches.  ``cache`` (from :func:`init_cache`) supplies
    the buffers and is fully overwritten, so callers may donate it.

    Returns (last-token logits (B, V) fp32, filled cache).
    """
    if cfg.family in (DENSE, VLM):
        return transformer.prefill(params, cfg, tokens, cache,
                                   patch_embeds=patch_embeds)
    if cfg.family == MOE:
        return moe.prefill(params, cfg, tokens, cache)
    if cfg.family == SSM:
        return mamba2.prefill(params, cfg, tokens, cache, use_kernel=use_kernel)
    if cfg.family == HYBRID:
        return hybrid.prefill(params, cfg, tokens, cache, use_kernel=use_kernel)
    raise ValueError(f"prefill not supported for family {cfg.family!r}")


# ---------------------------------------------------------------------------
# paged cache API (continuous batching over a shared page pool)
# ---------------------------------------------------------------------------
#
# ``init_paged_cache`` allocates ONE device buffer set per tier: attention
# K/V lives in a (…, num_pages, page_size, K·Dh) physical page pool indexed
# by the scheduler's int32 block table (physical page 0 is the null page);
# recurrent SSM state is per-slot (constant-size — nothing to page).
# ``prefill_paged`` admits ONE right-padded request into a slot at a FIXED
# (1, S_max) shape (logits read at ``length - 1``), and ``decode_step_paged``
# steps ALL slots at per-slot positions (reading K/V for the listed ones) —
# together they are shape-independent of the prompt bucket, which is what
# lets the continuous scheduler serve every bucket from a single compiled
# executable.

PAGED_FAMILIES = (DENSE, VLM, MOE, SSM, HYBRID)


def init_paged_cache(cfg: ModelConfig, num_slots: int, num_pages: int,
                     page_size: int, dtype=jnp.bfloat16):
    if cfg.family in (DENSE, VLM):
        return transformer.init_paged_cache(cfg, num_slots, num_pages,
                                            page_size, dtype)
    if cfg.family == MOE:
        return moe.init_paged_cache(cfg, num_slots, num_pages, page_size,
                                    dtype)
    if cfg.family == SSM:
        return mamba2.init_paged_cache(cfg, num_slots, num_pages, page_size,
                                       dtype)
    if cfg.family == HYBRID:
        return hybrid.init_paged_cache(cfg, num_slots, num_pages, page_size,
                                       dtype)
    raise ValueError(f"paged cache not supported for family {cfg.family!r}")


def prefill_paged(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  lengths: jnp.ndarray, slots: jnp.ndarray,
                  block_rows: jnp.ndarray, cache, *,
                  use_kernel: bool = False, start=None):
    """Admit a BATCH of requests in one pass: tokens (A, S_max) right-padded
    with true lengths (A,), into decode slots ``slots`` (A,) whose
    block-table rows are ``block_rows`` (A, n_pages).  Padded admission rows
    use an out-of-range slot + null-page rows, so their writes drop.

    ``start`` (A,) enables PARTIAL prefill at a page-aligned offset: each
    row's positions < start[i] are served from its aliased (prefix-shared)
    pages — the attention families splice the cached K/V under the in-pass
    values and redirect the prefix page writes to the null page; the
    recurrent families ignore it (their sharing is the whole-prompt
    snapshot/restore path).  Returns (per-row last-prompt-position logits
    (A, V) fp32, cache)."""
    if cfg.family in (DENSE, VLM):
        return transformer.prefill_paged(params, cfg, tokens, lengths, slots,
                                         block_rows, cache, start=start)
    if cfg.family == MOE:
        return moe.prefill_paged(params, cfg, tokens, lengths, slots,
                                 block_rows, cache, start=start)
    if cfg.family == SSM:
        return mamba2.prefill_paged(params, cfg, tokens, lengths, slots,
                                    block_rows, cache, use_kernel=use_kernel,
                                    start=start)
    if cfg.family == HYBRID:
        return hybrid.prefill_paged(params, cfg, tokens, lengths, slots,
                                    block_rows, cache, use_kernel=use_kernel,
                                    start=start)
    raise ValueError(f"prefill_paged not supported for family {cfg.family!r}")


def decode_step_paged(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                      pos: jnp.ndarray, block: jnp.ndarray, cache, *,
                      use_kernel: bool = False, write_block=None,
                      rows=None):
    """One decode step for every slot at per-slot positions.  token (B, 1);
    pos (B,); block (B, n_pages) block table; write_block masks shared
    (read-only) pages out of the append path.  ``rows`` = (slots, branch,
    widths) narrows each attention layer's page gather and attention to
    the listed slots — the scheduler's decode lane passes its occupied
    slots, read at the narrowest width of its ladder (see
    ``layers.over_rows``); ``None`` reads every slot.  Returns (logits,
    cache)."""
    if cfg.family in (DENSE, VLM):
        return transformer.decode_step_paged(params, cfg, token, pos, block,
                                             cache, use_kernel=use_kernel,
                                             write_block=write_block,
                                             rows=rows)
    if cfg.family == MOE:
        return moe.decode_step_paged(params, cfg, token, pos, block, cache,
                                     use_kernel=use_kernel,
                                     write_block=write_block, rows=rows)
    if cfg.family == SSM:
        return mamba2.decode_step_paged(params, cfg, token, pos, block, cache,
                                        write_block=write_block)
    if cfg.family == HYBRID:
        return hybrid.decode_step_paged(params, cfg, token, pos, block, cache,
                                        use_kernel=use_kernel,
                                        write_block=write_block, rows=rows)
    raise ValueError(
        f"decode_step_paged not supported for family {cfg.family!r}")


# ---------------------------------------------------------------------------
# chunked token lane (unified multi-token paged pass)
# ---------------------------------------------------------------------------
#
# ``forward_chunk_paged`` is the generalisation that subsumes the other two
# paged entry points: a (B, C) block of tokens for ALL slots at per-slot
# start positions, K/V written through the block table, per-position logits
# back.  ``decode_step_paged`` is the C = 1 special case; ``prefill_paged``
# is the everything-at-once special case (kept as the batched admission
# fast path).  The scheduler's chunked-prefill admission feeds long prompts
# through this lane C tokens per tick.
#
# Boundary contract: the attention families' chunk state is positional (a
# partial final chunk's padding sits past the host position, shadowed by
# the mask), so their ``staged`` is empty; the recurrent families emit
# per-step chunk-boundary snapshots in ``staged`` and the scheduler commits
# the chunk's live token count with ``select_stage`` + ``restore_stage``.


def forward_chunk_paged(params: Params, cfg: ModelConfig,
                        tokens: jnp.ndarray, pos: jnp.ndarray,
                        block: jnp.ndarray, cache, *,
                        use_kernel: bool = False, write_block=None):
    """One multi-token paged pass: tokens (B, C) at per-slot start positions
    ``pos`` (B,) — token i of slot b lands at position ``pos[b] + i``.
    Greedy outputs are token-identical to C sequential ``decode_step_paged``
    calls — bitwise for the recurrent families, whose chunk IS a scan of the
    per-token step (tests/test_chunk_lane.py asserts it per family).
    Returns (logits (B, C, V) fp32, cache, staged)."""
    if cfg.family in (DENSE, VLM):
        return transformer.forward_chunk_paged(params, cfg, tokens, pos,
                                               block, cache,
                                               use_kernel=use_kernel,
                                               write_block=write_block)
    if cfg.family == MOE:
        return moe.forward_chunk_paged(params, cfg, tokens, pos, block,
                                       cache, use_kernel=use_kernel,
                                       write_block=write_block)
    if cfg.family == SSM:
        return mamba2.forward_chunk_paged(params, cfg, tokens, pos, block,
                                          cache, use_kernel=use_kernel,
                                          write_block=write_block)
    if cfg.family == HYBRID:
        return hybrid.forward_chunk_paged(params, cfg, tokens, pos, block,
                                          cache, use_kernel=use_kernel,
                                          write_block=write_block)
    raise ValueError(
        f"forward_chunk_paged not supported for family {cfg.family!r}")


def gather_chunk_slots(cfg: ModelConfig, cache, slots: jnp.ndarray):
    """A W-row view of the cache for the scheduler's chunk-prefill lane:
    the lane runs ``forward_chunk_paged`` over only the W slots actually
    mid-prefill (W << num_slots), not the whole slot table.  Attention state
    lives in the SHARED page pool (routed by the lane's gathered block rows),
    so the attention families pass the cache through; the recurrent families
    gather their per-slot state rows (sentinel rows gather-clamp harmlessly —
    their writes drop on the scatter side)."""
    if cfg.family in (SSM, HYBRID):
        from repro.models import layers as L
        return {"state": cache["state"][:, slots],
                "conv": cache["conv"][:, slots], **L.paged_kv(cache)}
    return cache


def scatter_chunk_slots(cfg: ModelConfig, cache, mini, stage_sel,
                        slots: jnp.ndarray):
    """Merge a W-row chunk pass back into the full cache: page pools pass
    through (the lane wrote them in place through its block rows); recurrent
    state scatters the SELECTED boundary snapshot (``select_stage`` over the
    lane's staged outputs — exactly ``chunk_keep`` inputs absorbed) at
    ``slots`` (sentinel == num_slots drops)."""
    if cfg.family in (SSM, HYBRID):
        from repro.models import layers as L
        return dict(cache, **L.paged_kv(mini),
                    state=cache["state"].at[:, slots].set(
                        stage_sel["state"], mode="drop"),
                    conv=cache["conv"].at[:, slots].set(
                        stage_sel["conv"], mode="drop"))
    return mini


def chunk_stage(cfg: ModelConfig, cache):
    """The rollback-able (recurrent) slice of a paged cache — {} for the
    attention families, whose chunk state is positional."""
    if cfg.family in (SSM, HYBRID):
        return mamba2.chunk_stage(cfg, cache)
    return {}


def restore_stage(cfg: ModelConfig, cache, stage, mask: jnp.ndarray):
    """Overwrite slots where ``mask`` (B,) is True with ``stage``'s recurrent
    state (no-op for the attention families)."""
    if cfg.family in (SSM, HYBRID):
        return mamba2.restore_stage(cfg, cache, stage, mask)
    return cache


def select_stage(cfg: ModelConfig, staged, keep: jnp.ndarray):
    """Per-slot chunk-boundary snapshot after exactly ``keep`` (B,) inputs
    (staged leaves carry a leading chunk axis; {} passes through)."""
    if cfg.family in (SSM, HYBRID):
        return mamba2.select_stage(cfg, staged, keep)
    return {}


# ---------------------------------------------------------------------------
# prefix cache (cross-request prompt reuse)
# ---------------------------------------------------------------------------
#
# The pool's prefix index retains prompt pages by content hash; the DEVICE
# side keeps a small row cache per tier holding whatever a full-prompt
# restore needs beyond the pages themselves: the last-prompt-position logits
# (every family — they seed tok0 + the confidence gate without re-running
# the admit lane) and the recurrent state + conv window at the prompt
# boundary (SSM/hybrid).  Rows are host-allocated (LRU) by kv_pool and
# scattered/gathered inside the one tick program — no extra dispatch, no
# extra host sync.


def init_prefix_cache(cfg: ModelConfig, entries: int, dtype=jnp.bfloat16):
    """Device-side prefix-cache rows: (E, V) fp32 last-position logits for
    every family, plus the families' own snapshot extras."""
    base = {"logits": jnp.zeros((entries, cfg.vocab_size), jnp.float32)}
    if cfg.family in (SSM, HYBRID):
        base.update(mamba2.init_prefix_cache(cfg, entries, dtype))
    elif cfg.family not in PAGED_FAMILIES:
        raise ValueError(
            f"prefix cache not supported for family {cfg.family!r}")
    return base


def snapshot_save(cfg: ModelConfig, cache, prefix, rows: jnp.ndarray,
                  slots: jnp.ndarray):
    """Scatter admitted slots' post-prefill recurrent state into prefix-cache
    rows (rows (A,), sentinel == entries drops).  No-op for the attention
    families — their prompt state IS the retained pages."""
    if cfg.family in (SSM, HYBRID):
        return mamba2.snapshot_save(cfg, cache, prefix, rows, slots)
    return prefix


def snapshot_restore(cfg: ModelConfig, cache, prefix, rows: jnp.ndarray,
                     slots: jnp.ndarray):
    """Scatter prefix-cache rows into restored decode slots (slots (A,),
    sentinel == num_slots drops)."""
    if cfg.family in (SSM, HYBRID):
        return mamba2.snapshot_restore(cfg, cache, prefix, rows, slots)
    return cache


def cow_pages(cfg: ModelConfig, cache, src: jnp.ndarray, dst: jnp.ndarray, *,
              use_kernel: bool = False):
    """Execute the tick's copy-on-write page duplications: dst pages become
    copies of src pages in every layer's pool.  Pairs are padded with (0, 0)
    — the null page copied onto itself.  SSM caches have no pages."""
    if cfg.family == SSM:
        return cache
    if cfg.family not in PAGED_FAMILIES:
        raise ValueError(f"cow_pages not supported for family {cfg.family!r}")
    from repro.models import layers as L
    if use_kernel:
        from repro.kernels import ops as kops
        out = dict(cache, kp=kops.copy_pages(cache["kp"], src, dst),
                   vp=kops.copy_pages(cache["vp"], src, dst))
        if "ks" in cache:
            # scale rows move with their pages: copy_pages is shape/dtype
            # generic, so the same scalar-prefetched kernel relocates the
            # (L|G, P, K) fp32 scale tensors
            out["ks"] = kops.copy_pages(cache["ks"], src, dst)
            out["vs"] = kops.copy_pages(cache["vs"], src, dst)
        return out
    out = dict(cache, kp=L.cow_copy_pages(cache["kp"], src, dst),
               vp=L.cow_copy_pages(cache["vp"], src, dst))
    if "ks" in cache:
        out["ks"] = L.cow_copy_scales(cache["ks"], src, dst)
        out["vs"] = L.cow_copy_scales(cache["vs"], src, dst)
    return out


def decode_step(params: Params, cfg: ModelConfig, token: jnp.ndarray, cache, *,
                use_kernel: bool = False):
    if cfg.family in (DENSE, VLM):
        return transformer.decode_step(params, cfg, token, cache,
                                       use_kernel=use_kernel)
    if cfg.family == MOE:
        return moe.decode_step(params, cfg, token, cache, use_kernel=use_kernel)
    if cfg.family == SSM:
        return mamba2.decode_step(params, cfg, token, cache)
    if cfg.family == HYBRID:
        return hybrid.decode_step(params, cfg, token, cache)
    if cfg.family == ENCDEC:
        return whisper.decode_step(params, cfg, token, cache)
    raise ValueError(cfg.family)
