"""Zamba2-style hybrid: Mamba-2 backbone + a SHARED attention block applied
every ``shared_attn_every`` layers [arXiv:2411.15242].

Structured as ``G = num_layers // shared_attn_every`` groups; each group is an
inner scan over its mamba layers followed by the shared attention block (one
set of weights, applied G times — Zamba2's parameter-sharing trick).  The
outer scan carries the per-group KV-cache slots for decode.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba2
from repro.sharding import act

Params = Dict[str, Any]


def _num_groups(cfg: ModelConfig) -> int:
    k = cfg.shared_attn_every
    if cfg.num_layers % k:
        raise ValueError(f"num_layers {cfg.num_layers} must divide by "
                         f"shared_attn_every {k}")
    return cfg.num_layers // k


def init_model(rng, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(rng, 5)
    layer_rngs = jax.random.split(ks[0], cfg.num_layers)
    stacked = jax.vmap(lambda r: mamba2.init_mamba_block(r, cfg, dtype))(layer_rngs)
    g, k = _num_groups(cfg), cfg.shared_attn_every
    # reshape the layer stack to (G, k, ...) for the nested scan
    stacked = jax.tree.map(lambda a: a.reshape(g, k, *a.shape[1:]), stacked)
    shared = {
        "ln1": L.rmsnorm_init(cfg.d_model, dtype),
        "attn": L.attention_init(ks[1], cfg.d_model, cfg.num_heads,
                                 cfg.num_kv_heads, cfg.resolved_head_dim,
                                 dtype=dtype),
        "ln2": L.rmsnorm_init(cfg.d_model, dtype),
        "mlp": L.swiglu_init(ks[2], cfg.d_model, cfg.d_ff, dtype),
    }
    return {
        "embed": L.embed_init(ks[3], cfg.vocab_size, cfg.d_model, dtype),
        "layers": stacked,
        "shared_attn": shared,
        "final_norm": L.rmsnorm_init(cfg.d_model, dtype),
        "lm_head": L.dense_init(ks[4], cfg.d_model, cfg.vocab_size, dtype),
    }


def _shared_block(sp: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    a = L.attention_forward(sp["attn"], L.rmsnorm(sp["ln1"], x, cfg.norm_eps),
                            num_heads=cfg.num_heads, num_kv=cfg.num_kv_heads,
                            head_dim=cfg.resolved_head_dim,
                            rope_theta=cfg.rope_theta)
    x = x + a
    return x + L.swiglu(sp["mlp"], L.rmsnorm(sp["ln2"], x, cfg.norm_eps))


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray, *,
            remat: bool = False, use_kernel: bool = False,
            last_only: bool = False) -> jnp.ndarray:
    h = params["embed"][tokens]
    sp = params["shared_attn"]

    def inner(carry, lp):
        x = act.shard_hidden(carry)
        y = mamba2.mamba_block(lp, cfg, L.rmsnorm(lp["ln"], x, cfg.norm_eps),
                               use_kernel=use_kernel)
        return x + y, None

    def outer(carry, group_params):
        x = carry
        x, _ = lax.scan(inner, x, group_params)
        x = _shared_block(sp, cfg, x)
        return act.shard_hidden(x), None

    if remat:
        outer = jax.checkpoint(outer, prevent_cse=False)
    h, _ = lax.scan(outer, act.shard_hidden(h), params["layers"])
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    if last_only:
        h = h[:, -1:]
    return act.shard_logits((h @ params["lm_head"]).astype(jnp.float32))


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=jnp.bfloat16):
    g = _num_groups(cfg)
    ssm = mamba2.init_cache(cfg, batch, seq_len, dtype)
    kv_shape = (g, batch, seq_len, cfg.num_kv_heads, cfg.resolved_head_dim)
    gk = _num_groups(cfg), cfg.shared_attn_every
    ssm_state = ssm["state"].reshape(gk[0], gk[1], *ssm["state"].shape[1:])
    ssm_conv = ssm["conv"].reshape(gk[0], gk[1], *ssm["conv"].shape[1:])
    return {
        "state": ssm_state, "conv": ssm_conv,
        "k": jnp.zeros(kv_shape, dtype), "v": jnp.zeros(kv_shape, dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def decode_step(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                cache: Params) -> Tuple[jnp.ndarray, Params]:
    h = params["embed"][token]
    sp = params["shared_attn"]
    pos = cache["pos"]

    def inner(carry, xs):
        x = carry
        lp, st, cw = xs
        y, st, cw = mamba2.mamba_block_step(
            lp, cfg, L.rmsnorm(lp["ln"], x, cfg.norm_eps), st, cw)
        return x + y, (st, cw)

    def outer(carry, xs):
        x = carry
        gp, st_g, cw_g, ck, cv = xs
        x, (st_g, cw_g) = lax.scan(inner, x, (gp, st_g, cw_g))
        a, ck, cv = L.attention_decode(sp["attn"],
                                       L.rmsnorm(sp["ln1"], x, cfg.norm_eps),
                                       ck, cv, pos,
                                       num_heads=cfg.num_heads,
                                       num_kv=cfg.num_kv_heads,
                                       head_dim=cfg.resolved_head_dim,
                                       rope_theta=cfg.rope_theta)
        x = x + a
        x = x + L.swiglu(sp["mlp"], L.rmsnorm(sp["ln2"], x, cfg.norm_eps))
        return x, (st_g, cw_g, ck, cv)

    h, (ns, ncw, nk, nv) = lax.scan(
        outer, h, (params["layers"], cache["state"], cache["conv"],
                   cache["k"], cache["v"]))
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h[:, 0, :] @ params["lm_head"]).astype(jnp.float32)
    return logits, {"state": ns, "conv": ncw, "k": nk, "v": nv, "pos": pos + 1}


# ---------------------------------------------------------------------------
# paged cache API (continuous batching)
# ---------------------------------------------------------------------------
#
# Mamba states are per-slot (constant-size, nothing to page); the shared
# attention block's KV is a paged pool with a per-GROUP leading axis,
# (G, P, page, K, Dh), indexed by the scheduler's single block table (one
# physical page holds one group's K/V for a page worth of positions).


def init_paged_cache(cfg: ModelConfig, num_slots: int, num_pages: int,
                     page_size: int, dtype=jnp.bfloat16):
    g, k = _num_groups(cfg), cfg.shared_attn_every
    h, p, n = cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state
    conv_dim = cfg.ssm_d_inner + 2 * n
    kv_shape = (g, num_pages, page_size, cfg.num_kv_heads,
                cfg.resolved_head_dim)
    quant = jnp.dtype(dtype) == jnp.int8
    # recurrent state stays full-precision under int8 KV quantization (it is
    # per-slot and constant-size — paging/quantizing it buys nothing)
    conv_dtype = jnp.bfloat16 if quant else dtype
    cache = {
        "state": jnp.zeros((g, k, num_slots, h, p, n), jnp.float32),
        "conv": jnp.zeros((g, k, num_slots, cfg.ssm_conv_width - 1, conv_dim),
                          conv_dtype),
        "kp": jnp.zeros(kv_shape, dtype), "vp": jnp.zeros(kv_shape, dtype),
    }
    if quant:
        sshape = (g, num_pages, cfg.num_kv_heads)
        cache["ks"] = jnp.zeros(sshape, jnp.float32)
        cache["vs"] = jnp.zeros(sshape, jnp.float32)
    return cache


def _prefill_outer(params: Params, cfg: ModelConfig, s: int, b: int,
                   kv_dtype, conv_dtype, use_kernel: bool, length, store_kv,
                   page: int = 0, quant: bool = False):
    """The per-group prefill scan body shared by :func:`prefill` (contiguous
    cache) and :func:`prefill_paged` (page pool).  ``store_kv(kv, k, v)``
    writes the group's shared-attention K/V into whichever layout the caller
    scans through; everything else is identical between the two paths.

    ``quant`` (int8 page pool): in-pass attention sees K/V fake-quantized
    through the per-page int8 grid while RAW values flow to ``store_kv``,
    whose quantize-on-write recomputes the identical scales."""
    sp = params["shared_attn"]
    hd = cfg.resolved_head_dim
    pos = jnp.arange(s)

    def inner(carry, lp):
        x = carry
        y, st, cw = mamba2.mamba_block_prefill(
            lp, cfg, L.rmsnorm(lp["ln"], x, cfg.norm_eps),
            use_kernel=use_kernel, conv_dtype=conv_dtype, length=length)
        return x + y, (st, cw)

    def outer(carry, xs):
        x = carry
        gp, kv = xs
        x, (st_g, cw_g) = lax.scan(inner, x, gp)
        xn = L.rmsnorm(sp["ln1"], x, cfg.norm_eps)
        q, k, v = L._qkv(sp["attn"], xn, cfg.num_heads, cfg.num_kv_heads, hd)
        if cfg.rope_theta > 0:
            q = L.apply_rope(q, pos, cfg.rope_theta)
            k = L.apply_rope(k, pos, cfg.rope_theta)
        if quant:
            k_raw, v_raw = k, v
            k = L.quant_dequant_pages(k, page)
            v = L.quant_dequant_pages(v, page)
        else:
            k = k.astype(kv_dtype)
            v = v.astype(kv_dtype)
        a = L._sdpa(q, k, v, L.causal_window_mask(s, s))
        x = x + a.reshape(b, s, cfg.num_heads * hd) @ sp["attn"]["wo"]
        x = x + L.swiglu(sp["mlp"], L.rmsnorm(sp["ln2"], x, cfg.norm_eps))
        stored = store_kv(kv, k_raw, v_raw) if quant else store_kv(kv, k, v)
        return act.shard_hidden(x), (st_g, cw_g, stored)

    return outer


def init_prefix_cache(cfg: ModelConfig, entries: int, dtype=jnp.bfloat16):
    """Full-prompt snapshot rows for the recurrent half of the hybrid: the
    per-group mamba states + conv windows at the prompt boundary.  The
    shared-attention K/V needs no snapshot — its prompt pages are retained
    by the pool's prefix index and aliased on restore."""
    g, k = _num_groups(cfg), cfg.shared_attn_every
    h, p, n = cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state
    conv_dim = cfg.ssm_d_inner + 2 * n
    if jnp.dtype(dtype) == jnp.int8:
        dtype = jnp.bfloat16
    return {
        "state": jnp.zeros((g, k, entries, h, p, n), jnp.float32),
        "conv": jnp.zeros((g, k, entries, cfg.ssm_conv_width - 1, conv_dim),
                          dtype),
    }


def snapshot_save(cfg: ModelConfig, cache: Params, prefix: Params,
                  rows: jnp.ndarray, slots: jnp.ndarray) -> Params:
    return dict(prefix,
                state=prefix["state"].at[:, :, rows].set(
                    cache["state"][:, :, slots], mode="drop"),
                conv=prefix["conv"].at[:, :, rows].set(
                    cache["conv"][:, :, slots], mode="drop"))


def snapshot_restore(cfg: ModelConfig, cache: Params, prefix: Params,
                     rows: jnp.ndarray, slots: jnp.ndarray) -> Params:
    return dict(cache,
                state=cache["state"].at[:, :, slots].set(
                    prefix["state"][:, :, rows], mode="drop"),
                conv=cache["conv"].at[:, :, slots].set(
                    prefix["conv"][:, :, rows], mode="drop"))


def prefill_paged(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  lengths: jnp.ndarray, slots: jnp.ndarray,
                  block_rows: jnp.ndarray, cache: Params, *,
                  use_kernel: bool = False,
                  start=None) -> Tuple[jnp.ndarray, Params]:
    """Prefill a batch of admitted requests: per-group SSM states/conv
    windows land in slots ``slots``; shared-attention K/V lands in each
    slot's pages.  The group math is EXACTLY :func:`prefill`'s (shared
    ``_prefill_outer``); only the K/V store differs.

    ``start``: the hybrid family shares prefixes only at whole-prompt
    granularity (the mamba state has no mid-prompt snapshot), so per row
    ``start`` is 0 (miss: full prefill) or bucket (restore: every page write
    redirected to the null page — the aliased prompt pages are read-only)."""
    h = params["embed"][tokens]
    b, s, _ = h.shape
    page = cache["kp"].shape[2]
    npg = s // page
    quant = "ks" in cache
    wrows = (block_rows[:, :npg] if start is None
             else L.suffix_write_rows(block_rows, start, npg, page))

    if quant:
        def store_kv(kv, k, v):
            pk, pv, sk, sv = kv
            pk, sk = L.quant_scatter_prefill_pages(pk, sk, k, wrows)
            pv, sv = L.quant_scatter_prefill_pages(pv, sv, v, wrows)
            return (pk, pv, sk, sv)
        kv0 = (cache["kp"], cache["vp"], cache["ks"], cache["vs"])
    else:
        def store_kv(kv, k, v):
            pk, pv = kv
            return (L.scatter_prefill_pages(pk, k, wrows),
                    L.scatter_prefill_pages(pv, v, wrows))
        kv0 = (cache["kp"], cache["vp"])

    outer = _prefill_outer(params, cfg, s, b, cache["kp"].dtype,
                           cache["conv"].dtype, use_kernel, lengths, store_kv,
                           page=page, quant=quant)
    h, (ns, ncw, nkv) = lax.scan(
        outer, act.shard_hidden(h), (params["layers"], kv0))
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    h = jnp.take_along_axis(h, (lengths - 1)[:, None, None], axis=1)
    logits = (h[:, 0, :] @ params["lm_head"]).astype(jnp.float32)
    new_cache = {
        "state": cache["state"].at[:, :, slots].set(ns, mode="drop"),
        "conv": cache["conv"].at[:, :, slots].set(ncw, mode="drop"),
        "kp": nkv[0], "vp": nkv[1],
    }
    if quant:
        new_cache["ks"], new_cache["vs"] = nkv[2], nkv[3]
    return logits, new_cache


def decode_step_paged(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                      pos: jnp.ndarray, block: jnp.ndarray, cache: Params, *,
                      use_kernel: bool = False,
                      write_block=None) -> Tuple[jnp.ndarray, Params]:
    """One decode step for all slots at per-slot positions."""
    h = params["embed"][token]
    sp = params["shared_attn"]

    def inner(carry, xs):
        x = carry
        lp, st, cw = xs
        y, st, cw = mamba2.mamba_block_step(
            lp, cfg, L.rmsnorm(lp["ln"], x, cfg.norm_eps), st, cw)
        return x + y, (st, cw)

    def outer(carry, xs):
        x, kv = carry
        gp, st_g, cw_g, gi = xs
        x, (st_g, cw_g) = lax.scan(inner, x, (gp, st_g, cw_g))
        a, kv = L.attention_decode_paged(
            sp["attn"], L.rmsnorm(sp["ln1"], x, cfg.norm_eps), kv, gi,
            block, pos, num_heads=cfg.num_heads, num_kv=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            use_kernel=use_kernel, write_block=write_block)
        x = x + a
        x = x + L.swiglu(sp["mlp"], L.rmsnorm(sp["ln2"], x, cfg.norm_eps))
        return (x, kv), (st_g, cw_g)

    # the shared block's stacked KV pools ride in the carry (updated in
    # place); the per-slot recurrent state is sliced in and stacked out
    (h, kv), (ns, ncw) = lax.scan(
        outer, (h, L.paged_kv(cache)),
        (params["layers"], cache["state"], cache["conv"],
         jnp.arange(cache["kp"].shape[0])))
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h[:, 0, :] @ params["lm_head"]).astype(jnp.float32)
    return logits, {"state": ns, "conv": ncw, **kv}


def forward_chunk_paged(params: Params, cfg: ModelConfig,
                        tokens: jnp.ndarray, pos: jnp.ndarray,
                        block: jnp.ndarray, cache: Params, *,
                        use_kernel: bool = False,
                        write_block=None) -> Tuple[jnp.ndarray, Params, dict]:
    """Chunked token lane for the hybrid: a ``lax.scan`` of per-token steps
    (bitwise identical to C sequential ``decode_step_paged`` calls — the
    mamba half is inherently sequential) emitting per-step {state, conv}
    CHUNK-BOUNDARY SNAPSHOTS so a chunk can be rolled back to any intra-chunk
    position; the shared-attention K/V lands in the pages positionally (a
    rollback just rewinds the host position).  Token i of slot b writes at
    ``pos[b] + i`` through ``write_block``.  Returns (logits (B, C, V) fp32,
    cache, staged)."""

    def step(carry, xs):
        cache = carry
        tok, j = xs
        logits, cache = decode_step_paged(params, cfg, tok[:, None], pos + j,
                                          block, cache, use_kernel=use_kernel,
                                          write_block=write_block)
        return cache, (logits, {"state": cache["state"],
                                "conv": cache["conv"]})

    c = tokens.shape[1]
    cache, (logits, staged) = lax.scan(step, cache,
                                       (tokens.T, jnp.arange(c)))
    return logits.transpose(1, 0, 2), cache, staged


def chunk_stage(cfg: ModelConfig, cache: Params) -> dict:
    """Rollback-able recurrent slice (slot axis 2: leaves (G, K, B, ...))."""
    return {"state": cache["state"], "conv": cache["conv"]}


def restore_stage(cfg: ModelConfig, cache: Params, stage: dict,
                  mask: jnp.ndarray) -> Params:
    return dict(cache,
                state=jnp.where(mask[None, None, :, None, None, None],
                                stage["state"], cache["state"]),
                conv=jnp.where(mask[None, None, :, None, None],
                               stage["conv"], cache["conv"]))


def select_stage(cfg: ModelConfig, staged: dict, keep: jnp.ndarray) -> dict:
    """staged leaves (C, G, K, B, ...) -> snapshot after ``keep`` inputs."""
    idx = jnp.maximum(keep - 1, 0)

    def sel(a):
        i = idx.reshape((1, 1, 1, -1) + (1,) * (a.ndim - 4))
        return jnp.take_along_axis(a, i, axis=0)[0]

    return {"state": sel(staged["state"]), "conv": sel(staged["conv"])}


def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            cache: Params, *, use_kernel: bool = False
            ) -> Tuple[jnp.ndarray, Params]:
    """Consume the whole (B, S) prompt in one batched pass, writing the SSM
    states, conv windows, and the per-group shared-attention KV slots.
    ``cache`` supplies the buffers and is overwritten (donation-safe).

    Returns (last-token logits (B, V) fp32, filled cache).
    """
    h = params["embed"][tokens]
    b, s, _ = h.shape

    def store_kv(kv, k, v):
        ck, cv = kv
        return (lax.dynamic_update_slice(ck, k, (0, 0, 0, 0)),
                lax.dynamic_update_slice(cv, v, (0, 0, 0, 0)))

    outer = _prefill_outer(params, cfg, s, b, cache["k"].dtype,
                           cache["conv"].dtype, use_kernel, None, store_kv)
    h, (ns, ncw, (nk, nv)) = lax.scan(
        outer, act.shard_hidden(h), (params["layers"],
                                     (cache["k"], cache["v"])))
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h[:, -1, :] @ params["lm_head"]).astype(jnp.float32)
    return logits, {"state": ns, "conv": ncw, "k": nk, "v": nv,
                    "pos": jnp.asarray(s, jnp.int32)}
