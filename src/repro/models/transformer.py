"""Dense decoder-only transformer (granite / gemma3 / qwen2 / danube / llava LM).

Scanned over layers: the HLO contains exactly one layer body regardless of
depth, which keeps 512-device dry-run compiles tractable on one CPU core.

Per-layer heterogeneity (gemma3's 5:1 local:global attention) is expressed as
a scanned ``window`` vector — the sliding-window size enters the mask as data,
so a single uniform body covers both layer kinds with no ``lax.cond``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.sharding import act

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(rng, cfg: ModelConfig, dtype) -> Params:
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model, dtype),
        "attn": L.attention_init(k1, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                                 cfg.resolved_head_dim, bias=cfg.qkv_bias,
                                 dtype=dtype),
        "ln2": L.rmsnorm_init(cfg.d_model, dtype),
        "mlp": L.swiglu_init(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def init_decoder(rng, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(rng, 3)
    layer_rngs = jax.random.split(ks[0], cfg.num_layers)
    stacked = jax.vmap(lambda r: _init_layer(r, cfg, dtype))(layer_rngs)
    return {
        "embed": L.embed_init(ks[1], cfg.vocab_size, cfg.d_model, dtype),
        "layers": stacked,
        "final_norm": L.rmsnorm_init(cfg.d_model, dtype),
        "lm_head": L.dense_init(ks[2], cfg.d_model, cfg.vocab_size, dtype),
    }


def layer_windows(cfg: ModelConfig, seq_len: int) -> jnp.ndarray:
    """Per-layer sliding-window sizes; ``seq_len`` means full causal."""
    full = jnp.full((cfg.num_layers,), seq_len, dtype=jnp.int32)
    if cfg.local_global_ratio:
        idx = jnp.arange(cfg.num_layers)
        is_global = (idx % (cfg.local_global_ratio + 1)) == cfg.local_global_ratio
        return jnp.where(is_global, seq_len, cfg.sliding_window).astype(jnp.int32)
    if cfg.sliding_window:
        return jnp.full((cfg.num_layers,), cfg.sliding_window, dtype=jnp.int32)
    return full


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def forward_hidden(params: Params, cfg: ModelConfig, h: jnp.ndarray, *,
                   remat: bool = False,
                   positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Run the scanned layer stack over already-embedded hidden states."""
    seq = h.shape[1]
    windows = layer_windows(cfg, seq)

    def body(carry, xs):
        lp, win = xs
        x = act.shard_hidden(carry)
        a = L.attention_forward(lp["attn"], L.rmsnorm(lp["ln1"], x, cfg.norm_eps),
                                num_heads=cfg.num_heads, num_kv=cfg.num_kv_heads,
                                head_dim=cfg.resolved_head_dim,
                                rope_theta=cfg.rope_theta, window=win,
                                positions=positions)
        x = x + a
        m = L.swiglu(lp["mlp"], L.rmsnorm(lp["ln2"], x, cfg.norm_eps))
        return act.shard_hidden(x + m), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    h, _ = lax.scan(body, act.shard_hidden(h), (params["layers"], windows))
    return h


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray, *,
            remat: bool = False, last_only: bool = False,
            patch_embeds: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """tokens: (B, S) -> logits (B, S_total, V).

    For VLM configs, ``patch_embeds`` (B, P, D) is prepended to the token
    embeddings (the stubbed vision tower's output).  ``last_only`` slices the
    final position *before* the vocab projection (prefill serving path).
    """
    h = params["embed"][tokens]
    if patch_embeds is not None:
        h = jnp.concatenate([patch_embeds.astype(h.dtype), h], axis=1)
    h = forward_hidden(params, cfg, h, remat=remat)
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    if last_only:
        h = h[:, -1:]
    return act.shard_logits((h @ params["lm_head"]).astype(jnp.float32))


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               dtype=jnp.bfloat16) -> Params:
    shape = (cfg.num_layers, batch, seq_len, cfg.num_kv_heads, cfg.resolved_head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32)}


def cache_spec(cfg: ModelConfig, batch: int, seq_len: int,
               dtype=jnp.bfloat16) -> Params:
    """ShapeDtypeStruct version of :func:`init_cache` (dry-run, no alloc)."""
    shape = (cfg.num_layers, batch, seq_len, cfg.num_kv_heads, cfg.resolved_head_dim)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype),
            "pos": jax.ShapeDtypeStruct((), jnp.int32)}


def decode_step(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                cache: Params, *, use_kernel: bool = False
                ) -> Tuple[jnp.ndarray, Params]:
    """token: (B, 1) -> (logits (B, V), updated cache)."""
    h = params["embed"][token]
    pos = cache["pos"]
    seq = cache["k"].shape[2]
    windows = layer_windows(cfg, seq)

    def body(carry, xs):
        x = carry
        lp, ck, cv, win = xs
        a, ck, cv = L.attention_decode(lp["attn"],
                                       L.rmsnorm(lp["ln1"], x, cfg.norm_eps),
                                       ck, cv, pos,
                                       num_heads=cfg.num_heads,
                                       num_kv=cfg.num_kv_heads,
                                       head_dim=cfg.resolved_head_dim,
                                       rope_theta=cfg.rope_theta, window=win,
                                       use_kernel=use_kernel)
        x = x + a
        m = L.swiglu(lp["mlp"], L.rmsnorm(lp["ln2"], x, cfg.norm_eps))
        return x + m, (ck, cv)

    h, (new_k, new_v) = lax.scan(body, h,
                                 (params["layers"], cache["k"], cache["v"], windows))
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h[:, 0, :] @ params["lm_head"]).astype(jnp.float32)
    new_cache = {"k": new_k, "v": new_v, "pos": pos + 1}
    return logits, new_cache


# ---------------------------------------------------------------------------
# split-cache decode: ring buffers for sliding-window layers
# ---------------------------------------------------------------------------
#
# The uniform cache allocates full seq_len for every layer, but a local
# (sliding-window) layer only ever reads the last W positions.  For gemma3's
# 5:1 pattern at 500k that wastes ~80% of cache HBM and — worse for the
# memory-bound decode roofline — reads it all back every step.  The split
# cache keeps a (n_local, B, W, K, Dh) ring for local layers and full
# (n_global, B, S, K, Dh) buffers only for the global ones.
#
# Ring semantics: position p lives in slot p % W.  Slot s therefore holds
# p_s = pos - ((pos - s) mod W); it is valid iff p_s >= 0 (RoPE is applied at
# absolute positions before the write, so reads need no rotation fix-up).

def _ring_positions(pos: jnp.ndarray, w: int) -> jnp.ndarray:
    s = jnp.arange(w)
    return pos - jnp.mod(pos - s, w)


def num_local_layers(cfg: ModelConfig) -> int:
    """Static count of sliding-window layers (python ints, eval_shape-safe)."""
    if cfg.local_global_ratio:
        period = cfg.local_global_ratio + 1
        n_global = cfg.num_layers // period
        return cfg.num_layers - n_global
    return cfg.num_layers if cfg.sliding_window else 0


def init_split_cache(cfg: ModelConfig, batch: int, seq_len: int,
                     dtype=jnp.bfloat16) -> Params:
    n_local = num_local_layers(cfg)
    n_global = cfg.num_layers - n_local
    w = cfg.sliding_window
    hd = cfg.resolved_head_dim
    return {
        "local_k": jnp.zeros((n_local, batch, w, cfg.num_kv_heads, hd), dtype),
        "local_v": jnp.zeros((n_local, batch, w, cfg.num_kv_heads, hd), dtype),
        "global_k": jnp.zeros((n_global, batch, seq_len, cfg.num_kv_heads, hd),
                              dtype),
        "global_v": jnp.zeros((n_global, batch, seq_len, cfg.num_kv_heads, hd),
                              dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def split_cache_spec(cfg: ModelConfig, batch: int, seq_len: int,
                     dtype=jnp.bfloat16) -> Params:
    return jax.eval_shape(lambda: init_split_cache(cfg, batch, seq_len, dtype))


def decode_step_split(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                      cache: Params) -> Tuple[jnp.ndarray, Params]:
    """Decode with ring-buffered local layers.  Requires sliding_window > 0.

    Layer heterogeneity (which stack a layer's cache lives in) is static, so
    this path unrolls the layer loop instead of scanning — decode bodies are
    small and L <= ~40 for the SWA archs, and unrolling avoids dragging the
    full-seq global stacks through scan carries.
    """
    h = params["embed"][token]
    pos = cache["pos"]
    w = cfg.sliding_window
    hd = cfg.resolved_head_dim
    import numpy as _np
    if cfg.local_global_ratio:
        idx = _np.arange(cfg.num_layers)
        is_local = (idx % (cfg.local_global_ratio + 1)) != cfg.local_global_ratio
    else:
        is_local = _np.ones(cfg.num_layers, bool)

    lk_stack, lv_stack = cache["local_k"], cache["local_v"]
    gk_stack, gv_stack = cache["global_k"], cache["global_v"]
    li = gi = 0
    for layer in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[layer], params["layers"])
        xn = L.rmsnorm(lp["ln1"], h, cfg.norm_eps)
        if is_local[layer]:
            q, k, v = L._qkv(lp["attn"], xn, cfg.num_heads, cfg.num_kv_heads,
                             hd)
            p1 = jnp.full((1,), pos, jnp.int32)
            if cfg.rope_theta > 0:
                q = L.apply_rope(q, p1, cfg.rope_theta)
                k = L.apply_rope(k, p1, cfg.rope_theta)
            slot = jnp.mod(pos, w)
            lk = lax.dynamic_update_slice(
                lk_stack[li], k.astype(lk_stack.dtype), (0, slot, 0, 0))
            lv = lax.dynamic_update_slice(
                lv_stack[li], v.astype(lv_stack.dtype), (0, slot, 0, 0))
            valid = _ring_positions(pos, w) >= 0
            out = L._sdpa(q, lk, lv, valid[None, None, :])
            a = out.reshape(h.shape[0], 1, cfg.num_heads * hd) @ \
                lp["attn"]["wo"]
            lk_stack = lk_stack.at[li].set(lk)
            lv_stack = lv_stack.at[li].set(lv)
            li += 1
        else:
            a, gk, gv = L.attention_decode(
                lp["attn"], xn, gk_stack[gi], gv_stack[gi], pos,
                num_heads=cfg.num_heads, num_kv=cfg.num_kv_heads,
                head_dim=hd, rope_theta=cfg.rope_theta)
            gk_stack = gk_stack.at[gi].set(gk)
            gv_stack = gv_stack.at[gi].set(gv)
            gi += 1
        h = h + a
        h = h + L.swiglu(lp["mlp"], L.rmsnorm(lp["ln2"], h, cfg.norm_eps))

    new_cache = {"local_k": lk_stack, "local_v": lv_stack,
                 "global_k": gk_stack, "global_v": gv_stack, "pos": pos + 1}
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h[:, 0, :] @ params["lm_head"]).astype(jnp.float32)
    return logits, new_cache


def _prefill_body(cfg: ModelConfig, s: int, b: int, kv_dtype,
                  block_rows=None, start=None, page: int = 0,
                  quant: bool = False):
    """The per-layer prefill scan body shared by :func:`prefill` (contiguous
    cache) and :func:`prefill_paged` (page pool): K/V are rounded to the
    cache dtype *before* the in-pass attention so logits and cache match the
    token-by-token decode path exactly, and long sequences take the
    query-chunked attention path.  Emits (k, v) per layer for the caller to
    store.

    With ``block_rows``/``start`` (prefix sharing) the scan also carries the
    layer's page pool and splices cached-prefix K/V under the in-pass values
    (``layers.substitute_prefix_kv``) — the spliced tensor holds bitwise the
    values a from-scratch prefill would compute, so suffix K/V and
    last-position logits are bitwise identical to the non-sharing path.

    ``quant`` (int8 page pool): the in-pass attention sees K/V FAKE-quantized
    through the per-page int8 grid (``layers.quant_dequant_pages`` — the
    exact values later paged reads dequantize to), while the RAW values are
    emitted for the caller's ``quant_scatter_prefill_pages`` write, which
    recomputes the identical scales — no double rounding.  With prefix
    sharing the scan additionally carries the scale tensors to dequantize
    the spliced cached prefix."""
    prefix = start is not None

    def body(carry, xs):
        if prefix and quant:
            lp, win, pk, pv, sk, sv = xs
        elif prefix:
            lp, win, pk, pv = xs
        else:
            lp, win = xs
        x = carry
        xn = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
        q, k, v = L._qkv(lp["attn"], xn, cfg.num_heads, cfg.num_kv_heads,
                         cfg.resolved_head_dim)
        if cfg.rope_theta > 0:
            pos = jnp.arange(s)
            q = L.apply_rope(q, pos, cfg.rope_theta)
            k = L.apply_rope(k, pos, cfg.rope_theta)
        if quant:
            k_raw, v_raw = k, v
            k = L.quant_dequant_pages(k, page)
            v = L.quant_dequant_pages(v, page)
            if prefix:
                k = L.substitute_prefix_kv(pk, k, block_rows, start, sk)
                v = L.substitute_prefix_kv(pv, v, block_rows, start, sv)
        else:
            k = k.astype(kv_dtype)
            v = v.astype(kv_dtype)
            if prefix:
                k = L.substitute_prefix_kv(pk, k, block_rows, start)
                v = L.substitute_prefix_kv(pv, v, block_rows, start)
        qc = 512 if (s > 512 and s % 512 == 0) else s
        if s > qc:
            a = L.chunked_attention(q, k, v, q_chunk=qc, causal=True, window=win)
        else:
            mask = L.causal_window_mask(s, s, window=win)
            a = L._sdpa(q, k, v, mask)
        a = a.reshape(b, s, cfg.num_heads * cfg.resolved_head_dim) @ lp["attn"]["wo"]
        x = x + a
        m = L.swiglu(lp["mlp"], L.rmsnorm(lp["ln2"], x, cfg.norm_eps))
        return x + m, ((k_raw, v_raw) if quant else (k, v))

    return body


# ---------------------------------------------------------------------------
# paged KV cache (continuous batching)
# ---------------------------------------------------------------------------
#
# One physical page pool per tier — (L, P, page, K·Dh), each position's KV
# heads side by side in one minor dim (``layers.paged_kv``) — shared by every
# decode slot through an int32 block table (slot, logical page) -> physical
# page.  Physical page 0 is the null/trash page: idle slots and unallocated
# logical pages point there, its contents are garbage by design, and no
# positional mask ever exposes it.  There is NO global ``pos`` scalar — each
# slot carries its own position (slots decode at different depths).


def init_paged_cache(cfg: ModelConfig, num_slots: int, num_pages: int,
                     page_size: int, dtype=jnp.bfloat16) -> Params:
    del num_slots                       # attention state lives in pages only
    shape = (cfg.num_layers, num_pages, page_size,
             cfg.num_kv_heads * cfg.resolved_head_dim)
    cache = {"kp": jnp.zeros(shape, dtype), "vp": jnp.zeros(shape, dtype)}
    if jnp.dtype(dtype) == jnp.int8:
        # symmetric per-page-per-head scales ride beside the int8 pools in
        # the same donated cache pytree (dequant = int8 * scale)
        sshape = (cfg.num_layers, num_pages, cfg.num_kv_heads)
        cache["ks"] = jnp.zeros(sshape, jnp.float32)
        cache["vs"] = jnp.zeros(sshape, jnp.float32)
    return cache


def prefill_paged(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  lengths: jnp.ndarray, slots: jnp.ndarray,
                  block_rows: jnp.ndarray, cache: Params, *,
                  start: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, Params]:
    """Prefill a batch of admitted requests (each padded to the fixed max
    bucket) into their pages in ONE pass.

    tokens: (A, S_max) right-padded; lengths: (A,) int32 true (bucketed)
    prompt lengths — each row's logits are taken at ``lengths[i] - 1`` and
    only keys below ``lengths[i]`` are ever unmasked downstream, so the
    padded tails compute garbage that is never observed.  block_rows:
    (A, n_pages) the admitted slots' block-table rows; padded admission rows
    point at the null page.  The fixed (A, S_max) shape is what keeps the
    scheduler at ONE compiled executable across every prompt bucket, and the
    A-way batching is what amortises admission cost like the drain path does.

    ``start`` (prefix sharing): per-row first UNCACHED position.  Cached
    positions' K/V are read from the aliased pages (``substitute_prefix_kv``)
    and their page writes are redirected to the null page
    (``suffix_write_rows``) — the shared prefix is read-only; only the
    suffix is prefilled.  With ``start=None`` the graph is exactly the
    non-sharing one.

    The layer math is EXACTLY :func:`prefill`'s (shared ``_prefill_body``);
    only the cache write (page scatter vs contiguous) and the logits
    position differ.  Returns (logits (A, V) fp32, cache).
    """
    del slots                           # dense state is fully page-resident
    h = params["embed"][tokens]
    b, s, _ = h.shape
    windows = layer_windows(cfg, s)
    page = cache["kp"].shape[2]
    npg = s // page
    quant = "ks" in cache
    if start is None:
        body = _prefill_body(cfg, s, b, cache["kp"].dtype, page=page,
                             quant=quant)
        h, (ks, vs) = lax.scan(body, h, (params["layers"], windows))
        wrows = block_rows[:, :npg]
    else:
        body = _prefill_body(cfg, s, b, cache["kp"].dtype, block_rows, start,
                             page=page, quant=quant)
        xs = (params["layers"], windows, cache["kp"], cache["vp"])
        if quant:
            xs = xs + (cache["ks"], cache["vs"])
        h, (ks, vs) = lax.scan(body, h, xs)
        wrows = L.suffix_write_rows(block_rows, start, npg, page)
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    h = jnp.take_along_axis(h, (lengths - 1)[:, None, None], axis=1)
    logits = (h[:, 0, :] @ params["lm_head"]).astype(jnp.float32)
    if quant:
        # per-layer quantize + scatter (vmapped over the leading L axis);
        # scales are recomputed from the same raw values the in-pass
        # fake-quant used, so in-pass and later paged reads agree
        new_k, new_sk = jax.vmap(
            lambda p, sc, kv: L.quant_scatter_prefill_pages(p, sc, kv, wrows)
        )(cache["kp"], cache["ks"], ks)
        new_v, new_sv = jax.vmap(
            lambda p, sc, kv: L.quant_scatter_prefill_pages(p, sc, kv, wrows)
        )(cache["vp"], cache["vs"], vs)
        return logits, {"kp": new_k, "vp": new_v, "ks": new_sk, "vs": new_sv}
    # ks: (L, A, S, K, Dh) -> every layer's (page, K·Dh) rows in one scatter
    shape = ks.shape[:1] + (b, npg, page, -1)
    new_k = cache["kp"].at[:, wrows].set(ks.reshape(shape), mode="drop")
    new_v = cache["vp"].at[:, wrows].set(vs.reshape(shape), mode="drop")
    return logits, {"kp": new_k, "vp": new_v}


def decode_step_paged(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                      pos: jnp.ndarray, block: jnp.ndarray, cache: Params, *,
                      use_kernel: bool = False,
                      write_block: Optional[jnp.ndarray] = None, rows=None
                      ) -> Tuple[jnp.ndarray, Params]:
    """One decode step for ALL slots at per-slot positions.

    token: (B, 1); pos: (B,) int32; block: (B, n_pages) int32; write_block:
    the append-side table with shared (read-only) pages masked to the null
    page; rows: the rows whose K/V each layer reads — see
    ``layers.attention_decode_paged``.  The stacked pools ride in the layer
    scan's carry, so each layer updates them in place.
    Returns (logits (B, V) fp32, cache)."""
    h = params["embed"][token]
    page = cache["kp"].shape[2]
    s_tot = block.shape[1] * page
    windows = layer_windows(cfg, s_tot)

    def body(carry, xs):
        x, kv = carry
        lp, win, li = xs
        a, kv = L.attention_decode_paged(
            lp["attn"], L.rmsnorm(lp["ln1"], x, cfg.norm_eps), kv, li,
            block, pos, num_heads=cfg.num_heads, num_kv=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            window=win, use_kernel=use_kernel, write_block=write_block,
            rows=rows)
        x = x + a
        m = L.swiglu(lp["mlp"], L.rmsnorm(lp["ln2"], x, cfg.norm_eps))
        return (x + m, kv), None

    (h, kv), _ = lax.scan(body, (h, L.paged_kv(cache)),
                          (params["layers"], windows,
                           jnp.arange(cache["kp"].shape[0])))
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h[:, 0, :] @ params["lm_head"]).astype(jnp.float32)
    return logits, {**cache, **kv}


def forward_chunk_paged(params: Params, cfg: ModelConfig,
                        tokens: jnp.ndarray, pos: jnp.ndarray,
                        block: jnp.ndarray, cache: Params, *,
                        use_kernel: bool = False,
                        write_block: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, Params, dict]:
    """The chunked token lane: C tokens for ALL slots at per-slot start
    positions in ONE multi-token paged pass — the generalisation that
    subsumes both ``prefill_paged`` (chunked prompt ingestion: feed the
    prompt C tokens per tick) and ``decode_step_paged`` (C = 1).

    tokens: (B, C) int32; pos: (B,) int32 per-slot start positions (token i
    of slot b lands at ``pos[b] + i``).  Per-position logits come back for
    every chunk token; a prefill chunk reads only its last live position.
    Attention state is entirely positional, so the staged snapshot dict is
    empty.  Returns (logits (B, C, V) fp32, cache, staged).
    """
    h = params["embed"][tokens]
    page = cache["kp"].shape[2]
    s_tot = block.shape[1] * page
    windows = layer_windows(cfg, s_tot)
    quant = "ks" in cache

    def body(carry, xs):
        x = carry
        if quant:
            lp, pk, pv, sk, sv, win = xs
            a, pk, pv, sk, sv = L.attention_chunk_paged(
                lp["attn"], L.rmsnorm(lp["ln1"], x, cfg.norm_eps), pk, pv,
                block, pos, num_heads=cfg.num_heads, num_kv=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
                window=win, use_kernel=use_kernel, write_block=write_block,
                scale_k=sk, scale_v=sv)
            x = x + a
            m = L.swiglu(lp["mlp"], L.rmsnorm(lp["ln2"], x, cfg.norm_eps))
            return x + m, (pk, pv, sk, sv)
        lp, pk, pv, win = xs
        a, pk, pv = L.attention_chunk_paged(
            lp["attn"], L.rmsnorm(lp["ln1"], x, cfg.norm_eps), pk, pv,
            block, pos, num_heads=cfg.num_heads, num_kv=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            window=win, use_kernel=use_kernel, write_block=write_block)
        x = x + a
        m = L.swiglu(lp["mlp"], L.rmsnorm(lp["ln2"], x, cfg.norm_eps))
        return x + m, (pk, pv)

    if quant:
        h, (nk, nv, nsk, nsv) = lax.scan(
            body, h, (params["layers"], cache["kp"], cache["vp"],
                      cache["ks"], cache["vs"], windows))
    else:
        h, (nk, nv) = lax.scan(body, h, (params["layers"], cache["kp"],
                                         cache["vp"], windows))
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h @ params["lm_head"]).astype(jnp.float32)
    if quant:
        return logits, {"kp": nk, "vp": nv, "ks": nsk, "vs": nsv}, {}
    return logits, {"kp": nk, "vp": nv}, {}


def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            cache: Params, *, patch_embeds: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Params]:
    """Fill the KV cache from a (B, S) prompt in ONE batched pass.

    ``cache`` (from :func:`init_cache`) supplies the buffers; its contents are
    fully overwritten, so callers may donate it across requests.  K/V are
    rounded to the cache dtype *before* the in-pass attention so logits and
    cache match the token-by-token :func:`decode_step` path exactly.

    Returns (last-token logits (B, V) fp32, filled cache).
    """
    h = params["embed"][tokens]
    if patch_embeds is not None:
        h = jnp.concatenate([patch_embeds.astype(h.dtype), h], axis=1)
    b, s, _ = h.shape
    windows = layer_windows(cfg, s)
    body = _prefill_body(cfg, s, b, cache["k"].dtype)
    h, (ks, vs) = lax.scan(body, h, (params["layers"], windows))
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h[:, -1, :] @ params["lm_head"]).astype(jnp.float32)
    # write the prompt K/V into the provided buffers; the tail past ``s`` is
    # never read (decode masks positions > pos), so stale values are fine
    new_k = lax.dynamic_update_slice(cache["k"], ks, (0, 0, 0, 0, 0))
    new_v = lax.dynamic_update_slice(cache["v"], vs, (0, 0, 0, 0, 0))
    return logits, {"k": new_k, "v": new_v, "pos": jnp.asarray(s, jnp.int32)}
