"""Zamba2 hybrid: a Mamba-2 backbone whose layers in ``hybrid_layer_ids``
first apply one of ``num_mem_blocks`` shared transformer blocks, taken in
turn [arXiv:2411.15242; transformers' ``modeling_zamba2``].

Hybrid layer i, application j (the j-th id), h its input, e the token
embedding:

    t = RMSNorm_2d(concat(h, e))
    a = Attn_j%nb(t)        q/k/v 2d -> H x Dh, RoPE, scores x (Dh / 2)^-0.5
    m = MLP_j%nb(RMSNorm_d(a))          gate_up = x W + B_j(A_j(x)), act(gate) * up
    u = Linear_j(m)                     the block has no residual
    h = h + Mamba_i(RMSNorm(h + u))     the residual is taken from h

Every other layer is h + Mamba_i(RMSNorm(h)).  The shared blocks and the
per-application adapters and linears are lists (no slicing of a stacked
weight); the mamba layers are one stack walked by index, in runs that start
at each application (:func:`_runs`), so uneven gaps between the ids cost
nothing.  Each application has its own KV cache: the pools' leading axis is
the application.  The per-slot recurrent state has the SSM family's layout
(layer, slot, ...), so ``model_zoo`` runs its snapshots and chunk stages
through ``mamba2``.

Inside a tier's lane scope the mamba layers run under ``jax.named_scope
("ssm")`` and the shared blocks, adapters and linears under ``"shared"``.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba2
from repro.sharding import act

Params = Dict[str, Any]

def _runs(cfg: ModelConfig) -> List[Tuple[Optional[int], int, int]]:
    """(application j or None, first layer, end layer): the mamba layers
    [lo, hi) that follow application j; None for those before the first."""
    ids = list(cfg.hybrid_layer_ids)
    ends = ids + [cfg.num_layers]
    runs = [(None, 0, ends[0])] if ends[0] > 0 else []
    return runs + [(j, lo, ends[j + 1]) for j, lo in enumerate(ids)]


# Zamba2 scales scores by (head_dim / 2) ** -0.5: sqrt(2) over the attention
# path's 1 / sqrt(head_dim), applied to q (``layers.rope_q``)
Q_SCALE = math.sqrt(2.0)


def init_model(rng, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    """Mamba layers as the SSM family's; the shared blocks, adapters and
    linears normal with std 1 / sqrt(fan_in), unit gain at every width
    (Zamba2's initializer_range 0.02 is about that at its widths)."""
    d, f, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank
    hq = cfg.num_heads * cfg.resolved_head_dim
    hkv = cfg.num_kv_heads * cfg.resolved_head_dim
    n_apps = len(cfg.hybrid_layer_ids)
    k_mamba, k_embed, k_head, k_rest = jax.random.split(rng, 4)
    stacked = jax.vmap(lambda k: mamba2.init_mamba_block(k, cfg, dtype))(
        jax.random.split(k_mamba, cfg.num_layers))
    ks = iter(jax.random.split(k_rest, 6 * cfg.num_mem_blocks + 3 * n_apps))

    def normal(fan_in, fan_out):
        return L.dense_init(next(ks), fan_in, fan_out, dtype)

    def block():
        din = 2 * d                  # the block attends over concat(h, e)
        return {"ln1": L.rmsnorm_init(din, dtype),
                "attn": {"wq": normal(din, hq), "wk": normal(din, hkv),
                         "wv": normal(din, hkv), "wo": normal(hq, d)},
                "ln2": L.rmsnorm_init(d, dtype),
                "mlp": {"wi": normal(d, 2 * f), "wo": normal(f, d)}}

    return {
        "embed": L.embed_init(k_embed, cfg.vocab_size, d, dtype),
        "mamba": stacked,
        "shared": [block() for _ in range(cfg.num_mem_blocks)],
        "apps": [{"lora_a": normal(d, r), "lora_b": normal(r, 2 * f),
                  "linear": normal(d, d)} for _ in range(n_apps)],
        "final_norm": L.rmsnorm_init(d, dtype),
        "lm_head": L.dense_init(k_head, d, cfg.vocab_size, dtype),
    }


# ---------------------------------------------------------------------------
# the layer walk every pass shares
# ---------------------------------------------------------------------------

def _layer(stack: Params, i) -> Params:
    return jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False),
                        stack)


def _shared_out(sp: Params, ap: Params, cfg: ModelConfig,
                a: jnp.ndarray) -> jnp.ndarray:
    """The block after attention: RMSNorm, the MLP with this application's
    adapter on gate_up, then this application's linear."""
    x = L.rmsnorm(sp["ln2"], a, cfg.norm_eps)
    gu = x @ sp["mlp"]["wi"] + (x @ ap["lora_a"]) @ ap["lora_b"]
    gate, up = jnp.split(gu, 2, axis=-1)
    gate = jax.nn.gelu(gate, approximate=False)
    return ((gate * up) @ sp["mlp"]["wo"]) @ ap["linear"]


def _walk(params: Params, cfg: ModelConfig, e: jnp.ndarray, aux,
          attend: Callable, mamba: Callable, remat: bool = False):
    """Run the layers over hidden states starting at the embedding ``e``.

    ``attend(attn_params, j, t, aux) -> (a, aux)``: application j's attention
    over the normed block input t, output projection included.
    ``mamba(lp, i, x, aux) -> (y, aux)``: mamba layer i over its normed
    input.  ``aux`` carries what the pass updates (caches), in place."""
    h = e
    for j, lo, hi in _runs(cfg):
        u = jnp.zeros_like(h)
        if j is not None:
            with jax.named_scope("shared"):
                sp = params["shared"][j % cfg.num_mem_blocks]
                t = L.rmsnorm(sp["ln1"], jnp.concatenate([h, e], axis=-1),
                              cfg.norm_eps)
                a, aux = attend(sp["attn"], j, t, aux)
                u = _shared_out(sp, params["apps"][j], cfg, a)

        def body(i, c):
            h, u, aux = c
            lp = _layer(params["mamba"], i)
            y, aux = mamba(lp, i, L.rmsnorm(lp["ln"], h + u, cfg.norm_eps), aux)
            # u enters the run's first layer only
            return act.shard_hidden(h + y), jnp.zeros_like(u), aux

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        with jax.named_scope("ssm"):
            h, _, aux = lax.fori_loop(lo, hi, body, (h, u, aux))
    return h, aux


def _logits(params: Params, cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return (h @ params["lm_head"]).astype(jnp.float32)


def _qkv_rope(ap: Params, cfg: ModelConfig, t: jnp.ndarray, pos):
    hd = cfg.resolved_head_dim
    q, k, v = L._qkv(ap, t, cfg.num_heads, cfg.num_kv_heads, hd)
    q = L.rope_q(q, pos, cfg.rope_theta, Q_SCALE)
    if cfg.rope_theta > 0:
        k = L.apply_rope(k, pos, cfg.rope_theta)
    return q, k, v


def _causal_attend(ap: Params, cfg: ModelConfig, q, k, v) -> jnp.ndarray:
    b, s = q.shape[:2]
    o = L._sdpa(q, k, v, L.causal_window_mask(s, s))
    return o.reshape(b, s, cfg.num_heads * cfg.resolved_head_dim) @ ap["wo"]


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray, *,
            remat: bool = False, use_kernel: bool = False,
            last_only: bool = False) -> jnp.ndarray:
    e = act.shard_hidden(params["embed"][tokens])
    pos = jnp.arange(tokens.shape[1])

    def attend(ap, j, t, aux):
        return _causal_attend(ap, cfg, *_qkv_rope(ap, cfg, t, pos)), aux

    def mamba(lp, i, x, aux):
        return mamba2.mamba_block(lp, cfg, x, use_kernel=use_kernel), aux

    h, _ = _walk(params, cfg, e, None, attend, mamba, remat=remat)
    if last_only:
        h = h[:, -1:]
    return act.shard_logits(_logits(params, cfg, h))


# ---------------------------------------------------------------------------
# contiguous cache (the drain path)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=jnp.bfloat16):
    ssm = mamba2.init_cache(cfg, batch, seq_len, dtype)
    kv_shape = (len(cfg.hybrid_layer_ids), batch, seq_len, cfg.num_kv_heads,
                cfg.resolved_head_dim)
    return {"state": ssm["state"], "conv": ssm["conv"],
            "k": jnp.zeros(kv_shape, dtype), "v": jnp.zeros(kv_shape, dtype),
            "pos": jnp.zeros((), jnp.int32)}


def _step_mamba(cfg: ModelConfig):
    """A decode step's mamba layer: the slot rows' state and conv window of
    layer i, updated in place in ``aux``'s "state" and "conv"."""
    def mamba(lp, i, x, aux):
        st = lax.dynamic_index_in_dim(aux["state"], i, keepdims=False)
        cw = lax.dynamic_index_in_dim(aux["conv"], i, keepdims=False)
        y, st, cw = mamba2.mamba_block_step(lp, cfg, x, st, cw)
        return y, dict(aux,
                       state=lax.dynamic_update_index_in_dim(aux["state"], st, i, 0),
                       conv=lax.dynamic_update_index_in_dim(aux["conv"], cw, i, 0))
    return mamba


def decode_step(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                cache: Params) -> Tuple[jnp.ndarray, Params]:
    pos = cache["pos"]

    def attend(ap, j, t, aux):
        a, ck, cv = L.attention_decode(
            ap, t, aux["k"][j], aux["v"][j], pos, num_heads=cfg.num_heads,
            num_kv=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
            rope_theta=cfg.rope_theta, q_scale=Q_SCALE)
        return a, dict(aux, k=aux["k"].at[j].set(ck), v=aux["v"].at[j].set(cv))

    aux = {k: cache[k] for k in ("state", "conv", "k", "v")}
    h, aux = _walk(params, cfg, params["embed"][token], aux, attend,
                   _step_mamba(cfg))
    return _logits(params, cfg, h[:, 0]), dict(aux, pos=pos + 1)


def _prefill_walk(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  aux, store_kv: Callable, state_rows, kv_dtype, conv_dtype,
                  use_kernel: bool, length=None, quant_page: int = 0):
    """The prompt pass shared by :func:`prefill` and :func:`prefill_paged`:
    each application's K/V goes to ``store_kv(j, k, v, aux)``, each layer's
    final state and conv window to rows ``state_rows`` of ``aux``'s "state"
    and "conv" at that layer.  In-pass attention reads K/V as the cache
    holds them: rounded to ``kv_dtype`` or, with ``quant_page`` (int8 page
    pools), fake-quantized through the per-page grid while the raw values
    go to ``store_kv``, whose quantize-on-write recomputes the identical
    scales."""
    e = act.shard_hidden(params["embed"][tokens])
    pos = jnp.arange(tokens.shape[1])

    def attend(ap, j, t, aux):
        q, k, v = _qkv_rope(ap, cfg, t, pos)
        aux = store_kv(j, k, v, aux)
        if quant_page:
            k = L.quant_dequant_pages(k, quant_page)
            v = L.quant_dequant_pages(v, quant_page)
        else:
            k, v = k.astype(kv_dtype), v.astype(kv_dtype)
        return _causal_attend(ap, cfg, q, k, v), aux

    def mamba(lp, i, x, aux):
        y, st, cw = mamba2.mamba_block_prefill(
            lp, cfg, x, use_kernel=use_kernel, conv_dtype=conv_dtype,
            length=length)
        return y, dict(aux, state=aux["state"].at[i, state_rows].set(st, mode="drop"),
                       conv=aux["conv"].at[i, state_rows].set(cw, mode="drop"))

    return _walk(params, cfg, e, aux, attend, mamba)


def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            cache: Params, *, use_kernel: bool = False
            ) -> Tuple[jnp.ndarray, Params]:
    """Consume the whole (B, S) prompt in one batched pass, writing the SSM
    states, conv windows and every application's K/V.  ``cache`` supplies
    the buffers and is overwritten (donation-safe).

    Returns (last-token logits (B, V) fp32, filled cache).
    """
    s = tokens.shape[1]

    def store_kv(j, k, v, aux):
        return dict(aux, k=aux["k"].at[j, :, :s].set(k.astype(aux["k"].dtype)),
                    v=aux["v"].at[j, :, :s].set(v.astype(aux["v"].dtype)))

    aux = {k: cache[k] for k in ("state", "conv", "k", "v")}
    h, aux = _prefill_walk(params, cfg, tokens, aux, store_kv, slice(None),
                           cache["k"].dtype, cache["conv"].dtype, use_kernel)
    return _logits(params, cfg, h[:, -1]), dict(aux, pos=jnp.asarray(s, jnp.int32))


# ---------------------------------------------------------------------------
# paged cache API (continuous batching)
# ---------------------------------------------------------------------------
#
# Mamba states are per-slot (constant-size, nothing to page); each shared
# block application's K/V is a paged pool behind one leading axis,
# (A, P, page, K·Dh) as ``layers.paged_kv`` lays it out, indexed by the
# scheduler's single block table (one physical page holds every
# application's K/V for a page worth of positions).


def init_paged_cache(cfg: ModelConfig, num_slots: int, num_pages: int,
                     page_size: int, dtype=jnp.bfloat16):
    cache = mamba2.init_paged_cache(cfg, num_slots, num_pages, page_size, dtype)
    kv_shape = (len(cfg.hybrid_layer_ids), num_pages, page_size,
                cfg.num_kv_heads * cfg.resolved_head_dim)
    cache.update(kp=jnp.zeros(kv_shape, dtype), vp=jnp.zeros(kv_shape, dtype))
    if jnp.dtype(dtype) == jnp.int8:
        sshape = kv_shape[:2] + (cfg.num_kv_heads,)
        cache.update(ks=jnp.zeros(sshape, jnp.float32),
                     vs=jnp.zeros(sshape, jnp.float32))
    return cache


def prefill_paged(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  lengths: jnp.ndarray, slots: jnp.ndarray,
                  block_rows: jnp.ndarray, cache: Params, *,
                  use_kernel: bool = False,
                  start=None) -> Tuple[jnp.ndarray, Params]:
    """Prefill a batch of admitted requests: per-layer SSM states and conv
    windows land in slots ``slots``; each application's K/V lands in each
    slot's pages.

    ``start``: the hybrid shares prefixes only at whole-prompt granularity
    (the mamba state has no mid-prompt snapshot), so per row ``start`` is 0
    (miss: full prefill) or bucket (restore: every page write redirected to
    the null page — the aliased prompt pages are read-only)."""
    s = tokens.shape[1]
    page = cache["kp"].shape[2]
    npg = s // page
    quant = "ks" in cache
    wrows = (block_rows[:, :npg] if start is None
             else L.suffix_write_rows(block_rows, start, npg, page))

    def store_kv(j, k, v, aux):
        if quant:
            kp, ks = L.quant_scatter_prefill_pages(aux["kp"], aux["ks"], k, wrows, j)
            vp, vs = L.quant_scatter_prefill_pages(aux["vp"], aux["vs"], v, wrows, j)
            return dict(aux, kp=kp, vp=vp, ks=ks, vs=vs)
        return dict(aux, kp=L.scatter_prefill_pages(aux["kp"], k, wrows, j),
                    vp=L.scatter_prefill_pages(aux["vp"], v, wrows, j))

    h, cache = _prefill_walk(params, cfg, tokens, dict(cache), store_kv,
                             slots, cache["kp"].dtype, cache["conv"].dtype,
                             use_kernel, length=lengths,
                             quant_page=page if quant else 0)
    h = jnp.take_along_axis(h, (lengths - 1)[:, None, None], axis=1)
    return _logits(params, cfg, h[:, 0]), cache


def decode_step_paged(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                      pos: jnp.ndarray, block: jnp.ndarray, cache: Params, *,
                      use_kernel: bool = False,
                      write_block=None, rows=None) -> Tuple[jnp.ndarray, Params]:
    """One decode step for all slots at per-slot positions.  The stacked KV
    pools and the per-slot recurrent state ride in the layer walk's carry
    and are updated in place; ``rows`` (see
    ``layers.attention_decode_paged``) narrows the shared blocks' K/V
    reads."""

    def attend(ap, j, t, aux):
        a, kv = L.attention_decode_paged(
            ap, t, L.paged_kv(aux), j, block, pos, num_heads=cfg.num_heads,
            num_kv=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
            rope_theta=cfg.rope_theta, use_kernel=use_kernel,
            write_block=write_block, q_scale=Q_SCALE, rows=rows)
        return a, dict(aux, **kv)

    h, cache = _walk(params, cfg, params["embed"][token], dict(cache), attend,
                     _step_mamba(cfg))
    return _logits(params, cfg, h[:, 0]), cache


def forward_chunk_paged(params: Params, cfg: ModelConfig,
                        tokens: jnp.ndarray, pos: jnp.ndarray,
                        block: jnp.ndarray, cache: Params, *,
                        use_kernel: bool = False,
                        write_block=None) -> Tuple[jnp.ndarray, Params, dict]:
    """Chunked token lane for the hybrid: a ``lax.scan`` of per-token steps
    (bitwise identical to C sequential ``decode_step_paged`` calls — the
    mamba half is inherently sequential) emitting per-step {state, conv}
    CHUNK-BOUNDARY SNAPSHOTS so a chunk can be rolled back to any intra-chunk
    position; the shared blocks' K/V lands in the pages positionally (a
    rollback just rewinds the host position).  Token i of slot b writes at
    ``pos[b] + i`` through ``write_block``.  Returns (logits (B, C, V) fp32,
    cache, staged)."""

    def step(cache, xs):
        tok, j = xs
        logits, cache = decode_step_paged(params, cfg, tok[:, None], pos + j,
                                          block, cache, use_kernel=use_kernel,
                                          write_block=write_block)
        return cache, (logits, mamba2.chunk_stage(cfg, cache))

    c = tokens.shape[1]
    cache, (logits, staged) = lax.scan(step, cache, (tokens.T, jnp.arange(c)))
    return logits.transpose(1, 0, 2), cache, staged
