"""Shared neural-net building blocks (pure JAX, pytree params).

Conventions
-----------
* hidden states ``(B, S, D)``; attention heads ``(B, S, H, Dh)``.
* params are nested dicts of ``jnp.ndarray``; per-layer stacks add a leading
  ``L`` axis and are consumed by ``lax.scan`` (compile-time: one layer body).
* everything is differentiable and jit/pjit-safe (static shapes only).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# initialisers
# ---------------------------------------------------------------------------

def dense_init(rng, in_dim: int, out_dim: int, dtype=jnp.float32) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(rng, (in_dim, out_dim)) * scale).astype(dtype)


def embed_init(rng, vocab: int, dim: int, dtype=jnp.float32) -> jnp.ndarray:
    return (jax.random.normal(rng, (vocab, dim)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_init(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, Dh); positions: (S,) or (B, S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                      # (Dh/2,)
    if positions.ndim == 1:
        angles = positions[:, None].astype(jnp.float32) * freqs[None, :]   # (S, Dh/2)
        angles = angles[None, :, None, :]                                   # (1,S,1,Dh/2)
    else:
        angles = positions[..., None].astype(jnp.float32) * freqs           # (B,S,Dh/2)
        angles = angles[:, :, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def rope_q(q: jnp.ndarray, positions: jnp.ndarray, theta: float,
           q_scale: float = 1.0) -> jnp.ndarray:
    """RoPE on queries, then ``q_scale`` in the same float32 pass (one
    rounding to q's dtype): attention divides scores by sqrt(head_dim), so a
    model whose published score scale differs (zamba2's (head_dim / 2) **
    -0.5) passes the ratio here.  ``q_scale`` 1 is exactly the plain path."""
    if q_scale == 1.0:
        return apply_rope(q, positions, theta) if theta > 0 else q
    q32 = q.astype(jnp.float32)
    if theta > 0:
        q32 = apply_rope(q32, positions, theta)
    return (q32 * q_scale).astype(q.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attention_init(rng, d_model: int, num_heads: int, num_kv: int, head_dim: int,
                   *, bias: bool = False, dtype=jnp.float32) -> Params:
    ks = jax.random.split(rng, 4)
    p: Params = {
        "wq": dense_init(ks[0], d_model, num_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, num_kv * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, num_kv * head_dim, dtype),
        "wo": dense_init(ks[3], num_heads * head_dim, d_model, dtype),
    }
    if bias:
        p["bq"] = jnp.zeros((num_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((num_kv * head_dim,), dtype)
        p["bv"] = jnp.zeros((num_kv * head_dim,), dtype)
    return p


def _qkv(params: Params, x: jnp.ndarray, num_heads: int, num_kv: int, head_dim: int):
    b, s, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    return (q.reshape(b, s, num_heads, head_dim),
            k.reshape(b, s, num_kv, head_dim),
            v.reshape(b, s, num_kv, head_dim))


def _sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
          mask: Optional[jnp.ndarray]) -> jnp.ndarray:
    """q: (B,Sq,H,Dh)  k,v: (B,Sk,K,Dh)  GQA via head grouping.

    mask: broadcastable to (B, H, Sq, Sk), True = attend.
    """
    b, sq, h, dh = q.shape
    kheads = k.shape[2]
    groups = h // kheads
    # matmuls run in the cache dtype with fp32 accumulation
    # (preferred_element_type) — never materialise an upcast copy of the
    # K/V cache (for a 32k cache that copy would double decode HBM).
    qg = q.reshape(b, sq, kheads, groups, dh).astype(k.dtype)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) / math.sqrt(dh)
    if mask is not None:
        if mask.ndim == 3:                    # (B|1, Sq, Sk)
            m = mask[:, None, None, :, :]
        else:                                  # (B|1, H, Sq, Sk)
            m = mask.reshape(mask.shape[0], kheads, groups, *mask.shape[-2:])
        scores = jnp.where(m, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, dh).astype(q.dtype)


def causal_window_mask(sq: int, sk: int, *, q_offset: int = 0,
                       window: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(1, Sq, Sk) boolean mask: causal, optionally sliding-window.

    ``window`` may be a traced scalar (enables gemma3's per-layer local/global
    switch inside a single scanned layer body without lax.cond).
    """
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    mask = kpos <= qpos
    if window is not None:
        mask = mask & (qpos - kpos < window)
    return mask[None]


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      q_chunk: int, causal: bool,
                      window: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Query-chunked attention: bounds the score buffer to (B,H,qc,Sk).

    Each query block sees its complete key row, so plain (not online) softmax
    is exact.  Memory per block: B*H*qc*Sk fp32 instead of B*H*Sq*Sk.
    """
    b, sq, h, dh = q.shape
    if sq % q_chunk:
        raise ValueError(f"seq {sq} not divisible by q_chunk {q_chunk}")
    nblk = sq // q_chunk
    qb = q.reshape(b, nblk, q_chunk, h, dh).transpose(1, 0, 2, 3, 4)

    def body(carry, args):
        i, qi = args
        mask = None
        if causal:
            mask = causal_window_mask(q_chunk, k.shape[1],
                                      q_offset=i * q_chunk, window=window)
        out = _sdpa(qi, k, v, mask)
        return carry, out

    _, outs = lax.scan(body, None, (jnp.arange(nblk), qb))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dh)


def attention_forward(params: Params, x: jnp.ndarray, *, num_heads: int,
                      num_kv: int, head_dim: int, rope_theta: float,
                      causal: bool = True,
                      window: Optional[jnp.ndarray] = None,
                      positions: Optional[jnp.ndarray] = None,
                      q_chunk: int = 512) -> jnp.ndarray:
    """Full-sequence self-attention (train / prefill)."""
    b, s, _ = x.shape
    q, k, v = _qkv(params, x, num_heads, num_kv, head_dim)
    if rope_theta > 0:
        pos = positions if positions is not None else jnp.arange(s)
        q = apply_rope(q, pos, rope_theta)
        k = apply_rope(k, pos, rope_theta)
    if s > q_chunk and s % q_chunk == 0:
        out = chunked_attention(q, k, v, q_chunk=q_chunk, causal=causal,
                                window=window)
    else:
        mask = causal_window_mask(s, s, window=window) if causal else None
        out = _sdpa(q, k, v, mask)
    return out.reshape(b, s, num_heads * head_dim) @ params["wo"]


def _decode_attn_streamed(q: jnp.ndarray, cache_k: jnp.ndarray,
                          cache_v: jnp.ndarray, valid: jnp.ndarray,
                          block_s: int) -> jnp.ndarray:
    """Online-softmax decode attention streaming the cache in S blocks —
    the jnp mirror of kernels/decode_attention.py.  Bounds the working set
    to one (B, block_s, K, D) tile (the full-cache _sdpa path would force an
    upcast copy of the entire cache)."""
    b, _, h, d = q.shape
    s, kh = cache_k.shape[1], cache_k.shape[2]
    g = h // kh
    nblk = s // block_s
    qg = (q.reshape(b, 1, kh, g, d).astype(cache_k.dtype)
          / math.sqrt(d))

    def body(carry, i):
        m, l, acc = carry
        sl = i * block_s
        kb = lax.dynamic_slice_in_dim(cache_k, sl, block_s, axis=1)
        vb = lax.dynamic_slice_in_dim(cache_v, sl, block_s, axis=1)
        vm = lax.dynamic_slice_in_dim(valid, sl, block_s, axis=0)
        scores = jnp.einsum("bqkgd,bskd->bkgs", qg, kb,
                            preferred_element_type=jnp.float32)
        scores = jnp.where(vm[None, None, None, :], scores, -1e30)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        p = jnp.exp(scores - m_new[..., None]) \
            * vm[None, None, None, :].astype(jnp.float32)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgs,bskd->bkgd", p.astype(vb.dtype), vb,
                        preferred_element_type=jnp.float32)
        acc = acc * corr[..., None] + pv
        return (m_new, l, acc), None

    m0 = jnp.full((b, kh, g), -1e30, jnp.float32)
    l0 = jnp.zeros((b, kh, g), jnp.float32)
    a0 = jnp.zeros((b, kh, g, d), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(nblk))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, 1, h, d).astype(q.dtype)


# stream the cache for long contexts; below this, one full-row _sdpa is fine
_DECODE_STREAM_THRESHOLD = 8192
_DECODE_BLOCK_S = 2048


def _no_mesh() -> bool:
    from repro.sharding import act
    return act.current_mesh() is None


def attention_decode(params: Params, x: jnp.ndarray, cache_k: jnp.ndarray,
                     cache_v: jnp.ndarray, pos: jnp.ndarray, *, num_heads: int,
                     num_kv: int, head_dim: int, rope_theta: float,
                     window: Optional[jnp.ndarray] = None,
                     use_kernel: bool = False, q_scale: float = 1.0
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token decode.  x: (B, 1, D); cache_k/v: (B, S, K, Dh); pos: scalar.
    ``q_scale``: see :func:`rope_q`.

    Returns (out (B,1,D), new_cache_k, new_cache_v).
    """
    b = x.shape[0]
    q, k, v = _qkv(params, x, num_heads, num_kv, head_dim)
    p1 = jnp.full((1,), pos, dtype=jnp.int32)
    q = rope_q(q, p1, rope_theta, q_scale)
    if rope_theta > 0:
        k = apply_rope(k, p1, rope_theta)
    cache_k = lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                       (0, pos, 0, 0))
    cache_v = lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                       (0, pos, 0, 0))
    s = cache_k.shape[1]
    kpos = jnp.arange(s)
    valid = kpos <= pos
    if window is not None:
        valid = valid & (pos - kpos < window)
    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.decode_attention(q, cache_k, cache_v, valid)
    elif s >= _DECODE_STREAM_THRESHOLD and s % _DECODE_BLOCK_S == 0 \
            and _no_mesh():
        # streaming bounds the working set for LOCAL serving; under a mesh
        # the cache is sequence-sharded and block-slicing it would all-gather
        # (measured +129 GB/step on llava decode) — GSPMD's partial-softmax
        # over the sharded S dim is the right plan there.
        out = _decode_attn_streamed(q, cache_k, cache_v, valid,
                                    _DECODE_BLOCK_S)
    else:
        mask = valid[None, None, :]            # (1, 1, S) -> broadcast (B,Sq,Sk)
        out = _sdpa(q, cache_k, cache_v, mask)
    out = out.reshape(b, 1, num_heads * head_dim) @ params["wo"]
    return out, cache_k, cache_v


def paged_kv(cache: Params) -> Params:
    """The stacked KV pools of a paged cache: ``kp``/``vp`` (L, P, page,
    K·Dh), plus the int8 pools' ``ks``/``vs`` (L, P, K) fp32 scales.

    A pool row holds one position's K (or V) for every KV head, head-major
    in one minor dim: every lane (COW, admission scatter, decode append,
    page gather) reads and writes whole ``(page, K·Dh)`` rows, so no lane
    wants another tiling of the pool than the others and XLA never copies
    it to a second layout.  Heads are split out only on gathered rows."""
    return {k: cache[k] for k in ("kp", "vp", "ks", "vs") if k in cache}


def attention_decode_paged(params: Params, x: jnp.ndarray, kv: Params,
                           layer: jnp.ndarray, block: jnp.ndarray,
                           pos: jnp.ndarray, *, num_heads: int, num_kv: int,
                           head_dim: int, rope_theta: float,
                           window: Optional[jnp.ndarray] = None,
                           use_kernel: bool = False,
                           write_block: Optional[jnp.ndarray] = None,
                           q_scale: float = 1.0, rows=None
                           ) -> Tuple[jnp.ndarray, Params]:
    """One-token decode of layer ``layer`` against a tier's STACKED paged KV
    pools, updated in place.

    x: (B, 1, D); kv: :func:`paged_kv` of the cache — ``kp``/``vp`` (L, P,
    page, K·Dh), every layer's pages in ONE physical allocation shared by
    every slot; layer: int32 scalar index into their leading axis; block:
    (B, n_pages) int32 block table mapping each slot's logical pages to
    physical ones (0 = the null/trash page); pos: (B,) int32 per-slot
    positions (continuous batching: slots decode at DIFFERENT positions,
    unlike the contiguous cache's single scalar).

    The new token's (K·Dh) K/V row is scattered into ``[layer, block[b,
    pos_b // page], pos_b % page]``; reads gather every slot's pages back
    through the table straight from the stacked pools (or stream the layer's pages inside the
    Pallas kernel when ``use_kernel``).  Masking is positional (``kpos <=
    pos_b``) so stale page contents are never observable.  Callers carry
    ``kv`` through their layer scan: with no layer sliced out and none
    stacked back, XLA updates the donated pools in place.

    ``write_block`` (defaults to ``block``): the table used for the APPEND
    only.  With prefix sharing, pages aliased by several slots are read-only
    — admission copy-on-writes any page a slot will append into, and the
    scheduler masks shared pages to the null page in ``write_block`` so a
    violated exclusivity invariant drops the write instead of corrupting a
    co-resident request's cache.

    ``ks``/``vs`` in ``kv`` mark the pools int8-quantized (per-page-per-head
    symmetric scales): the append quantizes through a monotone running-max
    page scale (see :func:`quant_append_page`) and the gather dequantizes —
    fused into the Pallas kernel under ``use_kernel``.  ``q_scale``: see
    :func:`rope_q`.

    ``rows`` = (slots (B,), branch, widths): read only the rows that
    :func:`over_rows` picks (the scheduler's occupied slots); the append
    stays at every row, an idle slot's going to the null page.

    Returns (out (B,1,D), kv).
    """
    b = x.shape[0]
    pool_k, pool_v = kv["kp"], kv["vp"]
    scale_k, scale_v = kv.get("ks"), kv.get("vs")
    page = pool_k.shape[2]
    n_pages = block.shape[1]
    s_tot = n_pages * page
    q, k, v = _qkv(params, x, num_heads, num_kv, head_dim)
    pq = pos[:, None]                        # (B, 1) absolute positions
    q = rope_q(q, pq, rope_theta, q_scale)
    if rope_theta > 0:
        k = apply_rope(k, pq, rope_theta)
    wb = block if write_block is None else write_block
    pg = wb[jnp.arange(b), pos // page]      # (B,) physical page of this token
    off = pos % page
    # duplicate (page 0) targets from idle slots race benignly: the null page
    # is never covered by any slot's positional mask
    if scale_k is not None:
        pool_k, scale_k = quant_append_page(pool_k, scale_k, pg, off, k[:, 0],
                                            layer=layer)
        pool_v, scale_v = quant_append_page(pool_v, scale_v, pg, off, v[:, 0],
                                            layer=layer)
        kv = {"kp": pool_k, "vp": pool_v, "ks": scale_k, "vs": scale_v}
    else:
        pool_k = pool_k.at[layer, pg, off].set(
            k[:, 0].reshape(b, -1).astype(pool_k.dtype), mode="drop")
        pool_v = pool_v.at[layer, pg, off].set(
            v[:, 0].reshape(b, -1).astype(pool_v.dtype), mode="drop")
        kv = {"kp": pool_k, "vp": pool_v}
    kpos = jnp.arange(s_tot)[None, :]        # logical key positions per slot
    valid = kpos <= pos[:, None]
    if window is not None:
        valid = valid & (pos[:, None] - kpos < window)

    def attend(q, block, valid):
        if use_kernel:
            # the kernel streams one layer's pool: pass it that layer's slice
            from repro.kernels import ops as kops
            sk, sv = (None, None) if scale_k is None else \
                (scale_k[layer], scale_v[layer])
            return kops.decode_attention_paged(q, pool_k[layer],
                                               pool_v[layer], block, valid,
                                               sk, sv)
        kk = dequant_gather(pool_k, scale_k, block, num_kv, head_dim,
                            layer=layer)
        vv = dequant_gather(pool_v, scale_v, block, num_kv, head_dim,
                            layer=layer)
        return _sdpa(q, kk, vv, valid[:, None, :])

    out = attend(q, block, valid) if rows is None else \
        over_rows(attend, rows, q, block, valid)
    out = out.reshape(b, 1, num_heads * head_dim) @ params["wo"]
    return out, kv


def over_rows(fn, rows, *args):
    """``fn(*args)`` over a subset of the batch rows, at a width chosen at
    run time.  ``rows`` = (slots (B,) int32, branch int32 scalar, widths):
    ``widths`` is an ascending static tuple ending at B, ``slots`` lists
    the rows to compute first and then the sentinel B.  A ``lax.switch`` on
    ``branch`` runs ``fn`` on the first ``widths[branch]`` entries of
    ``slots``, gathered (a pad entry reads zeros), and scatters the results
    back into B rows (a pad's drops; rows not listed read zeros); the last
    branch is ``fn`` over every row.  ``fn`` only reads what it closes over:
    the page pools enter the switch read-only, so the caller's in-place pool
    updates stay outside it."""
    slots, branch, widths = rows
    b = args[0].shape[0]

    def at(w):
        if w == b:
            return lambda a: fn(*a)

        def narrow(a):
            sel = slots[:w]
            out = fn(*(x.at[sel].get(mode="fill", fill_value=0) for x in a))
            return jnp.zeros((b,) + out.shape[1:], out.dtype).at[sel].set(
                out, mode="drop")
        return narrow

    return lax.switch(branch, [at(w) for w in widths], args)


def attention_chunk_paged(params: Params, x: jnp.ndarray, pool_k: jnp.ndarray,
                          pool_v: jnp.ndarray, block: jnp.ndarray,
                          pos: jnp.ndarray, *, num_heads: int, num_kv: int,
                          head_dim: int, rope_theta: float,
                          window: Optional[jnp.ndarray] = None,
                          use_kernel: bool = False,
                          write_block: Optional[jnp.ndarray] = None,
                          scale_k: Optional[jnp.ndarray] = None,
                          scale_v: Optional[jnp.ndarray] = None):
    """CHUNK attention against the paged KV pool: C tokens per slot at
    per-slot start positions — the multi-token generalisation of
    :func:`attention_decode_paged` that powers the chunked-prefill lane.

    x: (B, C, D); pos: (B,) int32 — token i of slot b sits at absolute
    position ``pos[b] + i``.  All C tokens' K/V are scattered into the slot's
    pages FIRST (through ``write_block``, shared pages masked to the null
    page), then every query gathers the slot's whole page row with a mask
    ``kpos <= pos[b] + i`` — positional masking supplies the intra-chunk
    causal structure, so query i sees exactly the keys a sequential
    ``attention_decode_paged`` at position ``pos[b] + i`` would have seen
    (same K/V values: both paths round to the cache dtype before the read).
    Positions past the slot's page row write to the null page.

    pool_k/v: one layer's (P, page, K·Dh) pools.  ``scale_k/v`` (P, K)
    fp32 mark the pools int8-quantized; the chunk's appends quantize
    through a monotone running-max page scale (whole chunk committed at the
    final scale — one rounding, vs the per-token path's potential requant,
    which is why chunk-vs-steps equivalence is tolerance-based under
    int8).

    Returns (out (B, C, D'), pool_k, pool_v) — plus (scale_k, scale_v) when
    quantized."""
    b, c, _ = x.shape
    page = pool_k.shape[1]
    n_pages = block.shape[1]
    s_tot = n_pages * page
    quant = scale_k is not None
    q, k, v = _qkv(params, x, num_heads, num_kv, head_dim)
    positions = pos[:, None] + jnp.arange(c)[None, :]       # (B, C) absolute
    if rope_theta > 0:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    wb = block if write_block is None else write_block
    logical = positions // page                              # (B, C)
    in_range = logical < n_pages
    rows = jnp.arange(b)[:, None]
    pg = jnp.where(in_range, wb[rows, jnp.minimum(logical, n_pages - 1)], 0)
    off = positions % page
    if quant:
        pool_k, scale_k = quant_append_page(pool_k, scale_k, pg, off, k)
        pool_v, scale_v = quant_append_page(pool_v, scale_v, pg, off, v)
    else:
        pool_k = pool_k.at[pg, off].set(
            k.reshape(b, c, -1).astype(pool_k.dtype), mode="drop")
        pool_v = pool_v.at[pg, off].set(
            v.reshape(b, c, -1).astype(pool_v.dtype), mode="drop")
    kpos = jnp.arange(s_tot)[None, None, :]
    valid = kpos <= positions[:, :, None]                    # (B, C, S_tot)
    if window is not None:
        valid = valid & (positions[:, :, None] - kpos < window)
    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.decode_attention_chunk_paged(q, pool_k, pool_v, block,
                                                valid, scale_k, scale_v)
    else:
        kk = dequant_gather(pool_k, scale_k, block, num_kv, head_dim)
        vv = dequant_gather(pool_v, scale_v, block, num_kv, head_dim)
        out = _sdpa(q, kk, vv, valid)
    out = out.reshape(b, c, num_heads * head_dim) @ params["wo"]
    if quant:
        return out, pool_k, pool_v, scale_k, scale_v
    return out, pool_k, pool_v


# ---------------------------------------------------------------------------
# int8 page quantization (per-page-per-head symmetric scales)
# ---------------------------------------------------------------------------
#
# An int8 pool (P, page, K·Dh) carries a (P, K) fp32 scale tensor: one
# symmetric scale per physical page per kv head, dequant = int8 * scale.
# Write contract:
#   * prefill overwrites whole pages -> scale rows use SET semantics
#     (``quant_scatter_prefill_pages``), so stale scales from recycled pages
#     never survive;
#   * decode/chunk appends grow a page token-by-token -> the page scale is a
#     MONOTONE running max (``quant_append_page``); growing it requantizes
#     the page's existing content to the new grid (ratio <= 1, one extra
#     rounding), and a token at page offset 0 resets the (recycled) scale
#     first since the page has no live content yet.
# Scale rows are indexed by physical page id exactly like pages, so COW /
# eviction move them with ``cow_copy_scales`` alongside
# ``cow_copy_pages`` and the refcount machinery never needs to know about
# quantization.

_QMAX = 127.0


def _safe_scale(s: jnp.ndarray) -> jnp.ndarray:
    return jnp.maximum(s, 1e-30)


def quant_append_page(pool: jnp.ndarray, scale: jnp.ndarray, pg: jnp.ndarray,
                      off: jnp.ndarray, val: jnp.ndarray,
                      layer: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Append token K/V into an int8 pool at (pg, off) under a monotone
    per-page-per-head running-max scale.

    pool: (P, page, K·Dh) int8; scale: (P, K) fp32 — or, with ``layer``, a
    stacked (L, P, page, K·Dh) pool and (L, P, K) scales updated at that
    layer in place; pg/off: (...,) int32 (token -> physical page / in-page
    offset); val: (..., K, Dh).  Pages whose scale grows are requantized to
    the new grid (duplicate pg entries write identical bytes — ratio and
    gathered content agree — so chunked appends race benignly, same as the
    null-page discipline).
    """
    lead = () if layer is None else (layer,)
    v32 = val.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(v32), axis=-1)                   # (..., K)
    # offset 0 == first write into a freshly allocated page: reset the
    # recycled page's stale scale so it cannot poison this page's precision
    # (off != 0 tokens harmlessly re-zero the never-dequant-read null page)
    scale = scale.at[lead + (jnp.where(off == 0, pg, 0),)].set(0.0,
                                                               mode="drop")
    old = scale[lead + (pg,)]                                 # (..., K)
    scale = scale.at[lead + (pg,)].max(absmax / _QMAX, mode="drop")
    new = scale[lead + (pg,)]
    ratio = jnp.clip(_safe_scale(old) / _safe_scale(new), 0.0, 1.0)
    rows = pool[lead + (pg,)]                           # (..., page, K·Dh)
    heads = rows.reshape(rows.shape[:-1] + val.shape[-2:])
    repack = jnp.round(heads.astype(jnp.float32) * ratio[..., None, :, None])
    pool = pool.at[lead + (pg,)].set(
        repack.reshape(rows.shape).astype(pool.dtype), mode="drop")
    q = jnp.clip(jnp.round(v32 / _safe_scale(new)[..., None]), -_QMAX, _QMAX)
    pool = pool.at[lead + (pg, off)].set(
        q.reshape(q.shape[:-2] + (-1,)).astype(pool.dtype), mode="drop")
    return pool, scale


def dequant_gather(pool: jnp.ndarray, scale: Optional[jnp.ndarray],
                   block: jnp.ndarray, num_kv: int, head_dim: int,
                   layer: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Gather a batch's pages as (B, n_pages * page, K, Dh), dequantizing
    through the per-page scales when given (None = full-precision pool:
    bitwise the plain gather).  pool: (P, page, K·Dh); with ``layer`` the
    pool (and scales) are stacked over layers and the pages come from that
    layer in one gather, with no layer slice in between.  The heads are
    split out of the gathered rows, never of the pool."""
    b, npg = block.shape
    lead = () if layer is None else (layer,)
    page = pool.shape[-2]
    gathered = pool[lead + (block,)].reshape(    # (B, npg, page, K, Dh)
        b, npg, page, num_kv, head_dim)
    if scale is not None:
        gathered = gathered.astype(jnp.float32) \
            * scale[lead + (block,)][:, :, None, :, None]
    return gathered.reshape(b, npg * page, num_kv, head_dim)


def quant_dequant_pages(kv: jnp.ndarray, page: int) -> jnp.ndarray:
    """Fake-quantize full-sequence K/V through the int8 per-page-per-head
    grid: exactly the values a later paged read will dequantize to
    (``quant_scatter_prefill_pages`` recomputes the identical scales from the
    same raw values).  kv: (A, S, K, Dh) with S % page == 0."""
    a, s = kv.shape[:2]
    paged = kv.astype(jnp.float32).reshape(a, s // page, page, *kv.shape[2:])
    sc = jnp.max(jnp.abs(paged), axis=(2, 4), keepdims=True) / _QMAX
    q = jnp.clip(jnp.round(paged / _safe_scale(sc)), -_QMAX, _QMAX)
    return (q * sc).reshape(kv.shape).astype(kv.dtype)


def quant_scatter_prefill_pages(pool: jnp.ndarray, scale: jnp.ndarray,
                                seq_kv: jnp.ndarray, block_rows: jnp.ndarray,
                                layer: Optional[int] = None
                                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantized :func:`scatter_prefill_pages`: write whole prefill pages
    int8 with freshly computed per-page-per-head scales (SET semantics —
    prefill owns the page, recycled scales are overwritten).  seq_kv carries
    the RAW (pre-quantization) values; rows redirected to the null page
    (padding / shared-prefix suffixing) drop both page and scale writes
    there harmlessly.  ``layer``: as for :func:`scatter_prefill_pages`."""
    lead = () if layer is None else (layer,)
    page = pool.shape[-2]
    a, s = seq_kv.shape[:2]
    paged = seq_kv.astype(jnp.float32).reshape(a, s // page, page,
                                               *seq_kv.shape[2:])
    rows = block_rows[:, : s // page]
    sc = jnp.max(jnp.abs(paged), axis=(2, 4)) / _QMAX         # (A, npg, K)
    q = jnp.clip(jnp.round(paged / _safe_scale(sc)[:, :, None, :, None]),
                 -_QMAX, _QMAX)
    pool = pool.at[lead + (rows,)].set(
        q.reshape(a, s // page, page, -1).astype(pool.dtype), mode="drop")
    scale = scale.at[lead + (rows,)].set(sc, mode="drop")
    return pool, scale


def cow_copy_scales(scale: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray
                    ) -> jnp.ndarray:
    """Scale-row companion of :func:`cow_copy_pages`: scale (..., P, K) with
    the page axis at ndim-2 (vs ndim-3 for pools); same (src, dst) pairs,
    same null-page padding discipline."""
    axis = scale.ndim - 2
    idx = (slice(None),) * axis + (dst,)
    return scale.at[idx].set(jnp.take(scale, src, axis=axis))


def scatter_prefill_pages(pool: jnp.ndarray, seq_kv: jnp.ndarray,
                          block_rows: jnp.ndarray,
                          layer: Optional[int] = None) -> jnp.ndarray:
    """Write a batch of sequences' prefill K (or V) into their pages.

    pool: (P, page, K·Dh) — or, with ``layer``, a stacked (L, P, page,
    K·Dh) pool written at that layer in place; seq_kv: (A, S, K, Dh) with
    S % page == 0, written as (A, S // page, page, K·Dh) page rows;
    block_rows: (A, n_pages) — only the first S // page entries of each row
    are written.  Rows belonging to masked/padded admissions point at the
    null page 0; their (raced, garbage) writes land there harmlessly.
    """
    lead = () if layer is None else (layer,)
    page = pool.shape[-2]
    a, s = seq_kv.shape[:2]
    paged = seq_kv.reshape(a, s // page, page, -1)
    return pool.at[lead + (block_rows[:, : s // page],)].set(
        paged.astype(pool.dtype), mode="drop")


def suffix_write_rows(block_rows: jnp.ndarray, start: jnp.ndarray,
                      n_pages: int, page: int) -> jnp.ndarray:
    """Mask a batch of block-table rows down to the UNCACHED suffix.

    Pages below ``start // page`` belong to the shared prefix (aliased,
    possibly referenced by other slots or the prefix index) — they are
    read-only, so their prefill re-writes are redirected to the null page.
    ``start`` is page-aligned for partial hits and == bucket for full
    restores (which write nothing).
    """
    page_idx = jnp.arange(n_pages)[None, :]
    return jnp.where(page_idx < (start // page)[:, None], 0,
                     block_rows[:, :n_pages])


def substitute_prefix_kv(pool: jnp.ndarray, inpass: jnp.ndarray,
                         block_rows: jnp.ndarray, start: jnp.ndarray,
                         scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Splice cached prefix K (or V) under the in-pass suffix values.

    pool: (P, page, Kh·Dh); inpass: (A, S, Kh, Dh); block_rows: (A, n_pages);
    start: (A,) first uncached position per row.  Positions < start read the
    slot's aliased pages (bitwise the values the row's own full prefill would
    have produced — the whole sharing-equivalence argument rests on this);
    positions >= start keep the in-pass values.  The result feeds the SAME
    attention as the non-sharing path, so suffix logits and suffix K/V are
    bitwise identical to a from-scratch prefill.

    ``scale`` (P, K) marks the pool int8: cached pages dequantize through
    their per-page scales (and ``inpass`` is expected fake-quantized through
    the same grid — see :func:`quant_dequant_pages`).
    """
    a, s = inpass.shape[:2]
    page = pool.shape[1]
    rows = block_rows[:, : s // page]
    cached = pool[rows].reshape(a, s // page, page, *inpass.shape[2:])
    if scale is not None:
        cached = cached.astype(jnp.float32) * scale[rows][:, :, None, :, None]
    cached = cached.reshape(a, s, *inpass.shape[2:])
    pos = jnp.arange(s)[None, :, None, None]
    return jnp.where(pos < start[:, None, None, None],
                     cached.astype(inpass.dtype), inpass)


def cow_copy_pages(pool: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray
                   ) -> jnp.ndarray:
    """Copy-on-write page duplication: pool[..., dst_i, :] = pool[..., src_i, :].

    pool: (..., P, page, K·Dh) with the page axis at ndim-3; src/dst: (C,)
    int32 physical page ids, padded with (0, 0) pairs — the null page copied
    onto itself is a no-op by construction.  Runs at the top of the scheduler
    tick, BEFORE prefill and decode, so an appending slot always owns a
    private copy of a retained tail page.
    """
    axis = pool.ndim - 3
    idx = (slice(None),) * axis + (dst,)
    return pool.at[idx].set(jnp.take(pool, src, axis=axis))


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def swiglu_init(rng, d_model: int, d_ff: int, dtype=jnp.float32) -> Params:
    ks = jax.random.split(rng, 3)
    return {
        "wi": dense_init(ks[0], d_model, d_ff, dtype),
        "wg": dense_init(ks[1], d_model, d_ff, dtype),
        "wo": dense_init(ks[2], d_ff, d_model, dtype),
    }


def swiglu(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    return (jax.nn.silu(x @ params["wg"]) * (x @ params["wi"])) @ params["wo"]


def gelu_mlp_init(rng, d_model: int, d_ff: int, dtype=jnp.float32) -> Params:
    ks = jax.random.split(rng, 2)
    return {"wi": dense_init(ks[0], d_model, d_ff, dtype),
            "wo": dense_init(ks[1], d_ff, d_model, dtype),
            "bi": jnp.zeros((d_ff,), dtype), "bo": jnp.zeros((d_model,), dtype)}


def gelu_mlp(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.gelu(x @ params["wi"] + params["bi"]) @ params["wo"] + params["bo"]
