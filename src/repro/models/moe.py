"""Mixture-of-Experts decoder (deepseek-moe fine-grained, arctic dense-residual).

Dispatch is the capacity-bounded scatter idiom (the production MoE pattern on
TPU): tokens pick top-k experts, each expert owns a static ``capacity`` slot
buffer, overflow tokens are dropped (drop rate is reported by the metrics).
This is also *exactly* the mechanism HI's sample router reuses one level up —
see DESIGN.md §2.

Expert weights are sharded expert-parallel over the ``model`` mesh axis (and
their hidden dim over ``data`` for the very large configs).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.sharding import act

Params = Dict[str, Any]


def moe_capacity(num_tokens: int, num_experts: int, k: int,
                 factor: float = 1.25) -> int:
    """Static per-expert slot count."""
    return max(1, int(math.ceil(num_tokens * k / num_experts * factor)))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_experts(rng, num_experts: int, d_model: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(rng, num_experts)
    return jax.vmap(lambda r: L.swiglu_init(r, d_model, d_ff, dtype))(ks)


def _init_layer(rng, cfg: ModelConfig, dtype) -> Params:
    k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
    p: Params = {
        "ln1": L.rmsnorm_init(cfg.d_model, dtype),
        "attn": L.attention_init(k1, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                                 cfg.resolved_head_dim, bias=cfg.qkv_bias,
                                 dtype=dtype),
        "ln2": L.rmsnorm_init(cfg.d_model, dtype),
        "router": L.dense_init(k2, cfg.d_model, cfg.num_experts, jnp.float32),
        "experts": _init_experts(k3, cfg.num_experts, cfg.d_model,
                                 cfg.d_ff_expert, dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = L.swiglu_init(
            k4, cfg.d_model, cfg.num_shared_experts * cfg.d_ff_expert, dtype)
    if cfg.moe_dense_residual:
        p["dense"] = L.swiglu_init(k5, cfg.d_model, cfg.d_ff, dtype)
    return p


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


# ---------------------------------------------------------------------------
# routed FFN
# ---------------------------------------------------------------------------

def moe_ffn(lp: Params, cfg: ModelConfig, x: jnp.ndarray,
            capacity_factor: float = 1.25) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (y, aux_loss).

    Scatter-based dispatch: (E, C, D) expert buffers; no (T, E, C) one-hot
    tensor is ever materialised (it would not fit for 1M-token batches).
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    t = b * s
    cap = moe_capacity(t, e, k, capacity_factor)
    xf = x.reshape(t, d)

    logits = (xf.astype(jnp.float32) @ lp["router"])              # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = lax.top_k(probs, k)                                # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)   # renorm

    # Per-choice dispatch loop (k is small and static): avoids both the
    # (T*k, E) one-hot and the repeated-token (T*k, D) buffer.  Slot order is
    # an arbitrary bijection, which is fine — only the drop *policy* differs.
    buf = jnp.zeros((e, cap, d), x.dtype)
    base = jnp.zeros((e,), jnp.int32)                              # slots used
    poss, keeps = [], []
    for j in range(k):
        oh = jax.nn.one_hot(idx[:, j], e, dtype=jnp.int32)         # (T, E)
        pos_j = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - 1,
                                    idx[:, j:j + 1], axis=1)[:, 0]
        pos_j = pos_j + base[idx[:, j]]
        keep_j = pos_j < cap
        pos_cj = jnp.minimum(pos_j, cap - 1)
        src = xf * keep_j[:, None].astype(x.dtype)
        buf = buf.at[idx[:, j], pos_cj].add(src)
        base = base + oh.sum(axis=0)
        poss.append(pos_cj)
        keeps.append(keep_j)
    buf = act.shard_experts(buf)   # expert-parallel over `model`

    # expert compute, batched over E
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, lp["experts"]["wg"])) * \
        jnp.einsum("ecd,edf->ecf", buf, lp["experts"]["wi"])
    out = jnp.einsum("ecf,efd->ecd", h, lp["experts"]["wo"])       # (E, C, D)

    # combine
    y = jnp.zeros((t, d), x.dtype)
    for j in range(k):
        w_j = (gate[:, j] * keeps[j]).astype(x.dtype)
        y = y + out[idx[:, j], poss[j]] * w_j[:, None]
    y = y.reshape(b, s, d)

    # load-balancing aux loss (Switch-style)
    frac_tokens = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return y, aux


# ---------------------------------------------------------------------------
# shard_map expert parallelism (the production dispatch)
# ---------------------------------------------------------------------------
#
# The pjit/scatter dispatch above computes slot positions with a cumsum over
# the GLOBAL token dim; under GSPMD that becomes cross-shard prefix sums plus
# a global scatter/gather into the expert buffers — measured at ~2.5 TB/chip
# of all-reduce per train step on deepseek-moe-16b (EXPERIMENTS.md §Perf).
# The shard_map version keeps routing completely shard-local: tokens stay on
# their `data` shard (replicated across `model`), every (data, model) device
# dispatches its local tokens to its local experts, and one bf16 psum over
# `model` combines the expert partial outputs.  Comms per layer = exactly one
# (T_local, D) psum.

def moe_ffn_sharded(lp: Params, cfg: ModelConfig, x: jnp.ndarray,
                    capacity_factor: float, mesh) -> Tuple[jnp.ndarray, jnp.ndarray]:
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    n_model = mesh.shape["model"]
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    e_local = e // n_model
    t_local = (b // n_data) * s
    cap = moe_capacity(t_local, e, k, capacity_factor)

    # --- schedule choice: move the SMALLER operand across `data` ----------
    # weights-move: all-gather the (E_l, D, F) expert weights per call
    # tokens-move:  all-gather the (E_l, cap, D) dispatch buffers, compute
    #               against F-sharded weights, reduce-scatter the outputs
    f = cfg.d_ff_expert
    weights_bytes = 3 * e_local * d * f * 2
    tokens_bytes = 2 * e_local * cap * n_data * d * 2
    tokens_move = tokens_bytes < weights_bytes

    def local(x_blk, router_w, wi, wg, wo):
        bl, sl, _ = x_blk.shape
        xf = x_blk.reshape(bl * sl, d)
        m_idx = jax.lax.axis_index("model")
        logits = xf.astype(jnp.float32) @ router_w                # (T_l, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = lax.top_k(probs, k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

        lo = m_idx * e_local
        buf = jnp.zeros((e_local, cap, d), x_blk.dtype)
        base = jnp.zeros((e_local,), jnp.int32)
        poss, keeps, locals_ = [], [], []
        for j in range(k):
            eid = idx[:, j]
            is_local = (eid >= lo) & (eid < lo + e_local)
            lid = jnp.where(is_local, eid - lo, 0)
            oh = (jax.nn.one_hot(lid, e_local, dtype=jnp.int32)
                  * is_local[:, None])
            pos_j = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - 1,
                                        lid[:, None], axis=1)[:, 0]
            pos_j = pos_j + base[lid]
            keep_j = is_local & (pos_j < cap) & (pos_j >= 0)
            pos_cj = jnp.clip(pos_j, 0, cap - 1)
            buf = buf.at[lid, pos_cj].add(
                xf * keep_j[:, None].astype(x_blk.dtype))
            base = base + oh.sum(axis=0)
            poss.append(pos_cj); keeps.append(keep_j); locals_.append(lid)

        if tokens_move:
            # weights stay F-sharded over `data`; the (small) token buffers
            # travel: AG tokens -> local matmuls -> RS partial outputs
            buf_all = lax.all_gather(buf, data_axes, axis=1, tiled=True)
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf_all, wg)) * \
                jnp.einsum("ecd,edf->ecf", buf_all, wi)
            out_part = jnp.einsum("ecf,efd->ecd", h, wo)   # partial over F
            out = lax.psum_scatter(out_part, data_axes, scatter_dimension=1,
                                   tiled=True)             # (E_l, cap, D)
        else:
            # small experts: gather full-F weights, tokens stay put
            wi_f = lax.all_gather(wi, data_axes, axis=2, tiled=True)
            wg_f = lax.all_gather(wg, data_axes, axis=2, tiled=True)
            wo_f = lax.all_gather(wo, data_axes, axis=1, tiled=True)
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg_f)) * \
                jnp.einsum("ecd,edf->ecf", buf, wi_f)
            out = jnp.einsum("ecf,efd->ecd", h, wo_f)

        y = jnp.zeros((bl * sl, d), x_blk.dtype)
        for j in range(k):
            w_j = (gate[:, j] * keeps[j]).astype(x_blk.dtype)
            y = y + out[locals_[j], poss[j]] * w_j[:, None]
        y = lax.psum(y, "model")                       # combine expert parts

        frac_tokens = jnp.mean(jax.nn.one_hot(idx[:, 0], e,
                                              dtype=jnp.float32), axis=0)
        frac_probs = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(frac_tokens * frac_probs)
        aux = lax.pmean(aux, data_axes)   # identical across `model` already
        return y.reshape(bl, sl, d), aux

    d_ax = data_axes if data_axes else None
    wi_spec = P("model", None, d_ax)          # F-sharded storage (both paths)
    wo_spec = P("model", d_ax, None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(data_axes, None, None), P(None, None),
                  wi_spec, wi_spec, wo_spec),
        out_specs=(P(data_axes, None, None), P()),
        check_vma=False)
    return fn(x, lp["router"], lp["experts"]["wi"], lp["experts"]["wg"],
              lp["experts"]["wo"])


def moe_ffn_auto(lp: Params, cfg: ModelConfig, x: jnp.ndarray,
                 capacity_factor: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pick the shard_map expert-parallel dispatch when running under a mesh
    whose `model` axis divides the expert count; else the local scatter."""
    from repro.sharding import act
    mesh = act.current_mesh()
    if (mesh is not None and "model" in mesh.shape
            and cfg.num_experts % mesh.shape["model"] == 0
            and x.shape[0] % max(mesh.shape.get("data", 1), 1) == 0):
        return moe_ffn_sharded(lp, cfg, x, capacity_factor, mesh)
    return moe_ffn(lp, cfg, x, capacity_factor)


def _block(lp: Params, cfg: ModelConfig, x: jnp.ndarray, win,
           capacity_factor: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    x = act.shard_hidden(x)
    a = L.attention_forward(lp["attn"], L.rmsnorm(lp["ln1"], x, cfg.norm_eps),
                            num_heads=cfg.num_heads, num_kv=cfg.num_kv_heads,
                            head_dim=cfg.resolved_head_dim,
                            rope_theta=cfg.rope_theta, window=win)
    x = x + a
    xn = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
    y, aux = moe_ffn_auto(lp, cfg, xn, capacity_factor)
    if "shared" in lp:
        y = y + L.swiglu(lp["shared"], xn)
    if "dense" in lp:
        y = y + L.swiglu(lp["dense"], xn)
    return act.shard_hidden(x + y), aux


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray, *,
            remat: bool = False, capacity_factor: float = 1.25,
            last_only: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens (B, S) -> (logits (B, S, V) fp32, mean aux loss)."""
    h = params["embed"][tokens]
    seq = h.shape[1]
    win = jnp.asarray(seq, jnp.int32)

    def body(carry, lp):
        x, _ = carry, None
        x, aux = _block(lp, cfg, x, win, capacity_factor)
        return x, aux

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    h, auxs = lax.scan(body, act.shard_hidden(h), params["layers"])
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    if last_only:
        h = h[:, -1:]
    logits = act.shard_logits((h @ params["lm_head"]).astype(jnp.float32))
    return logits, jnp.mean(auxs)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=jnp.bfloat16):
    from repro.models import transformer
    return transformer.init_cache(cfg, batch, seq_len, dtype)


def decode_step(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                cache: Params, *, capacity_factor: float = 2.0,
                use_kernel: bool = False) -> Tuple[jnp.ndarray, Params]:
    h = params["embed"][token]
    pos = cache["pos"]
    seq = cache["k"].shape[2]
    win = jnp.asarray(seq, jnp.int32)

    def body(carry, xs):
        x = carry
        lp, ck, cv = xs
        a, ck, cv = L.attention_decode(lp["attn"],
                                       L.rmsnorm(lp["ln1"], x, cfg.norm_eps),
                                       ck, cv, pos,
                                       num_heads=cfg.num_heads,
                                       num_kv=cfg.num_kv_heads,
                                       head_dim=cfg.resolved_head_dim,
                                       rope_theta=cfg.rope_theta, window=win,
                                       use_kernel=use_kernel)
        x = x + a
        xn = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
        y, _ = moe_ffn_auto(lp, cfg, xn, capacity_factor)
        if "shared" in lp:
            y = y + L.swiglu(lp["shared"], xn)
        if "dense" in lp:
            y = y + L.swiglu(lp["dense"], xn)
        return x + y, (ck, cv)

    h, (nk, nv) = lax.scan(body, h, (params["layers"], cache["k"], cache["v"]))
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h[:, 0, :] @ params["lm_head"]).astype(jnp.float32)
    return logits, {"k": nk, "v": nv, "pos": pos + 1}


def _prefill_body(cfg: ModelConfig, s: int, b: int, kv_dtype,
                  capacity_factor: float, block_rows=None, start=None,
                  page: int = 0, quant: bool = False):
    """The per-layer prefill scan body shared by :func:`prefill` (contiguous
    cache) and :func:`prefill_paged` (page pool).  Emits (k, v) per layer for
    the caller to store.

    With ``block_rows``/``start`` (prefix sharing) the scan also carries the
    layer's page pool and splices cached-prefix K/V under the in-pass values
    (see ``layers.substitute_prefix_kv``); routed-expert dispatch still runs
    over every position — the batch composition, and therefore the capacity
    drops, stay identical to the non-sharing pass."""
    hd = cfg.resolved_head_dim
    win = jnp.asarray(s, jnp.int32)
    pos = jnp.arange(s)
    mask = L.causal_window_mask(s, s, window=win)
    prefix = start is not None

    def body(carry, xs):
        if prefix and quant:
            lp, pk, pv, sk, sv = xs
        elif prefix:
            lp, pk, pv = xs
        else:
            lp = xs
        x = act.shard_hidden(carry)
        xq = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
        q, k, v = L._qkv(lp["attn"], xq, cfg.num_heads, cfg.num_kv_heads, hd)
        if cfg.rope_theta > 0:
            q = L.apply_rope(q, pos, cfg.rope_theta)
            k = L.apply_rope(k, pos, cfg.rope_theta)
        if quant:
            # in-pass attention sees the fake-quantized values later paged
            # reads dequantize to; the RAW values are emitted for the
            # caller's quantize-on-write (see transformer._prefill_body)
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
        a = L._sdpa(q, k, v, mask)
        x = x + a.reshape(b, s, cfg.num_heads * hd) @ lp["attn"]["wo"]
        xn = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
        y, _ = moe_ffn_auto(lp, cfg, xn, capacity_factor)
        if "shared" in lp:
            y = y + L.swiglu(lp["shared"], xn)
        if "dense" in lp:
            y = y + L.swiglu(lp["dense"], xn)
        return act.shard_hidden(x + y), ((k_raw, v_raw) if quant else (k, v))

    return body


def init_paged_cache(cfg: ModelConfig, num_slots: int, num_pages: int,
                     page_size: int, dtype=jnp.bfloat16):
    from repro.models import transformer
    return transformer.init_paged_cache(cfg, num_slots, num_pages, page_size,
                                        dtype)


def prefill_paged(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  lengths: jnp.ndarray, slots: jnp.ndarray,
                  block_rows: jnp.ndarray, cache: Params, *,
                  capacity_factor: float = 2.0,
                  start=None) -> Tuple[jnp.ndarray, Params]:
    """Paged batched admission prefill (see transformer.prefill_paged).

    Routed dispatch runs over all padded (A, S_max) token rows together; the
    padded tails do consume expert capacity, so keep ``capacity_factor``
    generous (the decode-path default) — drops on the tails cannot corrupt
    real positions, but drops caused BY the tails could.

    ``start`` (prefix sharing): cached positions read their K/V from the
    aliased pages and skip the page writes; NOTE the routed dispatch remains
    batch-coupled, so unlike the dense families a page's content can depend
    on which rows were co-admitted when it was first filled (capacity drops)
    — reuse is exact only up to routing-drop determinism.
    """
    del slots
    h = params["embed"][tokens]
    b, s, _ = h.shape
    page = cache["kp"].shape[2]
    npg = s // page
    quant = "ks" in cache
    if start is None:
        body = _prefill_body(cfg, s, b, cache["kp"].dtype, capacity_factor,
                             page=page, quant=quant)
        h, (ks, vs) = lax.scan(body, h, params["layers"])
        wrows = block_rows[:, :npg]
    else:
        body = _prefill_body(cfg, s, b, cache["kp"].dtype, capacity_factor,
                             block_rows, start, page=page, quant=quant)
        xs = (params["layers"], cache["kp"], cache["vp"])
        if quant:
            xs = xs + (cache["ks"], cache["vs"])
        h, (ks, vs) = lax.scan(body, h, xs)
        wrows = L.suffix_write_rows(block_rows, start, npg, page)
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    h = jnp.take_along_axis(h, (lengths - 1)[:, None, None], axis=1)
    logits = (h[:, 0, :] @ params["lm_head"]).astype(jnp.float32)
    if quant:
        new_k, new_sk = jax.vmap(
            lambda p, sc, kv: L.quant_scatter_prefill_pages(p, sc, kv, wrows)
        )(cache["kp"], cache["ks"], ks)
        new_v, new_sv = jax.vmap(
            lambda p, sc, kv: L.quant_scatter_prefill_pages(p, sc, kv, wrows)
        )(cache["vp"], cache["vs"], vs)
        return logits, {"kp": new_k, "vp": new_v, "ks": new_sk, "vs": new_sv}
    shape = ks.shape[:1] + (b, npg, page, -1)
    new_k = cache["kp"].at[:, wrows].set(ks.reshape(shape), mode="drop")
    new_v = cache["vp"].at[:, wrows].set(vs.reshape(shape), mode="drop")
    return logits, {"kp": new_k, "vp": new_v}


def decode_step_paged(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                      pos: jnp.ndarray, block: jnp.ndarray, cache: Params, *,
                      capacity_factor: float = 2.0, use_kernel: bool = False,
                      write_block=None, rows=None
                      ) -> Tuple[jnp.ndarray, Params]:
    """One decode step for all slots at per-slot positions (paged pool);
    ``rows`` as in ``layers.attention_decode_paged``."""
    h = params["embed"][token]
    page = cache["kp"].shape[2]
    s_tot = block.shape[1] * page
    win = jnp.asarray(s_tot, jnp.int32)

    def body(carry, xs):
        x, kv = carry
        lp, li = xs
        a, kv = L.attention_decode_paged(
            lp["attn"], L.rmsnorm(lp["ln1"], x, cfg.norm_eps), kv, li,
            block, pos, num_heads=cfg.num_heads, num_kv=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            window=win, use_kernel=use_kernel, write_block=write_block,
            rows=rows)
        x = x + a
        xn = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
        y, _ = moe_ffn_auto(lp, cfg, xn, capacity_factor)
        if "shared" in lp:
            y = y + L.swiglu(lp["shared"], xn)
        if "dense" in lp:
            y = y + L.swiglu(lp["dense"], xn)
        return (x + y, kv), None

    (h, kv), _ = lax.scan(body, (h, L.paged_kv(cache)),
                          (params["layers"],
                           jnp.arange(cache["kp"].shape[0])))
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h[:, 0, :] @ params["lm_head"]).astype(jnp.float32)
    return logits, {**cache, **kv}


def forward_chunk_paged(params: Params, cfg: ModelConfig,
                        tokens: jnp.ndarray, pos: jnp.ndarray,
                        block: jnp.ndarray, cache: Params, *,
                        capacity_factor: float = 2.0,
                        use_kernel: bool = False,
                        write_block=None) -> Tuple[jnp.ndarray, Params, dict]:
    """Chunked token lane for the MoE family: the SAME spliced attention +
    page writes as the dense chunk pass, with the routed FFN dispatched over
    all (B, C) chunk tokens together.  Routed dispatch stays batch-coupled
    (as in ``prefill_paged``) — keep ``capacity_factor`` at the generous
    decode-path default so chunk co-residency cannot introduce drops.
    Returns (logits (B, C, V) fp32, cache, staged — empty, attention state
    is positional)."""
    h = params["embed"][tokens]
    page = cache["kp"].shape[2]
    s_tot = block.shape[1] * page
    win = jnp.asarray(s_tot, jnp.int32)
    quant = "ks" in cache

    def body(carry, xs):
        x = carry
        if quant:
            lp, pk, pv, sk, sv = xs
            a, pk, pv, sk, sv = L.attention_chunk_paged(
                lp["attn"], L.rmsnorm(lp["ln1"], x, cfg.norm_eps), pk, pv,
                block, pos, num_heads=cfg.num_heads, num_kv=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
                window=win, use_kernel=use_kernel, write_block=write_block,
                scale_k=sk, scale_v=sv)
        else:
            lp, pk, pv = xs
            a, pk, pv = L.attention_chunk_paged(
                lp["attn"], L.rmsnorm(lp["ln1"], x, cfg.norm_eps), pk, pv,
                block, pos, num_heads=cfg.num_heads, num_kv=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
                window=win, use_kernel=use_kernel, write_block=write_block)
        x = x + a
        xn = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
        y, _ = moe_ffn_auto(lp, cfg, xn, capacity_factor)
        if "shared" in lp:
            y = y + L.swiglu(lp["shared"], xn)
        if "dense" in lp:
            y = y + L.swiglu(lp["dense"], xn)
        return x + y, ((pk, pv, sk, sv) if quant else (pk, pv))

    if quant:
        h, (nk, nv, nsk, nsv) = lax.scan(
            body, h, (params["layers"], cache["kp"], cache["vp"],
                      cache["ks"], cache["vs"]))
    else:
        h, (nk, nv) = lax.scan(body, h, (params["layers"], cache["kp"],
                                         cache["vp"]))
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h @ params["lm_head"]).astype(jnp.float32)
    if quant:
        return logits, {"kp": nk, "vp": nv, "ks": nsk, "vs": nsv}, {}
    return logits, {"kp": nk, "vp": nv}, {}


def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            cache: Params, *, capacity_factor: float = 2.0
            ) -> Tuple[jnp.ndarray, Params]:
    """Consume the whole (B, S) prompt in one batched pass and write the KV
    cache.  ``capacity_factor`` defaults to the decode-path value so routed
    dispatch behaves like generation, not training.  ``cache`` supplies the
    buffers and is overwritten (donation-safe).

    Returns (last-token logits (B, V) fp32, filled cache).
    """
    h = params["embed"][tokens]
    b, s, _ = h.shape
    body = _prefill_body(cfg, s, b, cache["k"].dtype, capacity_factor)
    h, (ks, vs) = lax.scan(body, h, params["layers"])
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h[:, -1, :] @ params["lm_head"]).astype(jnp.float32)
    new_k = lax.dynamic_update_slice(cache["k"], ks, (0, 0, 0, 0, 0))
    new_v = lax.dynamic_update_slice(cache["v"], vs, (0, 0, 0, 0, 0))
    return logits, {"k": new_k, "v": new_v, "pos": jnp.asarray(s, jnp.int32)}
