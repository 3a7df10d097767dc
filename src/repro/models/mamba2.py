"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060) in pure JAX.

Train/prefill use the chunked SSD algorithm: quadratic attention-like compute
inside fixed-size chunks + a linear inter-chunk state recurrence (lax.scan).
Decode is the O(1)-per-token recurrent update on the cached SSM state.

``kernels/ssd_scan.py`` provides the Pallas TPU kernel for the per-chunk
compute; this module is also its pure-jnp oracle entry point.
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
# SSD core (chunked) — pure jnp
# ---------------------------------------------------------------------------

def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                B: jnp.ndarray, C: jnp.ndarray, chunk: int,
                init_state: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SSD over a sequence.

    x:  (b, l, h, p)   per-head inputs
    dt: (b, l, h)      positive step sizes (softplus already applied)
    A:  (h,)           negative decay rates
    B:  (b, l, G, n)   input projections of G groups; head j reads group
                       j // (h / G)
    C:  (b, l, G, n)   output projections, grouped alike
    Returns (y (b,l,h,p), final_state (b,h,p,n)).
    """
    b, l, h, p = x.shape
    g, n = B.shape[-2:]
    hg = h // g
    chunk = min(chunk, l)
    orig_l = l
    if l % chunk:
        # pad with dt=0 steps: decay=1 and dx=0, so padding is a no-op on state
        pad = chunk - l % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        l = l + pad
    nc = l // chunk

    f32 = jnp.float32
    # heads split as (group, head in group) so each reads its group's B/C
    a = (dt.astype(f32) * A.astype(f32)).reshape(b, nc, chunk, g, hg)    # log-decay
    dx = (x.astype(f32) * dt.astype(f32)[..., None]).reshape(b, nc, chunk, g, hg, p)
    Bc = B.astype(f32).reshape(b, nc, chunk, g, n)
    Cc = C.astype(f32).reshape(b, nc, chunk, g, n)

    a_cs = jnp.cumsum(a, axis=2)                                         # (b,nc,q,g,hg)

    # --- intra-chunk (diagonal blocks): attention-like with decay mask
    # L[i, j] = exp(a_cs[i] - a_cs[j]) for i >= j else 0
    decay = jnp.exp(a_cs[:, :, :, None] - a_cs[:, :, None, :])           # (b,nc,i,j,g,hg)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(tri[None, None, :, :, None, None], decay, 0.0)
    cb = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc)                        # (b,nc,g,i,j)
    y_diag = jnp.einsum("bcgij,bcijgh,bcjghp->bcighp", cb, decay, dx)

    # --- chunk summary states: S_c = sum_j exp(a_end - a_cs[j]) dx_j B_j^T
    decay_to_end = jnp.exp(a_cs[:, :, -1:] - a_cs)                       # (b,nc,q,g,hg)
    S = jnp.einsum("bcjgh,bcjghp,bcjgn->bcghpn", decay_to_end, dx, Bc)
    S = S.reshape(b, nc, h, p, n)

    # --- inter-chunk recurrence
    gd = jnp.exp(a_cs[:, :, -1]).reshape(b, nc, h)                       # (b,nc,h)
    h0 = jnp.zeros((b, h, p, n), f32) if init_state is None \
        else init_state.astype(f32)

    def step(hprev, xs):
        g_c, S_c = xs
        hnew = g_c[:, :, None, None] * hprev + S_c
        return hnew, hprev

    hT, h_prevs = lax.scan(step, h0, (gd.transpose(1, 0, 2), S.transpose(1, 0, 2, 3, 4)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4).reshape(b, nc, g, hg, p, n)

    # --- inter-chunk contribution: y_off[i] = exp(a_cs[i]) C_i . h_prev
    y_off = jnp.einsum("bcigh,bcign,bcghpn->bcighp",
                       jnp.exp(a_cs), Cc, h_prevs)

    y = (y_diag + y_off).reshape(b, l, h, p)[:, :orig_l]
    return y.astype(x.dtype), hT


def ssd_recurrent_step(state: jnp.ndarray, x_t: jnp.ndarray, dt_t: jnp.ndarray,
                       A: jnp.ndarray, B_t: jnp.ndarray, C_t: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One decode step.

    state: (b, p, n, h), heads minor (see :func:`state_to_cache`); x_t:
    (b, h, p); dt_t: (b, h); B_t/C_t: (b, G, n).
    Returns (y_t (b, h, p), new_state (b, p, n, h)).  Elementwise products
    and a reduction over n, in the state's own layout.
    """
    f32 = jnp.float32
    rep = x_t.shape[1] // B_t.shape[1]
    Bh = jnp.repeat(B_t.astype(f32), rep, axis=1).swapaxes(1, 2)         # (b,n,h)
    Ch = jnp.repeat(C_t.astype(f32), rep, axis=1).swapaxes(1, 2)
    da = jnp.exp(dt_t.astype(f32) * A.astype(f32))                       # (b,h)
    dx = (x_t.astype(f32) * dt_t.astype(f32)[..., None]).swapaxes(1, 2)  # (b,p,h)
    new_state = (da[:, None, None, :] * state.astype(f32)
                 + dx[:, :, None, :] * Bh[:, None, :, :])
    y = jnp.sum(new_state * Ch[:, None, :, :], axis=2)                   # (b,p,h)
    return y.swapaxes(1, 2).astype(x_t.dtype), new_state


def state_to_cache(state: jnp.ndarray) -> jnp.ndarray:
    """(..., h, p, n) -> the cache's (..., p, n, h): heads minor, the layout
    the decode step's elementwise update keeps (lane-dense at 112 heads,
    where n = 64 minor would pad to twice its bytes)."""
    return jnp.moveaxis(state, -3, -1)


# ---------------------------------------------------------------------------
# causal depthwise conv (width W, per-channel)
# ---------------------------------------------------------------------------

def causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """x: (B, L, C); w: (W, C); b: (C,).  Shift-and-sum (W is tiny)."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(W))
    return out + b


def causal_conv_step(window: jnp.ndarray, x_t: jnp.ndarray, w: jnp.ndarray,
                     b: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """window: (B, W-1, C) previous inputs; x_t: (B, 1, C).

    Returns (y_t (B, 1, C), new window)."""
    full = jnp.concatenate([window, x_t], axis=1)                        # (B, W, C)
    y = jnp.einsum("bwc,wc->bc", full, w) + b
    # keep the rolled window in the cache dtype: the concat above promotes to
    # the (fp32) activation dtype, which would change the decode-scan carry
    # type step-over-step and break jitted generation loops
    return y[:, None, :], full[:, 1:, :].astype(window.dtype)


# ---------------------------------------------------------------------------
# block init / forward
# ---------------------------------------------------------------------------

def conv_dim(cfg: ModelConfig) -> int:
    """Channels of the causal conv: x, then the G groups' B and C."""
    return cfg.ssm_d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state


def init_mamba_block(rng, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    d, di = cfg.d_model, cfg.ssm_d_inner
    h, W = cfg.ssm_nheads, cfg.ssm_conv_width
    k1, k2, k3 = jax.random.split(rng, 3)
    w_in = L.dense_init(k1, d, di + conv_dim(cfg) + h, dtype)
    return {
        "ln": L.rmsnorm_init(d, dtype),
        # the input projection in three matrices, [z | xBC | dt]: each of
        # the first two has a 128-multiple width at real sizes, so the
        # device keeps its rows in one layout for every lane
        "in_z": w_in[:, :di], "in_xbc": w_in[:, di:di + conv_dim(cfg)],
        "in_dt": w_in[:, di + conv_dim(cfg):],
        "conv_w": (jax.random.normal(k2, (W, conv_dim(cfg))) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_dim(cfg),), dtype),
        "A_log": jnp.zeros((h,), jnp.float32),          # A = -exp(A_log) = -1
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm": L.rmsnorm_init(di, dtype),
        "out_proj": L.dense_init(k3, di, d, dtype),
    }


def _in_proj(lp: Params, x: jnp.ndarray):
    """x -> (gate z, conv input xBC, dt)."""
    return x @ lp["in_z"], x @ lp["in_xbc"], x @ lp["in_dt"]


def _ssm_inputs(lp: Params, cfg: ModelConfig, xbc: jnp.ndarray,
                dt: jnp.ndarray):
    """Post-conv xBC and raw dt -> x (..., H, P), B and C (..., G, N), and
    dt (..., H) after softplus and the configured floor."""
    di, n, g = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_ngroups
    lead = xbc.shape[:-1]
    xs = xbc[..., :di].reshape(*lead, cfg.ssm_nheads, cfg.ssm_head_dim)
    B = xbc[..., di:di + g * n].reshape(*lead, g, n)
    C = xbc[..., di + g * n:].reshape(*lead, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    if cfg.ssm_dt_min:
        dt = jnp.maximum(dt, cfg.ssm_dt_min)
    return xs, B, C, dt


def _gated_out(lp: Params, cfg: ModelConfig, y: jnp.ndarray,
               z: jnp.ndarray) -> jnp.ndarray:
    """RMSNorm of y * silu(z) over each group's d_inner / G channels, then
    the output projection."""
    v = y * jax.nn.silu(z)
    lead, di = v.shape[:-1], v.shape[-1]
    g = cfg.ssm_ngroups
    v32 = v.astype(jnp.float32).reshape(*lead, g, di // g)
    v32 = v32 * lax.rsqrt(jnp.mean(jnp.square(v32), axis=-1, keepdims=True)
                          + cfg.norm_eps)
    v = (v32.reshape(*lead, di)
         * lp["norm"]["scale"].astype(jnp.float32)).astype(y.dtype)
    return v @ lp["out_proj"]


def _ssd(cfg: ModelConfig, xs, dt, A, B, C, use_kernel: bool):
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.ssd(xs, dt, A, B, C, chunk=cfg.ssm_chunk)
    return ssd_chunked(xs, dt, A, B, C, chunk=cfg.ssm_chunk)


def mamba_block(lp: Params, cfg: ModelConfig, x: jnp.ndarray,
                use_kernel: bool = False) -> jnp.ndarray:
    """Full-sequence mamba2 block (pre-norm residual applied by caller)."""
    b, l, _ = x.shape
    z, xbc, dt = _in_proj(lp, x)
    xbc = jax.nn.silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"]))
    xs, B, C, dt = _ssm_inputs(lp, cfg, xbc, dt)
    A = -jnp.exp(lp["A_log"])
    y, _ = _ssd(cfg, xs, dt, A, B, C, use_kernel)
    y = y + lp["D"].astype(y.dtype)[None, None, :, None] * xs
    return _gated_out(lp, cfg, y.reshape(b, l, cfg.ssm_d_inner), z)


def mamba_block_prefill(lp: Params, cfg: ModelConfig, x: jnp.ndarray, *,
                        use_kernel: bool = False, conv_dtype=jnp.bfloat16,
                        length: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full-sequence mamba2 block that also emits the decode-cache entries.

    Returns (y (B, L, D), final SSM state (B, P, N, H) fp32 in the cache's
    layout, conv window (B, W-1, conv_dim)).  The conv window holds the last W-1 *raw*
    (pre-activation) conv inputs, zero-padded on the left for short prompts —
    exactly the state :func:`causal_conv_step` would have accumulated.

    ``length`` (traced, scalar or (B,), paged serving): each prompt is
    right-padded to a fixed max bucket; positions >= its length get dt = 0,
    which makes them exact no-ops on the recurrent state (decay 1, zero
    input — the same trick the chunk padding uses), and the conv window is
    gathered to END at the row's length instead of the padded tail.  The
    returned y rows are only valid below their lengths.
    """
    b, l, _ = x.shape
    W = cfg.ssm_conv_width
    z, xbc, dt = _in_proj(lp, x)
    if length is None:
        win = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))[:, l:, :].astype(conv_dtype)
    else:
        lens = jnp.broadcast_to(jnp.asarray(length), (b,))
        idx = lens[:, None] - (W - 1) + jnp.arange(W - 1)[None, :]  # (B, W-1)
        live = (idx >= 0)[:, :, None]
        win = (jnp.take_along_axis(xbc, jnp.clip(idx, 0, l - 1)[:, :, None],
                                   axis=1) * live).astype(conv_dtype)
    xbc = jax.nn.silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"]))
    xs, B, C, dt = _ssm_inputs(lp, cfg, xbc, dt)
    if length is not None:
        # dt = 0 AFTER softplus: pad steps decay by exp(0) = 1 and inject
        # dt * x = 0, so the state at the end equals the state at the row's
        # length
        dt = jnp.where(jnp.arange(l)[None, :, None] < lens[:, None, None],
                       dt, 0.0)
    A = -jnp.exp(lp["A_log"])
    y, state = _ssd(cfg, xs, dt, A, B, C, use_kernel)
    y = y + lp["D"].astype(y.dtype)[None, None, :, None] * xs
    return (_gated_out(lp, cfg, y.reshape(b, l, cfg.ssm_d_inner), z),
            state_to_cache(state), win)


def mamba_block_step(lp: Params, cfg: ModelConfig, x: jnp.ndarray,
                     state: jnp.ndarray, conv_win: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token mamba2 step.  x: (B, 1, D)."""
    b = x.shape[0]
    z, xbc, dt = _in_proj(lp, x)
    xbc_t, conv_win = causal_conv_step(conv_win, xbc, lp["conv_w"], lp["conv_b"])
    xs, B, C, dt = _ssm_inputs(lp, cfg, jax.nn.silu(xbc_t[:, 0]), dt[:, 0])
    A = -jnp.exp(lp["A_log"])
    y, state = ssd_recurrent_step(state, xs, dt, A, B, C)
    y = y + lp["D"].astype(y.dtype)[None, :, None] * xs
    return _gated_out(lp, cfg, y.reshape(b, 1, cfg.ssm_d_inner), z), state, conv_win


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def init_model(rng, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(rng, 3)
    layer_rngs = jax.random.split(ks[0], cfg.num_layers)
    stacked = jax.vmap(lambda r: init_mamba_block(r, cfg, dtype))(layer_rngs)
    return {
        "embed": L.embed_init(ks[1], cfg.vocab_size, cfg.d_model, dtype),
        "layers": stacked,
        "final_norm": L.rmsnorm_init(cfg.d_model, dtype),
        "lm_head": L.dense_init(ks[2], cfg.d_model, cfg.vocab_size, dtype),
    }


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray, *,
            remat: bool = False, use_kernel: bool = False,
            last_only: bool = False) -> jnp.ndarray:
    h = params["embed"][tokens]

    def body(carry, lp):
        x = act.shard_hidden(carry)
        y = mamba_block(lp, cfg, L.rmsnorm(lp["ln"], x, cfg.norm_eps),
                        use_kernel=use_kernel)
        return act.shard_hidden(x + y), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    h, _ = lax.scan(body, act.shard_hidden(h), params["layers"])
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    if last_only:
        h = h[:, -1:]
    return act.shard_logits((h @ params["lm_head"]).astype(jnp.float32))


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=jnp.bfloat16):
    """SSM decode cache: per-layer recurrent state + conv window.

    Constant-size in seq_len (the SSM advantage for long_500k)."""
    del seq_len
    h, p, n = cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state
    return {
        "state": jnp.zeros((cfg.num_layers, batch, p, n, h), jnp.float32),
        "conv": jnp.zeros((cfg.num_layers, batch, cfg.ssm_conv_width - 1, conv_dim(cfg)),
                          dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def decode_step(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                cache: Params) -> Tuple[jnp.ndarray, Params]:
    h = params["embed"][token]

    def body(carry, xs):
        x = carry
        lp, st, cw = xs
        y, st, cw = mamba_block_step(lp, cfg, L.rmsnorm(lp["ln"], x, cfg.norm_eps),
                                     st, cw)
        return x + y, (st, cw)

    h, (ns, ncw) = lax.scan(body, h, (params["layers"], cache["state"],
                                      cache["conv"]))
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h[:, 0, :] @ params["lm_head"]).astype(jnp.float32)
    return logits, {"state": ns, "conv": ncw, "pos": cache["pos"] + 1}


# ---------------------------------------------------------------------------
# paged cache API (continuous batching)
# ---------------------------------------------------------------------------
#
# The SSM decode state is CONSTANT-size per sequence (that is its whole point)
# so there is nothing to page: the "pool" is per-slot state + conv window, and
# the scheduler's block table is simply ignored by this family.  Admission
# overwrites the slot's state wholesale, which is also what makes slot reuse
# leak-free without an allocator.


def init_paged_cache(cfg: ModelConfig, num_slots: int, num_pages: int,
                     page_size: int, dtype=jnp.bfloat16):
    del num_pages, page_size
    h, p, n = cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state
    # int8 pool dtype only quantizes *paged KV*; the SSM has none, and its
    # recurrent carry must stay full-precision, so the conv window falls back
    # to bf16 (the state is always fp32).
    if jnp.dtype(dtype) == jnp.int8:
        dtype = jnp.bfloat16
    return {
        "state": jnp.zeros((cfg.num_layers, num_slots, p, n, h), jnp.float32),
        "conv": jnp.zeros((cfg.num_layers, num_slots, cfg.ssm_conv_width - 1,
                           conv_dim(cfg)), dtype),
    }


def init_prefix_cache(cfg: ModelConfig, entries: int, dtype=jnp.bfloat16):
    """Device-side full-prompt snapshot rows: the recurrent state + conv
    window at the prompt boundary, keyed host-side by the prompt's chain
    hash.  The SSM state is constant-size, so one row restores the WHOLE
    prompt — the recurrent families' equivalent of aliasing every page."""
    h, p, n = cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state
    if jnp.dtype(dtype) == jnp.int8:
        dtype = jnp.bfloat16
    return {
        "state": jnp.zeros((cfg.num_layers, entries, p, n, h), jnp.float32),
        "conv": jnp.zeros((cfg.num_layers, entries, cfg.ssm_conv_width - 1,
                           conv_dim(cfg)), dtype),
    }


def snapshot_save(cfg: ModelConfig, cache: Params, prefix: Params,
                  rows: jnp.ndarray, slots: jnp.ndarray) -> Params:
    """Snapshot admitted slots' post-prefill state into prefix rows.
    rows: (A,) snapshot rows (== entries sentinel drops); slots: (A,)."""
    return dict(prefix,
                state=prefix["state"].at[:, rows].set(
                    cache["state"][:, slots], mode="drop"),
                conv=prefix["conv"].at[:, rows].set(
                    cache["conv"][:, slots], mode="drop"))


def snapshot_restore(cfg: ModelConfig, cache: Params, prefix: Params,
                     rows: jnp.ndarray, slots: jnp.ndarray) -> Params:
    """Restore snapshot rows into decode slots (full-prompt prefix hit).
    slots: (A,) target slots (== num_slots sentinel drops)."""
    return dict(cache,
                state=cache["state"].at[:, slots].set(
                    prefix["state"][:, rows], mode="drop"),
                conv=cache["conv"].at[:, slots].set(
                    prefix["conv"][:, rows], mode="drop"))


def prefill_paged(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  lengths: jnp.ndarray, slots: jnp.ndarray,
                  block_rows: jnp.ndarray, cache: Params, *,
                  use_kernel: bool = False,
                  start=None) -> Tuple[jnp.ndarray, Params]:
    """Prefill a batch of admitted requests into decode slots ``slots``.

    tokens: (A, S_max) right-padded; each row's positions >= lengths[i] are
    exact state no-ops (dt = 0) and its logits are read at lengths[i] - 1.
    Padded admission rows carry an out-of-range slot index and their state
    writes are dropped.  ``start`` is accepted for API uniformity but unused:
    the SSM families have no pages to share mid-prompt — their prefix reuse
    is the full-prompt snapshot/restore path (state scatter order: a restore
    following this prefill overwrites the slot, so a restored row may run
    here as a passive batch member)."""
    del block_rows, start
    conv_dtype = cache["conv"].dtype
    h = params["embed"][tokens]

    def body(carry, lp):
        x = act.shard_hidden(carry)
        y, st, cw = mamba_block_prefill(lp, cfg,
                                        L.rmsnorm(lp["ln"], x, cfg.norm_eps),
                                        use_kernel=use_kernel,
                                        conv_dtype=conv_dtype, length=lengths)
        return act.shard_hidden(x + y), (st, cw)

    h, (ns, ncw) = lax.scan(body, h, params["layers"])
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    h = jnp.take_along_axis(h, (lengths - 1)[:, None, None], axis=1)
    logits = (h[:, 0, :] @ params["lm_head"]).astype(jnp.float32)
    new_cache = {
        "state": cache["state"].at[:, slots].set(ns, mode="drop"),
        "conv": cache["conv"].at[:, slots].set(ncw, mode="drop"),
    }
    return logits, new_cache


def decode_step_paged(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                      pos: jnp.ndarray, block: jnp.ndarray, cache: Params, *,
                      use_kernel: bool = False,
                      write_block=None, rows=None) -> Tuple[jnp.ndarray, Params]:
    """One decode step for all slots.  The recurrent update is position-free
    and reads no pages, so ``pos``/``block``/``write_block``/``rows`` are
    unused — idle slots advance garbage state that admission overwrites."""
    del pos, block, use_kernel, write_block, rows
    h = params["embed"][token]

    def body(carry, xs):
        x = carry
        lp, st, cw = xs
        y, st, cw = mamba_block_step(lp, cfg,
                                     L.rmsnorm(lp["ln"], x, cfg.norm_eps),
                                     st, cw)
        return x + y, (st, cw)

    h, (ns, ncw) = lax.scan(body, h, (params["layers"], cache["state"],
                                      cache["conv"]))
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h[:, 0, :] @ params["lm_head"]).astype(jnp.float32)
    return logits, {"state": ns, "conv": ncw}


def forward_chunk_paged(params: Params, cfg: ModelConfig,
                        tokens: jnp.ndarray, pos: jnp.ndarray,
                        block: jnp.ndarray, cache: Params, *,
                        use_kernel: bool = False,
                        write_block=None) -> Tuple[jnp.ndarray, Params, dict]:
    """Chunked token lane for the recurrent family: the chunk is consumed by
    a ``lax.scan`` of per-token recurrent steps — bitwise identical to C
    sequential ``decode_step_paged`` calls — and every step's {state, conv}
    is emitted as a CHUNK-BOUNDARY SNAPSHOT (``staged``, leading axis C).
    ``staged[j]`` holds the state after consuming exactly ``j + 1`` chunk
    inputs, which is what lets the scheduler commit a partial final prefill
    chunk (``select_stage`` + ``restore_stage``).

    Returns (logits (B, C, V) fp32, cache with the FULL chunk absorbed,
    staged)."""
    del pos, block, use_kernel, write_block      # recurrence is position-free

    def step(carry, tok):
        cache = carry
        logits, cache = decode_step_paged(params, cfg, tok[:, None], None,
                                          None, cache)
        return cache, (logits, {"state": cache["state"],
                                "conv": cache["conv"]})

    cache, (logits, staged) = lax.scan(step, cache, tokens.T)
    return logits.transpose(1, 0, 2), cache, staged


def chunk_stage(cfg: ModelConfig, cache: Params) -> dict:
    """The rollback-able slice of the cache: per-slot recurrent state + conv
    window (attention families return {} — their state is positional)."""
    return {"state": cache["state"], "conv": cache["conv"]}


def restore_stage(cfg: ModelConfig, cache: Params, stage: dict,
                  mask: jnp.ndarray) -> Params:
    """Overwrite the recurrent state of slots where ``mask`` (B,) is True
    with ``stage``'s values (leaves shaped like the cache's: slot axis 1)."""
    return dict(cache,
                state=jnp.where(mask[None, :, None, None, None],
                                stage["state"], cache["state"]),
                conv=jnp.where(mask[None, :, None, None],
                               stage["conv"], cache["conv"]))


def select_stage(cfg: ModelConfig, staged: dict, keep: jnp.ndarray) -> dict:
    """Pick each slot's snapshot after exactly ``keep`` (B,) chunk inputs
    (keep >= 1; masked out by the caller otherwise): ``staged[keep - 1]``
    per slot, leaves (C, L, B, ...) -> (L, B, ...)."""
    idx = jnp.maximum(keep - 1, 0)

    def sel(a):
        i = idx.reshape((1, 1, -1) + (1,) * (a.ndim - 3))
        return jnp.take_along_axis(a, i, axis=0)[0]

    return {"state": sel(staged["state"]), "conv": sel(staged["conv"])}


def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            cache: Params, *, use_kernel: bool = False
            ) -> Tuple[jnp.ndarray, Params]:
    """Consume the whole (B, S) prompt with the chunked SSD pass and write the
    per-layer recurrent state + conv window.  ``cache`` supplies shapes/dtypes
    and is fully overwritten (donation-safe).

    Returns (last-token logits (B, V) fp32, filled cache).
    """
    s = tokens.shape[1]
    conv_dtype = cache["conv"].dtype
    h = params["embed"][tokens]

    def body(carry, lp):
        x = act.shard_hidden(carry)
        y, st, cw = mamba_block_prefill(lp, cfg,
                                        L.rmsnorm(lp["ln"], x, cfg.norm_eps),
                                        use_kernel=use_kernel,
                                        conv_dtype=conv_dtype)
        return act.shard_hidden(x + y), (st, cw)

    h, (ns, ncw) = lax.scan(body, h, params["layers"])
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h[:, -1, :] @ params["lm_head"]).astype(jnp.float32)
    return logits, {"state": ns, "conv": ncw, "pos": jnp.asarray(s, jnp.int32)}
