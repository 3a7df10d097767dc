"""Public jit'd wrappers around the Pallas kernels.

The kernels are written for the TPU (BlockSpec VMEM tiling, scalar-prefetched
block tables, a sequential last grid dim).  Whether they run compiled or
through the Pallas interpreter follows from the backend, not from a flag:
:func:`interpret` is False on the TPU and True on the CPU backend, where the
interpreter validates the kernel bodies (tests, small smoke runs).  Any
other backend raises — a kernel is never interpreted on an accelerator, and
never quietly replaced by the jnp path (``use_kernel=False`` selects that).
``tests/test_tpu_compile.py`` compiles every kernel of the serving tick for
a v5e at qwen2-1.5b / zamba2-7b widths.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _da
from repro.kernels import hi_gate as _hg
from repro.kernels import ssd_scan as _ssd


def interpret() -> bool:
    """True on the CPU backend (Pallas interpreter), False on the TPU
    (Mosaic-compiled kernels); evaluated at trace time, never at import."""
    backend = jax.default_backend()
    if backend in ("cpu", "tpu"):
        return backend == "cpu"
    raise NotImplementedError(
        f"the Pallas kernels target the TPU and cannot run on backend "
        f"{backend!r}; serve with use_kernel=False")


@functools.partial(jax.jit, static_argnames=("metric",))
def hi_gate(logits: jnp.ndarray, theta, metric: str = "max_prob"
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused confidence + argmax + threshold.  logits: (N, C).

    ``theta`` is a TRACED operand (python float or fp32 scalar array): the
    serving engine's online policy moves it every batch, and a static theta
    would force a recompile per update.
    """
    return _hg.hi_gate_pallas(logits, theta, metric, interpret=interpret())


@functools.partial(jax.jit, static_argnames=("block_s",))
def decode_attention(q: jnp.ndarray, cache_k: jnp.ndarray,
                     cache_v: jnp.ndarray, valid: jnp.ndarray,
                     block_s: int = 512) -> jnp.ndarray:
    """Flash decode attention.  q: (B,1,H,D); cache: (B,S,K,D); valid: (S,)."""
    return _da.decode_attention_pallas(q, cache_k, cache_v, valid,
                                       block_s=block_s, interpret=interpret())


@jax.jit
def decode_attention_paged(q: jnp.ndarray, pool_k: jnp.ndarray,
                           pool_v: jnp.ndarray, block: jnp.ndarray,
                           valid: jnp.ndarray,
                           scale_k: jnp.ndarray | None = None,
                           scale_v: jnp.ndarray | None = None) -> jnp.ndarray:
    """Flash decode attention over a PAGED KV pool.

    q: (B,1,H,D); pool_k/v: (P, page, K*D); block: (B, n_pages) int32 block
    table (scalar-prefetched — the kernel DMAs physical pages directly);
    valid: (B, n_pages * page) per-slot positional mask.  Passing
    ``scale_k/v`` (P, K) fp32 marks the pools int8-quantized: the page's
    per-head scale is DMA'd through the same block-table index_map and
    dequant happens in-register inside the kernel."""
    return _da.decode_attention_paged_pallas(q, pool_k, pool_v, block, valid,
                                             scale_k=scale_k, scale_v=scale_v,
                                             interpret=interpret())


@jax.jit
def decode_attention_chunk_paged(q: jnp.ndarray, pool_k: jnp.ndarray,
                                 pool_v: jnp.ndarray, block: jnp.ndarray,
                                 valid: jnp.ndarray,
                                 scale_k: jnp.ndarray | None = None,
                                 scale_v: jnp.ndarray | None = None
                                 ) -> jnp.ndarray:
    """Flash CHUNK attention over a paged KV pool: C query tokens per slot at
    per-slot start positions in one streaming pass over the slot's pages.

    q: (B, C, H, D); pool_k/v: (P, page, K*D); block: (B, n_pages) int32
    (scalar-prefetched); valid: (B, C, n_pages * page) positional +
    intra-chunk causal mask.  ``scale_k/v`` (P, K) fp32 mark the pools
    int8-quantized with dequant fused into the page gather."""
    return _da.decode_attention_chunk_paged_pallas(q, pool_k, pool_v, block,
                                                   valid, scale_k=scale_k,
                                                   scale_v=scale_v,
                                                   interpret=interpret())


@jax.jit
def copy_pages(pool: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray
               ) -> jnp.ndarray:
    """Copy-on-write page duplication: pool pages ``dst`` become copies of
    pages ``src`` (pairs padded with (0, 0) — null onto null).

    pool: (L, P, ...) of any dtype — the kernel's block shape and out_shape
    derive from the operand, so the same op moves (L, P, page, K*D) int8/bf16
    page pools AND their (L, P, K) fp32 per-page scale rows.  The (src, dst)
    pairs are expanded into a per-page source map so the kernel writes every
    output page exactly once (identity for non-COW pages) with the map
    scalar-prefetched — see ``decode_attention.copy_pages_pallas``."""
    p = pool.shape[1]
    src_of = jnp.arange(p, dtype=jnp.int32).at[dst].set(src)
    return _da.copy_pages_pallas(pool, src_of, interpret=interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray,
        C: jnp.ndarray, chunk: int = 128) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD: Pallas intra-chunk kernel + jnp inter-chunk recurrence.

    x: (b, l, h, p); dt: (b, l, h); A: (h,); B/C: (b, l, G, n), head j
    reading group j // (h / G).
    Returns (y (b,l,h,p), final_state (b,h,p,n))."""
    b, l, h, p = x.shape
    ng, n = B.shape[2:]
    chunk = min(chunk, l)
    orig_l = l
    if l % chunk:
        pad = chunk - l % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        l += pad
    nc = l // chunk

    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    # groups ahead of the chunk's rows: the kernel reads one (Q, N) tile each
    Bc = B.reshape(b, nc, chunk, ng, n).transpose(0, 1, 3, 2, 4)
    Cc = C.reshape(b, nc, chunk, ng, n).transpose(0, 1, 3, 2, 4)

    y_diag, S, g, eacs = _ssd.ssd_chunk_pallas(xc, dtc, A, Bc, Cc,
                                               interpret=interpret())

    # inter-chunk linear recurrence (tiny: nc steps over (b,h,p,n))
    def step(hprev, xs):
        g_c, S_c = xs
        return g_c[:, :, None, None] * hprev + S_c, hprev

    h0 = jnp.zeros((b, h, p, n), jnp.float32)
    hT, h_prevs = jax.lax.scan(
        step, h0, (g.transpose(1, 0, 2), S.transpose(1, 0, 2, 3, 4)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)          # (b, nc, h, p, n)

    y_off = jnp.einsum("bcih,bchin,bchpn->bcihp", eacs,
                       jnp.repeat(Cc, h // ng, axis=2), h_prevs)
    y = (y_diag + y_off).reshape(b, l, h, p)[:, :orig_l]
    return y.astype(x.dtype), hT
