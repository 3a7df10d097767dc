"""decode_attention — flash-style GQA attention over a PAGED KV pool.

One kernel serves every attention read of the serving tick: a block of C
query tokens per slot (C = 1 for decode, C = chunk for the chunk lane)
attends over the slot's pages, which the
kernel DMAs straight out of the shared physical pool through the block
table.  The contiguous-cache decode entry point is the same kernel over an
identity block table (the cache viewed as one page run per row).

Grid: (batch, page).  The block table is a SCALAR-PREFETCH operand, so the
K/V index_maps dereference it: grid step (b, j) DMAs physical page
``block[b, j]`` of a ``(P, page, K*D)`` pool (``layers.paged_kv``) as a
whole ``(1, page, K*D)`` tile — every KV head of the page in one copy — and
the body loops over the K heads, head k being lanes ``[k*D, (k+1)*D)``.
Whole trailing dims keep every block shape legal for the TPU's (8, 128)
tiling at any head count and head_dim (qwen2's K=2/D=128, zamba2-7b's
K=32/D=224).  Per step the kernel holds:

  q     (1, K, C*G, D)      the slot's query rows, head-major   [VMEM]
  k/v   (1, page, K*D)      one physical page                   [VMEM]
  mask  (1, 1, C*G, page)   positional + intra-chunk causal     [VMEM]

and keeps the online-softmax state (running max m, normaliser l, fp32
accumulator) in VMEM scratch across the sequential page axis; the output
block is written once, at the slot's last page.  Consecutive grid steps
that name the same physical page (the null page 0 past a slot's live
length) are not re-fetched.

Numerical-safety choices: the running max starts at -1e30 (finite, so the
``exp(m - m_new)`` correction never sees inf-inf = NaN) and masked
probability mass is explicitly zeroed (a fully-masked page keeps l = 0).

Quantized page pools (int8, per-page-per-head scales)
-----------------------------------------------------
``pool_k/v`` may be (P, page, K*Dh) int8 with a symmetric fp32 scale
tensor ``scale_k/v`` of shape (P, K) beside each pool (dequantized value =
``int8 * scale[page, head]``).  The scales ride the same block-table
index_map as their pool, viewed as (P, 1, K) so the block is one whole
row; dequantization is one in-register multiply per head before the fp32
flash update.  ``scale_k=None`` (the default) traces exactly the
unquantized graph, so bf16 pools stay bitwise identical.  Write-side
quantization lives in ``models/layers.py``; the scale rows move with their
pages under COW via ``copy_pages_pallas``.

Mesh-sharded serving (``scheduler.ContinuousScheduler(mesh=...)``)
------------------------------------------------------------------
Under ``shard_map`` over the ``data`` axis every kernel here sees
PER-SHARD local shapes (the replica's slots, pool and block table), so the
grid composes with the sharded tick for any ``data`` size.  GSPMD cannot
partition a ``pallas_call`` body over ``model``: the scheduler rejects
``use_kernel`` with ``model > 1``, and ``_check_heads`` below catches the
symptom (a locally-narrower K pool meeting an unsharded q) with a
diagnosis instead of a silently wrong group size.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30

# Widest per-slot block-table row the paged kernels accept: the table is a
# SCALAR-PREFETCH operand (SMEM-resident on TPU), so a slot's row must fit
# the scalar-prefetch block.  KVPool refuses configurations past this at
# allocation time — a clear host-side error instead of a Pallas lowering
# failure deep inside the tick executable.
MAX_PREFETCH_PAGES = 2048


def _check_heads(h: int, kh: int) -> int:
    """GQA group size, with a mesh-aware diagnosis: a pool whose K dim was
    narrowed by a ``model``-axis partition while q kept all H heads shows up
    here as a non-dividing head count — fail loudly before the kernel
    computes with a wrong group size."""
    if h % kh:
        raise ValueError(
            f"query heads H={h} not divisible by pool kv heads K={kh}; if "
            "the page pool is model-axis sharded (mesh serving), the Pallas "
            "gather cannot be GSPMD-partitioned — run the L tier with "
            "use_kernel=False (the scheduler enforces this)")
    return h // kh


def _paged_kernel(blk_ref, q_ref, k_ref, v_ref, *refs, scale: float,
                  kh: int, d: int, quant: bool):
    """One (slot, page) grid step for all K heads: fold page ``block[b, j]``
    into every query row's online-softmax state; normalise and write the
    output at the slot's last page."""
    del blk_ref      # consumed by the index_maps, not the body
    if quant:
        sk_ref, sv_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        mask_ref, o_ref, m_scr, l_scr, acc_scr = refs
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid = mask_ref[0, 0] > 0                          # (R, page)
    live = valid.astype(jnp.float32)
    for hd in range(kh):
        q = q_ref[0, hd].astype(jnp.float32)            # (R, D)
        lanes = slice(hd * d, (hd + 1) * d)
        k = k_ref[0, :, lanes].astype(jnp.float32)      # (page, D)
        v = v_ref[0, :, lanes].astype(jnp.float32)
        if quant:
            k = k * sk_ref[0, :, hd:hd + 1]             # (1, 1) page scale
            v = v * sv_ref[0, :, hd:hd + 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, _NEG)                   # (R, page)
        m_prev = m_scr[hd]                              # (R, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new) * live
        corr = jnp.exp(m_prev - m_new)
        m_scr[hd] = m_new
        l_scr[hd] = l_scr[hd] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[hd] = acc_scr[hd] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        for hd in range(kh):
            o_ref[0, hd] = (acc_scr[hd] / jnp.maximum(l_scr[hd], 1e-30)
                            ).astype(o_ref.dtype)


def decode_attention_chunk_paged_pallas(q: jnp.ndarray, pool_k: jnp.ndarray,
                                        pool_v: jnp.ndarray,
                                        block: jnp.ndarray,
                                        valid: jnp.ndarray, *,
                                        scale_k: jnp.ndarray | None = None,
                                        scale_v: jnp.ndarray | None = None,
                                        interpret: bool = False
                                        ) -> jnp.ndarray:
    """q: (B, C, H, D) — a chunk of C query tokens per slot; pool_k/v:
    (P, page, K*D); block: (B, n_pages) int32; valid: (B, C, n_pages * page)
    bool — per-slot positional AND intra-chunk causal mask (query i of slot b
    may attend key position s iff ``valid[b, i, s]``).

    One grid step DMAs physical page ``block[b, j]`` (scalar-prefetched) and
    accumulates it into all C queries' online-softmax states at once — the
    chunk costs ONE streaming pass over the slot's pages instead of C.
    ``scale_k/v`` (P, K) fp32 mark the pools int8-quantized (see module
    docstring); dequant fuses into the gather.  Returns (B, C, H, D)
    attention output (fp32 accumulation)."""
    b, c, h, d = q.shape
    n_phys, page, kd = pool_k.shape
    kh = kd // d
    npg = block.shape[1]
    g = _check_heads(h, kh)
    r = c * g                                       # query rows per kv head
    # head-major query rows: row c*G + g of head k is query token c, head
    # k*G + g (the GQA grouping _sdpa uses)
    qr = q.reshape(b, c, kh, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, kh, r, d)
    mask = valid.astype(jnp.int32).reshape(b, c, npg, page)
    mask = jnp.broadcast_to(mask.transpose(0, 2, 1, 3)[:, :, :, None, :],
                            (b, npg, c, g, page)).reshape(b, npg, r, page)
    quant = scale_k is not None

    page_spec = pl.BlockSpec((1, page, kd),
                             lambda bi, j, blk: (blk[bi, j], 0, 0))
    scale_spec = pl.BlockSpec((1, 1, kh),
                              lambda bi, j, blk: (blk[bi, j], 0, 0))
    row_spec = pl.BlockSpec((1, kh, r, d), lambda bi, j, blk: (bi, 0, 0, 0))
    in_specs = [row_spec, page_spec, page_spec,
                *([scale_spec, scale_spec] if quant else []),
                pl.BlockSpec((1, 1, r, page),
                             lambda bi, j, blk: (bi, j, 0, 0))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, npg),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[pltpu.VMEM((kh, r, 1), jnp.float32),
                        pltpu.VMEM((kh, r, 1), jnp.float32),
                        pltpu.VMEM((kh, r, d), jnp.float32)],
    )
    scales = ()
    if quant:
        scales = (scale_k.reshape(n_phys, 1, kh),
                  scale_v.reshape(n_phys, 1, kh))
    kernel = functools.partial(_paged_kernel, scale=1.0 / math.sqrt(d),
                               kh=kh, d=d, quant=quant)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, r, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block, qr, pool_k, pool_v, *scales, mask)
    return out.reshape(b, kh, c, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, c, h, d)


def decode_attention_paged_pallas(q: jnp.ndarray, pool_k: jnp.ndarray,
                                  pool_v: jnp.ndarray, block: jnp.ndarray,
                                  valid: jnp.ndarray, *,
                                  scale_k: jnp.ndarray | None = None,
                                  scale_v: jnp.ndarray | None = None,
                                  interpret: bool = False) -> jnp.ndarray:
    """q: (B, 1, H, D); pool_k/v: (P, page, K*D); block: (B, n_pages) int32;
    valid: (B, n_pages * page) bool (per-slot positional mask); scale_k/v
    (P, K) fp32 mark the pools int8-quantized.  The C = 1 case of
    :func:`decode_attention_chunk_paged_pallas`.

    Returns (B, 1, H, D) attention output (fp32 accumulation)."""
    return decode_attention_chunk_paged_pallas(
        q, pool_k, pool_v, block, valid[:, None, :], scale_k=scale_k,
        scale_v=scale_v, interpret=interpret)


def decode_attention_pallas(q: jnp.ndarray, cache_k: jnp.ndarray,
                            cache_v: jnp.ndarray, valid: jnp.ndarray,
                            *, block_s: int = 512, interpret: bool = False
                            ) -> jnp.ndarray:
    """Contiguous-cache decode.  q: (B, 1, H, D); cache_k/v: (B, S, K, D);
    valid: (S,) bool.  The cache is viewed as a pool of ``block_s``-token
    pages under an identity block table and read by the paged kernel.

    Returns (B, 1, H, D) attention output (fp32 accumulation)."""
    b, s = cache_k.shape[:2]
    # largest divisor of S not exceeding the requested tile
    block_s = math.gcd(s, block_s)
    nsb = s // block_s
    pool_shape = (b * nsb, block_s, -1)
    table = jnp.arange(b * nsb, dtype=jnp.int32).reshape(b, nsb)
    return decode_attention_paged_pallas(
        q, cache_k.reshape(pool_shape), cache_v.reshape(pool_shape), table,
        jnp.broadcast_to(valid[None, :], (b, s)), interpret=interpret)


def copy_pages_pallas(pool: jnp.ndarray, src_of: jnp.ndarray, *,
                      interpret: bool = False) -> jnp.ndarray:
    """Copy-on-write page duplication over a physical page pool.

    pool: (L, P, ...) — (L, P, page, K*D) pages or (L, P, K) scale rows;
    src_of: (P,) int32 per-page SOURCE map — identity everywhere except the
    COW destinations, which name the page they clone.  The map is a
    scalar-prefetch operand (the same idiom as the paged attention kernel's
    block table): grid step (l, p) DMAs physical page ``src_of[p]`` into VMEM
    and writes it back out as page ``p``, so every page is written exactly
    once (identity pages stream through unchanged — no aliasing or
    output-revisiting hazards).  Scale rows are viewed as (L, P, 1, K) so the
    block's trailing dims are whole.

    Admission schedules at most one COW per admitted request, so on TPU the
    non-identity traffic is a handful of pages; the identity passthrough is
    the price of a single well-formed grid.  Returns the updated pool.
    """
    shape = pool.shape
    if pool.ndim == 3:
        pool = pool.reshape(shape[:2] + (1, shape[2]))
    l, p = pool.shape[:2]
    blk = (1, 1) + pool.shape[2:]
    tail = (0,) * (len(blk) - 2)

    def kernel(src_ref, in_ref, out_ref):
        del src_ref          # consumed by the index_map, not the body
        out_ref[...] = in_ref[...]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(l, p),
        in_specs=[pl.BlockSpec(blk, lambda li, pi, src: (li, src[pi]) + tail)],
        out_specs=pl.BlockSpec(blk, lambda li, pi, src: (li, pi) + tail),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        interpret=interpret,
    )(src_of, pool)
    return out.reshape(shape)
