"""ssd_scan — Mamba-2 SSD intra-chunk kernel (the quadratic hot spot).

The chunked SSD algorithm splits into:
  (a) intra-chunk: attention-like (Q x Q) compute per chunk — O(L*Q) FLOPs,
      the dominant term and the MXU-friendly part      -> THIS KERNEL
  (b) inter-chunk: linear recurrence over chunk states — O(L/Q) tiny scan
      -> stays in jnp (ops.py), it is bandwidth-trivial

Per grid step (batch b, chunk c, B/C group g) the kernel computes, entirely
in VMEM and for the group's Hg = H / G heads of the chunk:
  y_diag  (Q, Hg, P)  causal decay-masked intra-chunk output
  S       (Hg, P, N)  end-of-chunk summary state (feeds the jnp scan)
  g       (1, Hg)     total chunk decay  exp(sum a)
  exp_acs (Q, Hg)     exp(cumsum a) — reused for the inter-chunk y_off term

Everything is 2-D so that it lowers to the TPU's vector and matrix units:
the per-head cumulative log-decay is one (Q x Q) lower-triangular matmul
over the group's heads at once (Mosaic has no cumsum), the group's C B^T is
one (Q x Q) matmul, and each head's decay mask, output and state are 2-D
matmuls in a static loop over its heads.  Q=128 aligns the (Q x Q) matmuls
with the 128x128 MXU.  The wrapper lays the group axis out ahead of each
block's two trailing dims, so every block spans whole trailing dims (a legal
TPU tile at any head count); the scoped-VMEM limit is raised to hold the
(Q, Hg, P) x and y blocks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST
# x and y blocks (Q, Hg, P) fp32, double-buffered, the state block and the
# head loop's (Q, Q) temporaries: at zamba2-7b's Q=256, Hg=56, P=64 the
# compiler asks for about 66 MB (a described v5e refuses 64 MiB); the chip
# has 128 MiB
_VMEM_LIMIT = 96 * 1024 * 1024


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
            y_ref, s_ref, g_ref, eacs_ref):
    dt = dt_ref[0, 0, 0].astype(jnp.float32)  # (Q, Hg)
    A = a_ref[0].astype(jnp.float32)          # (1, Hg)
    B = b_ref[0, 0, 0].astype(jnp.float32)    # (Q, N)
    C = c_ref[0, 0, 0].astype(jnp.float32)    # (Q, N)
    q, nh = dt.shape

    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = rows >= cols
    a = dt * A                                 # (Q, Hg) log-decay
    # cumsum over Q as a lower-triangular matmul, and its transpose (Hg, Q)
    a_cs = jnp.dot(causal.astype(jnp.float32), a, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)
    a_cs_t = jax.lax.dot_general(a, causal.astype(jnp.float32),
                                 (((0,), (1,)), ((), ())),
                                 precision=_HIGHEST,
                                 preferred_element_type=jnp.float32)
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             precision=_HIGHEST,
                             preferred_element_type=jnp.float32)  # (Q, Q)
    decay_to_end = jnp.exp(a_cs[q - 1:q, :] - a_cs)            # (Q, Hg)

    for hd in range(nh):
        # causal decay mask  L[i, j] = exp(a_cs[i] - a_cs[j]), i >= j
        decay = jnp.where(causal, jnp.exp(a_cs[:, hd:hd + 1]
                                          - a_cs_t[hd:hd + 1, :]), 0.0)
        dx = x_ref[0, 0, :, 0, hd, :].astype(jnp.float32) * dt[:, hd:hd + 1]
        y_ref[0, 0, :, 0, hd, :] = jnp.dot(cb * decay, dx, precision=_HIGHEST,
                                           preferred_element_type=jnp.float32)
        s_ref[0, 0, 0, hd] = jax.lax.dot_general(
            dx * decay_to_end[:, hd:hd + 1], B, (((0,), (0,)), ((), ())),
            precision=_HIGHEST, preferred_element_type=jnp.float32)
    g_ref[0, 0, 0] = jnp.exp(a_cs[q - 1:q, :])                # (1, Hg)
    eacs_ref[0, 0, 0] = jnp.exp(a_cs)


def ssd_chunk_pallas(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                     B: jnp.ndarray, C: jnp.ndarray, *,
                     interpret: bool = False):
    """Intra-chunk SSD terms.

    x: (b, nc, Q, H, P); dt: (b, nc, Q, H); A: (H,); B/C: (b, nc, G, Q, N),
    head j reading group j // (H / G).
    Returns (y_diag (b,nc,Q,H,P), S (b,nc,H,P,N), g (b,nc,H),
             exp_acs (b,nc,Q,H))."""
    b, nc, q, h, p = x.shape
    ng, n = B.shape[2], B.shape[-1]
    hg = h // ng
    xg = x.reshape(b, nc, q, ng, hg, p)
    dtg = dt.reshape(b, nc, q, ng, hg).transpose(0, 1, 3, 2, 4)
    ag = A.reshape(ng, 1, hg)

    def im_x(i, c, k):
        return (i, c, 0, k, 0, 0)

    def im_5(i, c, k):
        return (i, c, k, 0, 0)

    def im_6(i, c, k):
        return (i, c, k, 0, 0, 0)

    y, S, g, eacs = pl.pallas_call(
        _kernel,
        grid=(b, nc, ng),
        in_specs=[
            pl.BlockSpec((1, 1, q, 1, hg, p), im_x),
            pl.BlockSpec((1, 1, 1, q, hg), im_5),
            pl.BlockSpec((1, 1, hg), lambda i, c, k: (k, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, n), im_5),
            pl.BlockSpec((1, 1, 1, q, n), im_5),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, 1, hg, p), im_x),
            pl.BlockSpec((1, 1, 1, hg, p, n), im_6),
            pl.BlockSpec((1, 1, 1, 1, hg), im_5),
            pl.BlockSpec((1, 1, 1, q, hg), im_5),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, q, ng, hg, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, ng, hg, p, n), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, ng, 1, hg), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, ng, q, hg), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(xg, dtg, ag, B, C)
    return (y.reshape(b, nc, q, h, p), S.reshape(b, nc, h, p, n),
            g.reshape(b, nc, h), eacs.transpose(0, 1, 3, 2, 4).reshape(b, nc, q, h))
