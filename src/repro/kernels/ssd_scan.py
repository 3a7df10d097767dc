"""ssd_scan — Mamba-2 SSD intra-chunk kernel (the quadratic hot spot).

The chunked SSD algorithm splits into:
  (a) intra-chunk: attention-like (Q x Q) compute per chunk — O(L*Q) FLOPs,
      the dominant term and the MXU-friendly part      -> THIS KERNEL
  (b) inter-chunk: linear recurrence over chunk states — O(L/Q) tiny scan
      -> stays in jnp (ops.py), it is bandwidth-trivial

Per grid step (batch b, chunk c) the kernel computes, entirely in VMEM and
for all H heads of the chunk:
  y_diag  (Q, H, P)  causal decay-masked intra-chunk output
  S       (H, P, N)  end-of-chunk summary state (feeds the jnp scan)
  g       (1, H)     total chunk decay  exp(sum a)
  exp_acs (Q, H)     exp(cumsum a) — reused for the inter-chunk y_off term

Everything is 2-D so that it lowers to the TPU's vector and matrix units:
the per-head cumulative log-decay is one (Q x Q) lower-triangular matmul
over all heads at once (Mosaic has no cumsum), and each head's (Q x Q)
decay mask, output and state are 2-D matmuls in a static loop over H.
Q=128 aligns the (Q x Q) matmuls with the 128x128 MXU.  Blocks span whole
trailing dims (all heads), which keeps every block shape a legal TPU tile;
the scoped-VMEM limit is raised to hold the (Q, H, P) x and y blocks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST
# x and y blocks (Q, H, P) fp32, double-buffered, plus the state block
_VMEM_LIMIT = 64 * 1024 * 1024


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
            y_ref, s_ref, g_ref, eacs_ref):
    dt = dt_ref[0, 0].astype(jnp.float32)     # (Q, H)
    A = a_ref[...].astype(jnp.float32)        # (1, H)
    B = b_ref[0, 0].astype(jnp.float32)       # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)       # (Q, N)
    q, nh = dt.shape

    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = rows >= cols
    a = dt * A                                 # (Q, H) log-decay
    # cumsum over Q as a lower-triangular matmul, and its transpose (H, Q)
    a_cs = jnp.dot(causal.astype(jnp.float32), a, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)
    a_cs_t = jax.lax.dot_general(a, causal.astype(jnp.float32),
                                 (((0,), (1,)), ((), ())),
                                 precision=_HIGHEST,
                                 preferred_element_type=jnp.float32)
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             precision=_HIGHEST,
                             preferred_element_type=jnp.float32)  # (Q, Q)
    decay_to_end = jnp.exp(a_cs[q - 1:q, :] - a_cs)            # (Q, H)

    for hd in range(nh):
        # causal decay mask  L[i, j] = exp(a_cs[i] - a_cs[j]), i >= j
        decay = jnp.where(causal, jnp.exp(a_cs[:, hd:hd + 1]
                                          - a_cs_t[hd:hd + 1, :]), 0.0)
        dx = x_ref[0, 0, :, hd, :].astype(jnp.float32) * dt[:, hd:hd + 1]
        y_ref[0, 0, :, hd, :] = jnp.dot(cb * decay, dx, precision=_HIGHEST,
                                        preferred_element_type=jnp.float32)
        s_ref[0, 0, hd] = jax.lax.dot_general(
            dx * decay_to_end[:, hd:hd + 1], B, (((0,), (0,)), ((), ())),
            precision=_HIGHEST, preferred_element_type=jnp.float32)
    g_ref[0, 0] = jnp.exp(a_cs[q - 1:q, :])                   # (1, H)
    eacs_ref[0, 0] = jnp.exp(a_cs)


def ssd_chunk_pallas(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                     B: jnp.ndarray, C: jnp.ndarray, *,
                     interpret: bool = False):
    """Intra-chunk SSD terms.

    x: (b, nc, Q, H, P); dt: (b, nc, Q, H); A: (H,); B/C: (b, nc, Q, N).
    Returns (y_diag (b,nc,Q,H,P), S (b,nc,H,P,N), g (b,nc,H),
             exp_acs (b,nc,Q,H))."""
    b, nc, q, h, p = x.shape
    n = B.shape[-1]

    def im_x(i, c):
        return (i, c, 0, 0, 0)

    def im_4(i, c):
        return (i, c, 0, 0)

    y, S, g, eacs = pl.pallas_call(
        _kernel,
        grid=(b, nc),
        in_specs=[
            pl.BlockSpec((1, 1, q, h, p), im_x),
            pl.BlockSpec((1, 1, q, h), im_4),
            pl.BlockSpec((1, h), lambda i, c: (0, 0)),
            pl.BlockSpec((1, 1, q, n), im_4),
            pl.BlockSpec((1, 1, q, n), im_4),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, h, p), im_x),
            pl.BlockSpec((1, 1, h, p, n), im_x),
            pl.BlockSpec((1, 1, 1, h), im_4),
            pl.BlockSpec((1, 1, q, h), im_4),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, q, h, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, 1, h), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, q, h), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x, dt, A.reshape(1, h), B, C)
    return y, S, g[:, :, 0], eacs
