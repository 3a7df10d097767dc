"""hi_gate — fused HI decision module as a Pallas TPU kernel.

One VMEM pass over the S-tier logits computes softmax statistics, the
confidence metric (max-prob / margin / entropy), the argmax prediction and
the threshold decision.  On a TPU serving tier this fuses what would
otherwise be 4 HBM round-trips over the (batch, num_classes) logits into one.

Tiling: grid over row blocks; each block holds (block_n, C) logits in VMEM.
``block_n`` is the whole batch or a multiple of 8 (the TPU's sublane tile),
chosen so the tile stays near the VMEM budget; the scoped-VMEM limit is
raised to what a full-vocabulary tile and its fp32 temporaries need
(152k-token qwen2 rows, 262k-token gemma3 rows).  C is never split: every
confidence metric is a full-row reduction, so splitting C would force
cross-block softmax renormalisation for no win — the row dimension
provides all the parallelism the VPU needs.  Outputs are (N, 1) columns so
every block's trailing dims are legal TPU tiles.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM tile budget for the logits block (bytes); v5e VMEM is ~16 MiB, leave
# headroom for the fp32 softmax intermediates (~3x the tile).
_VMEM_TILE_BUDGET = 4 * 1024 * 1024
# ceiling for the raised scoped-VMEM limit (v5e VMEM is 128 MiB)
_VMEM_LIMIT_CAP = 100 * 1024 * 1024


def _pick_block_n(n: int, c: int, itemsize: int) -> int:
    """Rows per tile: all N when they fit the budget, else the largest
    multiple of 8 within it (at least 8) that divides N, else all N."""
    rows = max(1, _VMEM_TILE_BUDGET // max(1, c * itemsize))
    if rows >= n:
        return n
    for bn in range(max(8, rows // 8 * 8), 7, -8):
        if n % bn == 0:
            return bn
    return n


def _vmem_limit(bn: int, c: int, itemsize: int) -> int:
    """Double-buffered input tile + ~4 fp32 row temporaries + slack."""
    need = bn * c * (2 * itemsize + 16) + (2 << 20)
    return min(max(need, 32 << 20), _VMEM_LIMIT_CAP)


def _kernel(logits_ref, theta_ref, conf_ref, pred_ref, off_ref, *,
            metric: str):
    theta = theta_ref[0, 0]
    x = logits_ref[...].astype(jnp.float32)                    # (bn, C)
    c = x.shape[-1]
    m = jnp.max(x, axis=-1, keepdims=True)
    ex = jnp.exp(x - m)
    z = jnp.sum(ex, axis=-1, keepdims=True)
    # argmax as the first column holding the row max (ties -> lowest index)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    pred = jnp.min(jnp.where(x == m, cols, c), axis=-1, keepdims=True)

    if metric == "max_prob":
        conf = jnp.max(ex, axis=-1, keepdims=True) / z
    elif metric == "margin":
        p = ex / z
        top1 = jnp.max(p, axis=-1, keepdims=True)
        # second max: mask out the argmax column
        p2 = jnp.where(cols == pred, -1.0, p)
        conf = top1 - jnp.max(p2, axis=-1, keepdims=True)
    elif metric == "entropy":
        p = ex / z
        logp = (x - m) - jnp.log(z)
        h = -jnp.sum(p * logp, axis=-1, keepdims=True)
        conf = 1.0 - h / jnp.log(float(c))
    else:
        raise ValueError(metric)

    conf_ref[...] = conf
    pred_ref[...] = pred
    off_ref[...] = (conf < theta).astype(jnp.int32)


def hi_gate_pallas(logits: jnp.ndarray, theta, metric: str = "max_prob",
                   interpret: bool = False
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """logits: (N, C) -> (conf (N,) f32, pred (N,) i32, offload (N,) i32).

    ``theta`` may be a python float or a traced fp32 scalar — it enters the
    kernel as a (1, 1) operand (broadcast to every grid step), so online
    policies can move the threshold every batch without recompiling.
    """
    n, c = logits.shape
    bn = _pick_block_n(n, c, logits.dtype.itemsize)
    grid = (n // bn,)
    theta_arr = jnp.asarray(theta, jnp.float32).reshape(1, 1)
    kernel = functools.partial(_kernel, metric=metric)
    col = pl.BlockSpec((bn, 1), lambda i: (i, 0))
    conf, pred, off = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bn, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=[col, col, col],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit(bn, c, logits.dtype.itemsize)),
        interpret=interpret,
    )(logits, theta_arr)
    return conf[:, 0], pred[:, 0], off[:, 0]
