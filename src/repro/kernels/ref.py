"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.confidence import confidence as _confidence
from repro.models import layers as L
from repro.models import mamba2


def hi_gate_ref(logits: jnp.ndarray, theta: float, metric: str = "max_prob"
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(N, C) -> (conf f32, pred i32, offload i32)."""
    conf = _confidence(logits, metric)
    pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    offload = (conf < theta).astype(jnp.int32)
    return conf.astype(jnp.float32), pred, offload


def decode_attention_ref(q: jnp.ndarray, cache_k: jnp.ndarray,
                         cache_v: jnp.ndarray, valid: jnp.ndarray
                         ) -> jnp.ndarray:
    """q: (B,1,H,D); cache: (B,S,K,D); valid: (S,) -> (B,1,H,D)."""
    mask = valid[None, None, :]
    return L._sdpa(q, cache_k, cache_v, mask)


def ssd_ref(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray,
            C: jnp.ndarray, chunk: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Delegates to the model's chunked-jnp implementation."""
    return mamba2.ssd_chunked(x, dt, A, B, C, chunk)


def ssd_naive_ref(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                  B: jnp.ndarray, C: jnp.ndarray) -> jnp.ndarray:
    """O(L^2)-free sequential recurrence — the ground-truth semantics:
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T;   y_t = C_t . h_t
    Used to validate ssd_chunked itself."""
    b, l, h, p = x.shape
    n = B.shape[-1]

    def step(hprev, inp):
        x_t, dt_t, B_t, C_t = inp
        y, hnew = mamba2.ssd_recurrent_step(hprev, x_t, dt_t, A, B_t, C_t)
        return hnew, y

    h0 = jnp.zeros((b, p, n, h), jnp.float32)     # the decode cache's layout
    _, ys = jax.lax.scan(step, h0, (x.transpose(1, 0, 2, 3),
                                    dt.transpose(1, 0, 2),
                                    B.swapaxes(0, 1),
                                    C.swapaxes(0, 1)))
    return ys.transpose(1, 0, 2, 3)
