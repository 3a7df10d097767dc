"""Activation sharding constraints (no-ops outside a mesh context).

GSPMD left to itself shards the residual stream's d_model over `model` and
replicates batch (observed: +80GB/dev on granite train_4k from replicated
logits/scores).  Production frameworks pin activation layouts at layer
boundaries; these helpers do that, keyed off the ambient mesh set by
``with jax.set_mesh(mesh):`` so model code stays mesh-agnostic and tests on 1
CPU device are unaffected.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax import lax
from jax.sharding import AbstractMesh, PartitionSpec as P


def current_mesh() -> Optional[AbstractMesh]:
    """The mesh of the enclosing ``jax.set_mesh`` context (valid inside jit),
    or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def shard_activation(x, *, extra: Tuple[Optional[str], ...] = ()):
    """Constrain a batch-leading activation over the WIDEST dividing set of
    batch axes.  (pod, data, model) when the batch divides all three — the
    ZeRO-DP layout — else (pod, data), else (data,), else unconstrained."""
    mesh = current_mesh()
    if mesh is None:
        return x
    candidates = [("pod", "data", "model"), ("pod", "data"), ("data",)]
    for cand in candidates:
        axes = tuple(a for a in cand if a in mesh.shape)
        if not axes:
            continue
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if x.shape[0] % n == 0:
            rest = list(extra) + [None] * (x.ndim - 1 - len(extra))
            # never reuse an axis already consumed by the batch dim
            rest = [None if r in axes else r for r in rest]
            return lax.with_sharding_constraint(x, P(axes, *rest))
    return x


def shard_hidden(h):
    """(B, S, D) residual stream: batch over (pod, data), D replicated."""
    return shard_activation(h)


def shard_experts(buf):
    """(E, C, D) MoE dispatch buffers: experts over `model`."""
    mesh = current_mesh()
    if mesh is None:
        return buf
    if buf.shape[0] % mesh.shape["model"]:
        return buf
    return lax.with_sharding_constraint(
        buf, P("model", *(None,) * (buf.ndim - 1)))


def shard_logits(logits):
    """(B, S, V) or (B, V): batch over (pod, data); V over model when it
    divides (most vocabs here don't divide 16 — then replicated-V with
    batch sharding is what keeps it small)."""
    mesh = current_mesh()
    if mesh is None:
        return logits
    v = logits.shape[-1]
    v_ax = "model" if v % mesh.shape["model"] == 0 else None
    return shard_activation(logits, extra=(None,) * (logits.ndim - 2) + (v_ax,))
