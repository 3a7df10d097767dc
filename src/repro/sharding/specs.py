"""Partition rules: param-path -> PartitionSpec.

Name-based rules on the trailing dims of each leaf (leading layer-stack dims
are always unsharded).  A dim is only sharded when the mesh axis size divides
it — otherwise the rule degrades to replication for that dim (GSPMD could pad,
but uneven shards waste the pad fraction; we prefer explicit replication and
report it).

Axes:
  data  — batch / FSDP axis
  model — tensor-parallel / expert-parallel axis
  pod   — multi-pod data-parallel axis (batch is sharded over ("pod","data"))
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig


def _div(dim: int, mesh: Mesh, axis: Optional[str]) -> Optional[str]:
    """axis if it divides dim, else None (replicate)."""
    if axis is None:
        return None
    if dim % mesh.shape[axis] == 0:
        return axis
    return None


def _trailing_spec(path: Tuple[str, ...], shape: Tuple[int, ...], mesh: Mesh,
                   fsdp: bool, tp: bool = True) -> P:
    """Spec for the *trailing* (semantic) dims; leading stack dims -> None.

    ``tp=False`` turns off tensor parallelism for the dense blocks (attention
    / MLP compute replicated over `model`, FSDP storage only) — the right
    layout for small-d_model MoE models where expert parallelism is the only
    `model`-axis consumer (EXPERIMENTS.md §Perf, deepseek hillclimb)."""
    name = path[-1]
    parents = set(path[:-1])
    d_axis = "data" if fsdp else None
    m_axis = "model" if tp else None

    def spec2(rows: Optional[str], cols: Optional[str]) -> P:
        lead = (None,) * (len(shape) - 2)
        return P(*lead, _div(shape[-2], mesh, rows), _div(shape[-1], mesh, cols))

    def spec1(ax: Optional[str]) -> P:
        lead = (None,) * (len(shape) - 1)
        return P(*lead, _div(shape[-1], mesh, ax))

    # ---- embeddings / head --------------------------------------------------
    # embed is NOT vocab-sharded: a gather over a vocab-sharded table trips
    # GSPMD "involuntary full rematerialization" (replicated (B,S,D) + logits)
    # — observed +78GB/dev on granite train_4k.  D shards over data (FSDP);
    # the lm_head vocab-shards over model so logits come out (data, -, model).
    if name == "embed":
        return spec2(None, d_axis)
    if name == "lm_head":
        return spec2(None, "model")
    if name in ("enc_pos", "dec_pos"):
        return P(*(None,) * len(shape))

    # ---- experts: expert-parallel over model, FF-hidden over data -----------
    # (F-sharded storage matches the shard_map dispatch's token-move schedule;
    #  see models/moe.py)
    if "experts" in parents:
        lead = (None,) * (len(shape) - 3)
        e = shape[-3]
        if name in ("wi", "wg"):        # (E, D, F): F is dim -1
            return P(*lead, _div(e, mesh, "model"), None,
                     _div(shape[-1], mesh, d_axis))
        if name == "wo":                 # (E, F, D): F is dim -2
            return P(*lead, _div(e, mesh, "model"),
                     _div(shape[-2], mesh, d_axis), None)
        return P(*lead, _div(e, mesh, "model"), None, None)
    if name == "router":
        return P(*(None,) * len(shape))

    # ---- attention -----------------------------------------------------------
    if parents & {"attn", "self_attn", "cross_attn"}:
        if name in ("wq", "wk", "wv"):
            return spec2(d_axis, m_axis)
        if name == "wo":
            return spec2(m_axis, d_axis)
        if name in ("bq", "bk", "bv"):
            return spec1(m_axis)

    # ---- MLPs ------------------------------------------------------------------
    if parents & {"mlp", "shared", "dense"}:
        if name in ("wi", "wg"):
            return spec2(d_axis, m_axis)
        if name == "wo":
            return spec2(m_axis, d_axis)
        if name == "bi":
            return spec1(m_axis)
        if name == "bo":
            return spec1(None)

    # ---- mamba2 ---------------------------------------------------------------
    if name in ("in_z", "in_xbc", "in_dt"):   # (D, di | di+2Gn | h): the
        return spec2(d_axis, None)   # conv/SSM read whole rows; FSDP rows only
    if name == "out_proj":      # (di, D): di is head-major -> TP over model
        return spec2("model", d_axis)
    if name in ("conv_w", "conv_b", "A_log", "D", "dt_bias"):
        return P(*(None,) * len(shape))

    # ---- norms / scalars -------------------------------------------------------
    return P(*(None,) * len(shape))


def param_specs(params_tree: Any, mesh: Mesh, *, fsdp: bool = False,
                tp: bool = True) -> Any:
    """Pytree of PartitionSpec matching ``params_tree`` (arrays or SDS)."""

    def rule(path, leaf):
        names = tuple(
            p.key if hasattr(p, "key") else str(p) for p in path)
        return _trailing_spec(names, leaf.shape, mesh, fsdp, tp)

    return jax.tree_util.tree_map_with_path(rule, params_tree)


def param_shardings(params_tree: Any, mesh: Mesh, *, fsdp: bool = False,
                    tp: bool = True) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params_tree, mesh, fsdp=fsdp, tp=tp))


# ---------------------------------------------------------------------------
# activations / inputs / caches
# ---------------------------------------------------------------------------

def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def data_spec(mesh: Mesh, batch: int, ndim: int) -> P:
    """Batch-leading input: shard dim 0 over (pod, data) when divisible."""
    axes = batch_axes(mesh)
    total = 1
    for a in axes:
        total *= mesh.shape[a]
    lead = axes if (total and batch % total == 0) else None
    return P(lead, *(None,) * (ndim - 1))


def cache_specs(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig) -> Any:
    """Sharding for the decode cache pytree.

    decode_32k: batch over (pod, data).  long_500k (batch=1): KV-cache
    *sequence parallelism* — the seq dim shards over data instead.
    """
    axes = batch_axes(mesh)
    total = 1
    for a in axes:
        total *= mesh.shape[a]
    batch_ok = shape.global_batch % total == 0

    def rule(path, leaf):
        names = tuple(p.key if hasattr(p, "key") else str(p) for p in path)
        name = names[-1]
        nd = len(leaf.shape)
        if name == "pos":
            return P()
        if name in ("k", "v", "ck", "cv"):
            # (L|G, B, S, K, Dh) — sequence-parallel KV cache: S shards over
            # `model` (batch already over data/pod).  Decode attention over a
            # sharded S costs only tiny softmax-stat + output all-reduces,
            # while cache HBM and the (B,H,S) score row spread over all chips.
            if nd != 5:
                return P(*(None,) * nd)
            s_dim = leaf.shape[2]
            if batch_ok:
                s_ax = "model" if s_dim % mesh.shape["model"] == 0 else None
                return P(None, axes, s_ax, None, None)
            # batch=1 (long_500k): spread S over every available axis
            flat = tuple(a for a in ("data", "model") if a in mesh.shape)
            tot = 1
            for a in flat:
                tot *= mesh.shape[a]
            s_ax2 = flat if s_dim % tot == 0 else "data"
            return P(None, None, s_ax2, None, None)
        if name == "state":
            # ssm state (L, B, H, P, N) or (G, k, B, H, P, N)
            lead = (None,) * (nd - 4)
            return P(*lead, axes if batch_ok else None,
                     _div(leaf.shape[-3], mesh, "model"), None, None)
        if name == "conv":
            lead = (None,) * (nd - 3)
            return P(*lead, axes if batch_ok else None, None, None)
        return P(*(None,) * nd)

    return jax.tree_util.tree_map_with_path(rule, shape_tree(cfg, shape))


def shape_tree(cfg: ModelConfig, shape: ShapeConfig):
    from repro.models import model_zoo
    return model_zoo.cache_spec(cfg, shape.global_batch, shape.seq_len)


def paged_cache_specs(cfg: ModelConfig, mesh: Mesh, buffers: Any) -> Any:
    """Sharding for a serving-tier PAGED pool pytree (``KVPool.buffers``).

    The L tier of the mesh-sharded scheduler keeps ONE pool whose page
    tensors shard over ``model`` on the KV heads — the same tensor-
    parallel cut as the attention projections, so the page-gather feeding a
    head group reads only that group's local pages.  Per leaf:

    * ``kp`` / ``vp`` (L, P, page, K·Dh): the folded head dim over
      ``model`` when ``model`` divides K — each head's Dh lanes are
      contiguous in it, so a shard holds whole heads; else replicated;
    * ``ks`` / ``vs`` int8-pool scales (L, P, K): K over ``model``;
    * everything else (recurrent ``state`` / ``conv`` rows, logits) —
      replicated: per-slot state is small and the slot dim is the DATA-axis
      concern, which the S tier handles by replica-stacking, not sharding.
    """
    heads = _div(cfg.num_kv_heads, mesh, "model")

    def rule(path, leaf):
        names = tuple(p.key if hasattr(p, "key") else str(p) for p in path)
        name = names[-1]
        nd = len(leaf.shape)
        if name in ("kp", "vp") and nd == 4:
            return P(None, None, None, heads)
        if name in ("ks", "vs") and nd == 3:
            return P(None, None, _div(leaf.shape[2], mesh, "model"))
        return P(*(None,) * nd)

    return jax.tree_util.tree_map_with_path(rule, buffers)


def paged_cache_shardings(cfg: ModelConfig, mesh: Mesh, buffers: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        paged_cache_specs(cfg, mesh, buffers))
