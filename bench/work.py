"""FLOPs and HBM bytes of the useful work in one scheduler tick, from shapes.

Only useful work counts, so that a share reads the same whatever implements
it and a change that skips padding raises it:

* prompt tokens that are not served from the prefix cache, and decode steps
  that emit a token (live slots, live context), never padded rows;
* each tier's weights read once per admission pass and once per decode step
  that has a live slot;
* the K/V of the live context read, and the new K/V written.

Attention FLOPs of a prompt pass are left out for a tier in a tick in which
part of some prompt came from the prefix cache (which rows it was is not
observable from outside), so the count stays a lower bound.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Sequence, Tuple

BF16 = 2


@dataclass(frozen=True)
class TierShape:
    body_params: int          # matmul weights a token passes through, head excluded
    head_params: int          # d_model x vocab
    weight_bytes: int         # every weight read once
    attn_apps: int            # attention applications per token
    q_width: int              # query heads x head_dim
    kv_bytes_per_token: int   # K and V of one position, all applications


def tier_shape(cfg: dict) -> TierShape:
    """Shape summary of one tier of a dense configuration."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["intermediate_size"]
    n_layers = cfg["num_hidden_layers"]
    dh = cfg.get("head_dim") or d // h
    body = n_layers * (d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f)
    return TierShape(body_params=body, head_params=d * v,
                     weight_bytes=BF16 * (body + d * v), attn_apps=n_layers,
                     q_width=h * dh,
                     kv_bytes_per_token=n_layers * 2 * kv * dh * BF16)


def prefill_work(shape: TierShape, prompts: Sequence[int], saved: int,
                 restored: int) -> Tuple[float, float]:
    """One admission pass: ``prompts`` are the admitted prompt lengths,
    ``saved`` the prompt tokens served from the prefix cache and
    ``restored`` the admissions that were whole-prompt hits (no compute)."""
    tokens = sum(prompts) - saved
    rows = len(prompts) - restored
    if tokens <= 0 or rows <= 0:
        return 0.0, 0.0
    flops = 2 * shape.body_params * tokens + 2 * shape.head_params * rows
    if saved == 0:
        flops += sum(4 * shape.q_width * shape.attn_apps * n * (n + 1) // 2
                     for n in prompts)
    nbytes = shape.weight_bytes + tokens * shape.kv_bytes_per_token
    return float(flops), float(nbytes)


def decode_work(shape: TierShape, slots: Iterable[Tuple[int, int]]
                ) -> Tuple[float, float]:
    """Fused decode steps: ``slots`` holds (context before the block's first
    step, tokens the block emitted) per live slot."""
    flops = nbytes = 0
    live_steps = 0
    per_token = 2 * (shape.body_params + shape.head_params)
    for c0, emitted in slots:
        live_steps = max(live_steps, emitted)
        for k in range(emitted):
            c = c0 + k + 1
            flops += per_token + 4 * shape.q_width * shape.attn_apps * c
            nbytes += c * shape.kv_bytes_per_token
    nbytes += live_steps * shape.weight_bytes
    return float(flops), float(nbytes)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline's lower bound on the time of this work, seconds."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
