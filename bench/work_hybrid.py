"""FLOPs and HBM bytes of the useful work of a Zamba2 tier in one scheduler
tick, from shapes (the hybrid's count beside ``bench/work.py``'s dense one).

Only useful work counts, as in ``bench/work.py``: prompt tokens and decode
steps that emit a token, never padded rows.  Per token:

* the mamba layers' projections (in, out) and causal conv, and the SSM
  recurrence over the state (h x p x n per layer: decay, input and output);
* every shared-block application: q/k/v over the 2d-wide concat, o, the MLP
  with this application's adapter on gate_up, and its linear;
* attention over the live context of each application.

Bytes: each tier's weights once per admission pass and once per decode step
with a live slot (a shared block is read once, however often applied); the
K/V of the live context read and the new K/V written; each live slot's
recurrent state (state and conv window, every layer) read and written once
per decode step, and written once per admitted prompt.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Sequence, Tuple

from bench import work

BF16, F32 = 2, 4


@dataclass(frozen=True)
class HybridShape:
    body_flops: int           # FLOPs per token, head and attention scores excluded
    head_params: int          # d_model x vocab
    weight_bytes: int         # every weight read once
    attn_apps: int            # shared-block applications per token
    q_width: int              # query heads x head_dim
    kv_bytes_per_token: int   # K and V of one position, all applications
    state_bytes: int          # recurrent state and conv window of one slot


def hybrid_shape(cfg: dict) -> HybridShape:
    """Shape summary of one tier of a Zamba2 configuration (the keys of
    ``bench/configs/zamba2-7b.json``)."""
    d, v, n_layers = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    f, r = cfg["intermediate_size"], cfg["adapter_rank"]
    g, n, p, w = (cfg["mamba_ngroups"], cfg["mamba_d_state"], cfg["mamba_headdim"],
                  cfg["mamba_d_conv"])
    nh = cfg["n_mamba_heads"]
    di = nh * p
    cd = di + 2 * g * n
    hq = cfg["num_attention_heads"] * cfg["attention_head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["attention_head_dim"]
    apps = len([i for i in cfg["hybrid_layer_ids"] if i < n_layers])
    mamba_params = d * (di + cd + nh) + di * d
    block_params = 2 * d * (hq + 2 * hkv) + hq * d + d * 2 * f + f * d
    app_params = d * r + r * 2 * f + d * d
    body_flops = (n_layers * (2 * mamba_params + 2 * w * cd + 5 * nh * p * n)
                  + apps * 2 * (block_params + app_params))
    weights = (n_layers * (mamba_params + w * cd) + cfg["num_mem_blocks"] * block_params
               + apps * app_params + d * v)
    return HybridShape(
        body_flops=body_flops, head_params=d * v, weight_bytes=BF16 * weights,
        attn_apps=apps, q_width=hq, kv_bytes_per_token=apps * 2 * hkv * BF16,
        state_bytes=n_layers * (F32 * nh * p * n + BF16 * (w - 1) * cd))


def prefill_work(shape: HybridShape, prompts: Sequence[int], saved: int,
                 restored: int) -> Tuple[float, float]:
    """One admission pass: ``prompts`` are the admitted prompt lengths,
    ``saved`` the prompt tokens served from the prefix cache and
    ``restored`` the admissions that were whole-prompt hits (the hybrid's
    only kind: no compute, their state restored from a snapshot row).
    Attention FLOPs are left out in a tick with a hit, as in
    ``bench/work.py``."""
    tokens = sum(prompts) - saved
    rows = len(prompts) - restored
    if tokens <= 0 or rows <= 0:
        return 0.0, 0.0
    flops = shape.body_flops * tokens + 2 * shape.head_params * rows
    if saved == 0:
        flops += sum(4 * shape.q_width * shape.attn_apps * m * (m + 1) // 2
                     for m in prompts)
    nbytes = (shape.weight_bytes + tokens * shape.kv_bytes_per_token
              + rows * shape.state_bytes)
    return float(flops), float(nbytes)


def decode_work(shape: HybridShape, slots: Iterable[Tuple[int, int]]
                ) -> Tuple[float, float]:
    """Fused decode steps: ``slots`` holds (context before the block's first
    step, tokens the block emitted) per live slot."""
    flops = nbytes = 0
    live_steps = 0
    per_token = shape.body_flops + 2 * shape.head_params
    for c0, emitted in slots:
        live_steps = max(live_steps, emitted)
        for k in range(emitted):
            c = c0 + k + 1
            flops += per_token + 4 * shape.q_width * shape.attn_apps * c
            nbytes += c * shape.kv_bytes_per_token + 2 * shape.state_bytes
    nbytes += live_steps * shape.weight_bytes
    return float(flops), float(nbytes)


def tick_work(run) -> list:
    """(tick, flops, bytes) of every tick's useful work, walking the same
    ``admitted`` and ``decode_block`` spans and prefix counters as
    ``bench/run.py: tick_work``."""
    shapes = {tier: hybrid_shape(cfg) for tier, cfg in run.cfgs.items()}
    spans = {}
    for tr in run.traces.values():
        for sp in tr.spans:
            if sp.kind in ("admitted", "decode_block"):
                spans.setdefault(sp.t0, []).append((tr.rid, sp))
    saved = dict(run.saved)
    prev = run.saved[0][1]
    out = []
    for tk in run.ticks:
        cur = saved.get(tk.t0)
        flops = nbytes = 0.0
        for tier, shape in shapes.items():
            prompts, slots = [], []
            for rid, sp in spans.get(tk.t0, ()):
                if sp.tier != tier or rid not in run.by_id:
                    continue
                adm = run.by_id[rid]
                n, m = adm.bucket, adm.request.max_new_tokens
                if sp.kind == "admitted":
                    prompts.append(n)
                else:
                    j, k = sp.args["j"], run.decode_block
                    emitted = max(0, min(k, m - 1 - k * j))
                    if emitted:
                        slots.append((n + k * j, emitted))
            d_saved = d_full = 0
            if cur is not None:
                d_saved = cur[tier][0] - prev[tier][0]
                d_full = cur[tier][1] - prev[tier][1]
            for f, b in (prefill_work(shape, prompts, d_saved, d_full),
                         decode_work(shape, slots)):
                flops += f
                nbytes += b
        if cur is not None:
            prev = cur
        out.append((tk, flops, nbytes))
    return out


least_time = work.least_time     # the same bound, shared
