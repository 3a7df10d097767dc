"""The comparison that decides ``correct``: what the timed path served, against
the plain float32 reference run over the same prompts and served tokens.

For each sampled request and each tier that answered it (the S tier always,
the L tier when the request escalated), the reference is run once over the
prompt followed by the served tokens, teacher-forced, and read at every
position that produced a served token.  The numbers compared:

* ``logit_gap``: the widest gap by which a served token's reference logit
  lies below the reference's best logit at that position.  Greedy decoding
  of a sound bf16 path only ever picks a token within rounding of the best.
* ``logit_gap_mean``: the same gap averaged over every read position of both
  tiers.  A position contributes only where the served token is not the
  reference's best, and then by about the served logit's error, so the mean
  grows with the square of the error while the widest gap grows with the
  error: it tells bf16 from int8 weights where the widest gap cannot.
* ``conf_gap``: the largest relative gap between a request's confidence as
  the program's gate computed it (mean max-probability over its S tokens)
  and the same mean over the reference's logits.
* ``escalation_errors``: requests whose escalation decision disagrees with
  the reference confidence against theta, outside a band of
  ``escalation_band`` (relative) around theta (exact: limit 0).
* ``answer_errors``: requests of the window whose answer has another length
  than asked for, or that never got an ``ok`` answer (exact: limit 0).

The control is the same reference with every weight matrix quantized per
output channel (``CONTROLS``: int8, or float8 e4m3), in the program's place:
at the same positions it reads the gap of the token the quantized model puts
first, and its own gate decides escalation.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 4                     # reference rows per call


def int8_cast(a):
    """float32 copy of a weight leaf, matrices fake-quantized to int8 with
    one symmetric scale per output channel (per column of ``x @ W``)."""
    a = a.astype(jnp.float32)
    if a.ndim < 2:
        return a
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=-2, keepdims=True), 1e-30) / 127.0
    return jnp.round(a / s) * s


def fp8_cast(a):
    """float32 copy of a weight leaf, matrices fake-quantized to float8
    (e4m3) with one scale per output channel."""
    a = a.astype(jnp.float32)
    if a.ndim < 2:
        return a
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=-2, keepdims=True), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


CONTROLS = {"int8": int8_cast, "fp8": fp8_cast}
CONTROL = "int8"             # the control the limits were set against


def sample(records: dict, rng, n: int = 8, n_escalated: int = 3) -> list:
    """Request ids to check: the longest answered request, then up to
    ``n_escalated`` escalated ones, then local ones, drawn from ``rng``."""
    ok = [rid for rid, r in records.items() if r["status"] == "ok"]
    if not ok:
        return []
    longest = max(ok, key=lambda rid: len(records[rid]["prompt"]) + len(records[rid]["tokens"]))
    esc = [rid for rid in ok if records[rid]["served_remote"] and rid != longest]
    loc = [rid for rid in ok if not records[rid]["served_remote"] and rid != longest]
    pick = [longest]
    pick += list(rng.choice(esc, min(n_escalated, len(esc)), replace=False)) if esc else []
    rest = n - len(pick)
    pick += list(rng.choice(loc, min(rest, len(loc)), replace=False)) if loc else []
    return [int(r) for r in pick]


@functools.lru_cache(maxsize=None)
def _stats_fn(fam, cfg_json: str, cast):
    """One jitted reference per (family, configuration, cast), reused by
    every run in a process."""
    cfg = json.loads(cfg_json)

    def stats(w, tokens, at, cand):
        with jax.default_matmul_precision("highest"):
            logits = fam.forward(w, cfg, tokens, at, cast=cast)
        best = logits.max(-1)
        gap = best - jnp.take_along_axis(logits, cand[..., None], -1)[..., 0]
        maxprob = jnp.exp(best - jax.nn.logsumexp(logits, -1))
        return gap, maxprob, jnp.argmax(logits, -1).astype(jnp.int32)

    return jax.jit(stats)


class Reference:
    """Teacher-forced reference statistics for one tier at fixed shapes
    (``t_max`` tokens, ``p_max`` read positions, ROWS rows per call)."""

    def __init__(self, fam, cfg: dict, weights, t_max: int, p_max: int, cast=None):
        self.fn = _stats_fn(fam, json.dumps(cfg, sort_keys=True), cast)
        self.w, self.t_max, self.p_max = weights, t_max, p_max

    def run(self, rows, cands=None):
        """rows: (prompt, served tokens); cands: tokens whose gap to read
        (default: the served ones).  Returns per row (gap, maxprob, argmax)
        arrays over the served positions."""
        out = []
        for i in range(0, len(rows), ROWS):
            block = rows[i:i + ROWS]
            tok = np.zeros((ROWS, self.t_max), np.int32)
            at = np.zeros((ROWS, self.p_max), np.int32)
            cand = np.zeros((ROWS, self.p_max), np.int32)
            for j, (prompt, served) in enumerate(block):
                n, m = len(prompt), len(served)
                seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
                tok[j, :len(seq)] = seq
                at[j, :m] = n - 1 + np.arange(m)
                at[j, m:] = n - 1 + m - 1
                c = served if cands is None else cands[i + j]
                cand[j, :m] = c
            with jax.default_matmul_precision("highest"):
                gap, mp, am = jax.device_get(self.fn(self.w, tok, at, cand))
            for j, (_, served) in enumerate(block):
                m = len(served)
                out.append((gap[j, :m], mp[j, :m], am[j, :m]))
        return out


def compare(tiers: dict, picked: list, records: dict, theta: float,
            answer_errors: int, limits: dict, control: bool = False) -> dict:
    """Numbers compared, each {"value", "limit"}.  ``tiers`` maps "S"/"L" to
    (reference, control reference or None); ``limits`` are the
    configuration's (set from readings, see PERF.md); with ``control`` the
    gaps are those of the control's first tokens."""
    rows = {"S": [], "L": []}
    owner = {"S": [], "L": []}
    for rid in picked:
        r = records[rid]
        rows["S"].append((r["prompt"], np.asarray(r["s_tokens"], np.int32)))
        owner["S"].append(rid)
        if r["served_remote"]:
            rows["L"].append((r["prompt"], np.asarray(r["tokens"], np.int32)))
            owner["L"].append(rid)
    gaps, conf_gaps, esc_err = [], [0.0], 0
    for tier in ("S", "L"):
        if not rows[tier]:
            continue
        ref, ctl = tiers[tier]
        ctl_out = ctl.run(rows[tier]) if control else None
        cands = [am for _, _, am in ctl_out] if control else None
        for i, (gap, mp, _) in enumerate(ref.run(rows[tier], cands)):
            gaps.append(gap)
            if tier != "S":
                continue
            r = records[owner[tier][i]]
            conf_ref = float(np.mean(mp))
            if control:     # the control's own gate in the program's place
                conf = float(np.mean(ctl_out[i][1]))
                offloaded = conf < theta
            else:
                conf, offloaded = r["confidence"], r["offloaded"]
            conf_gaps.append(abs(conf - conf_ref) / conf_ref)
            band = limits["escalation_band"] * theta
            if offloaded != (conf_ref < theta) and abs(conf_ref - theta) > band:
                esc_err += 1
    every = np.concatenate(gaps) if gaps else np.zeros(1)
    values = {"logit_gap": float(every.max()), "logit_gap_mean": float(every.mean()),
              "conf_gap": max(conf_gaps),
              "escalation_errors": esc_err, "answer_errors": answer_errors}
    # a number is compared only where its limit could be set between the
    # program's readings and the control's (see PERF.md)
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items() if k in limits}


def passed(numbers: dict) -> bool:
    return all(n["value"] <= n["limit"] for n in numbers.values())
