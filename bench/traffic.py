"""The one traffic generator: turns a mix's data file into seeded requests.

A mix (``bench/traffic/<name>.json``) holds only parameters:

* ``rate_per_s``: offered requests per second (open loop, fixed per cell);
* ``warm_s``: seconds of the same traffic served before the window opens;
* ``prompt`` / ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal length in tokens, clipped;
* ``sessions`` (optional): ``{"turns", "gap_mean_s", "system_prompts",
  "system_len", "zipf_s", "turn_tokens"}``: multi-turn sessions.  A session
  opens with one of the shared system prompts (Zipf-weighted) and every turn
  appends ``turn_tokens`` fresh tokens to the session's prompt; a turn is
  ready an exponential gap after the previous one.  Without ``sessions``
  every request is an independent prompt of uniform random ids.

Arrivals are generated per segment (warm-in, window, drain): a segment of
``T`` seconds holds exactly ``rate * T`` requests, placed by exponential
inter-arrival gaps (stratified quantiles in a seeded order) scaled to span
the segment.  Lengths are stratified quantiles too.  So every seed offers
the window the same number of requests and the same multiset of lengths and
gaps, in another order, with fresh token ids: the seed changes which request
is which and what it says, not how much work the window holds.  In a
session mix each arrival continues the open session whose next turn has
been ready longest, or opens a new session when none is ready.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
SYSTEM_STREAM = 1000


@dataclass
class Arrival:
    due_s: float                 # seconds after the traffic starts
    prompt: np.ndarray           # int32 token ids
    max_new: int                 # answer tokens (no EOS)


def load(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    z = np.asarray([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    q = spec["median"] * np.exp(spec["sigma"] * z)
    out = np.clip(np.rint(q), spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(out)


def _gaps(mean_s: float, n: int, rng) -> np.ndarray:
    return rng.permutation(-mean_s * np.log1p(-_quantiles(n)))


def _times(rate: float, bounds, rng) -> tuple[np.ndarray, list]:
    """Due times of every segment, and the request count of each."""
    due, counts = [], []
    for a, b in zip(bounds[:-1], bounds[1:]):
        n = int(round(rate * (b - a)))
        g = _gaps(1.0, n, rng)
        c = np.cumsum(g)
        due.append(a + (b - a) * (c - 0.5 * g) / c[-1] if n else np.zeros(0))
        counts.append(n)
    return np.concatenate(due), counts


def _session_prompts(sess: dict, due: np.ndarray, vocab: int, rng, system) -> list:
    n = len(due)
    turns = int(sess["turns"])
    k = int(sess["system_prompts"])
    w = 1.0 / np.arange(1, k + 1) ** float(sess["zipf_s"])
    counts = np.floor(w / w.sum() * n).astype(int)
    counts[np.argsort(counts - w / w.sum() * n)[: n - counts.sum()]] += 1
    which = iter(rng.permutation(np.repeat(np.arange(k), counts)))
    gaps = iter(_gaps(sess["gap_mean_s"], n, rng))
    open_ = []                   # [ready time, prompt, turns done]
    prompts = []
    for t in due:
        ready = [s for s in open_ if s[0] <= t]
        if ready:
            s = min(ready, key=lambda s: s[0])
        else:
            s = [0.0, system[next(which)], 0]
            open_.append(s)
        tail = rng.integers(0, vocab, int(sess["turn_tokens"])).astype(np.int32)
        s[1] = np.concatenate([s[1], tail])
        s[2] += 1
        prompts.append(s[1])
        if s[2] == turns:
            open_.remove(s)
        else:
            s[0] = t + next(gaps)
    return prompts


def generate(mix: dict, vocab: int, seed: int, bounds, stream: int = 0) -> list[Arrival]:
    """Arrivals over the segments ``bounds`` = (t0, t1, ..., tn) seconds,
    sorted by due time.  ``stream`` separates independent draws from one
    ``seed`` (the window's traffic and the calibration pass use different
    streams)."""
    rng = np.random.default_rng([seed, stream])
    due, counts = _times(float(mix["rate_per_s"]), list(bounds), rng)
    out_len = np.concatenate([_lengths(mix["output"], n, rng) for n in counts])
    sess = mix.get("sessions")
    if sess is None:
        plen = np.concatenate([_lengths(mix["prompt"], n, rng) for n in counts])
        prompts = [rng.integers(0, vocab, int(k)).astype(np.int32) for k in plen]
    else:
        # the system prompts are the deployment's: one draw per seed, shared
        # by every stream (the calibration pass sees the window's prompts)
        system = np.random.default_rng([seed, SYSTEM_STREAM]).integers(
            0, vocab, (int(sess["system_prompts"]), int(sess["system_len"]))).astype(np.int32)
        prompts = _session_prompts(sess, due, vocab, rng, system)
    return [Arrival(float(t), p, int(m)) for t, p, m in zip(due, prompts, out_len)]
