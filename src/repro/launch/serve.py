"""Serving launcher: requests through the HI cascade's continuous-batching
path (``HIEngine.serve_stream``).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --requests 32 --slots 8 --theta 0.6

``--no-reduced`` runs the full published config with bf16 params (sized for
one TPU v5e chip; not CPU-friendly).
"""
from __future__ import annotations

import argparse
import time
from collections import Counter

import jax.numpy as jnp
import numpy as np

from repro.configs.base import HIConfig
from repro.configs.registry import ARCHS
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model_zoo import PAGED_FAMILIES
from repro.serving.batcher import Request
from repro.serving.engine import build_engine

BUCKETS = (16, 32)


def run(arch: str, *, reduced: bool = True, requests: int = 32,
        slots: int = 8, theta: float = 0.6, seed: int = 0,
        max_new_tokens: int = 8, metric: str = "max_prob"):
    cfg = ARCHS[arch]
    if reduced:
        cfg = cfg.reduced()
    if cfg.family not in PAGED_FAMILIES:
        raise SystemExit(f"the serve launcher covers the paged decoder "
                         f"families; {cfg.family} is exercised via dryrun + "
                         "smoke tests")
    hi = HIConfig(theta=theta, metric=metric)
    dtype = jnp.float32 if reduced else jnp.bfloat16
    engine = build_engine(cfg, hi, dtype=dtype, max_new_tokens=max_new_tokens)

    rng = np.random.default_rng(seed)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, int(
        rng.integers(4, BUCKETS[-1]))).astype(np.int32))
        for i in range(requests)]

    t0 = time.perf_counter()
    out = engine.serve_stream(reqs, buckets=BUCKETS, num_slots=slots)
    dt = time.perf_counter() - t0
    statuses = Counter(str(r["status"]) for r in out.values())
    s = engine.summary()
    print(f"served {len(out)} requests in {dt:.1f}s (compile included) | "
          f"statuses {dict(statuses)} | offload_frac {s['offload_frac']:.2%} "
          f"| {int(s['stream_compiles'])} compiled tick, "
          f"{int(s['stream_ticks'])} ticks")
    return s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke-scale config (disable with --no-reduced)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--theta", type=float, default=0.6)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--metric", default="max_prob",
                    choices=["max_prob", "margin", "entropy"])
    args = ap.parse_args()
    enable_compile_cache()
    run(args.arch, reduced=args.reduced, requests=args.requests,
        slots=args.slots, theta=args.theta,
        max_new_tokens=args.max_new_tokens, metric=args.metric)


if __name__ == "__main__":
    main()
