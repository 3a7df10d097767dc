"""Shared set-up of the benchmark's CPU tests: the repository root on the
path, and a spec of one tiny cell whose configuration and traffic live in
``bench/tests/data``."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_spec(mix: str = "chat") -> dict:
    return {"configs": [{"name": "tiny", "file": "bench/tests/data/tiny-dense.json"}],
            "workloads": [{"name": "tiny.cell", "config": "tiny",
                           "traffic": f"tiny-{mix}", "chips": 1}],
            "per_layer": []}


def args(seed: int, seconds: float = 2.0, trace: int = 0):
    return SimpleNamespace(workload="tiny.cell", seed=seed, seconds=seconds, trace=trace)
