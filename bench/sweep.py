"""Offered-load sweep of one cell, to find the highest rate it sustains.

    python3 bench/sweep.py --workload <cell> --rates 6,8,10 --seconds 20 --seed 1

Serves the cell's traffic at each rate in turn, in this one process, and
prints per rate the answer-time percentiles, the admission wait, the tokens
per second, and how much later the window's last quarter was answered than
its first quarter (a growing backlog shows as a ratio well above 1).  The
cell's ``rate_per_s`` is then set at about four fifths of the highest rate
that kept up.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench.cache import use_compile_cache  # noqa: E402

use_compile_cache()

from bench import run, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args(argv)
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell, _, mix = run.load_cell(spec, a.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    load = traffic.load
    for rate in [float(r) for r in a.rates.split(",")]:
        traffic.load = lambda name, _r=rate: dict(load(name), rate_per_s=_r)
        args = SimpleNamespace(workload=a.workload, seed=a.seed, seconds=a.seconds, trace=0)
        out = run.run_cell(spec, args, chips=cell["chips"], device_kind=devs[0].device_kind)
        m = {k: round(v["value"], 1) for k, v in out["metrics"].items()}
        print(json.dumps({"rate": rate, "failed": out["failed"], "attempted": out["attempted"],
                          "correct": out["correct"], **out["diag"], **m,
                          "peak_bytes": out["device"]["memory_peak_bytes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
