"""Readings that the limits of ``bench/check.py`` are set from.

    python3 bench/limits.py --workload <cell> --seeds 1,2,3 --control 1,2,3 --seconds 6

Runs the cell once per seed in this one process (the tick program compiles
once), each with a short window at the cell's own load, and prints, per
seed, every number compared for the program as served and, for the seeds
in ``--control``, for each control of ``check.CONTROLS`` on the same
sample.  The last stdout line is a JSON summary: per number, the largest
program reading and each control's smallest reading.  The benchmark's own runs never run the control.
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

from bench import check, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=6.0)
    a = ap.parse_args(argv)
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell, _, _ = run.load_cell(spec, a.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print("needs a TPU", file=sys.stderr)
        return 3
    ctl_seeds = {int(s) for s in a.control.split(",") if s}
    prog, ctl = {}, {}
    for seed in [int(s) for s in a.seeds.split(",")]:
        args = SimpleNamespace(workload=a.workload, seed=seed, seconds=a.seconds, trace=0)
        out = run.run_cell(spec, args, chips=cell["chips"],
                           device_kind=devs[0].device_kind,
                           controls=tuple(check.CONTROLS) if seed in ctl_seeds else ())
        row = {k: v["value"] for k, v in out["compared"].items()}
        ctl_rows = {name: {k: v["value"] for k, v in nums.items()}
                    for name, nums in out["controls"].items()}
        print(json.dumps({"seed": seed, "correct": out["correct"], "program": row,
                          "controls": ctl_rows, "attempted": out["attempted"],
                          "metrics": {k: v["value"] for k, v in out["metrics"].items()}}),
              flush=True)
        for k, v in row.items():
            prog[k] = max(prog.get(k, v), v)
        for name, r in ctl_rows.items():
            for k, v in r.items():
                ctl.setdefault(name, {})[k] = min(ctl.get(name, {}).get(k, v), v)
    print(json.dumps({"program_max": prog, "control_min": ctl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
