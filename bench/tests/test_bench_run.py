"""``bench/run.py`` as its command line runs it: no TPU means a non-zero exit and
no result line; a cell added by files alone is found by name."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

from helpers import ROOT
from bench import run, traffic


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "qwen2-1.5b.chat",
                        "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no result" in p.stderr


def test_cell_added_by_files_is_found(tmp_path, monkeypatch):
    (tmp_path / "mix-new.json").write_text(json.dumps(
        {"name": "mix-new", "rate_per_s": 3.0, "warm_s": 1.0,
         "prompt": {"median": 20, "sigma": 0.5, "min": 8, "max": 40},
         "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16}}))
    (tmp_path / "new.metric.py").write_text(
        "def read(run):\n    return 2.0 * len(run.records)\n")
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", tmp_path)
    monkeypatch.setattr(run, "METRICS_DIR", tmp_path)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "qwen2-1.5b.new", "config": "qwen2-1.5b",
                              "traffic": "mix-new", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "new.metric", "unit": "ratio", "better": "higher",
                              "source": "program_counter", "layer": "gate",
                              "moves": "answer_ms_p50"})
    cell, config, mix = run.load_cell(spec, "qwen2-1.5b.new")
    assert config["name"] == "qwen2-1.5b" and mix["rate_per_s"] == 3.0
    only_new = dict(spec, per_layer=spec["per_layer"][-1:])
    read, unit = run.metric_readers(only_new)["new.metric"]
    assert unit == "ratio" and read(SimpleNamespace(records={1: 0, 2: 0})) == 4.0


def test_readers_with_nothing_to_read_return_none():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    empty = SimpleNamespace(window=(0.0, 1.0), window_ticks=[], in_window=set(), traces={},
                            records={}, by_id={}, saved=[], trace=None, traced=None,
                            peak=None, work=[])
    for name, (read, _) in run.metric_readers(spec).items():
        assert read(empty) is None, name
