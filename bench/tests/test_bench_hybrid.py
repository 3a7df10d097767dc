"""The hybrid's count of useful work (``bench/work_hybrid.py``) against the
sizes reckoned by hand for zamba2-7b's L tier, its reader, and the tiny
Zamba2 cell run through the harness on the CPU."""
import json
from types import SimpleNamespace

from helpers import DATA, ROOT, args
from bench import run, traffic, work_hybrid


def _l_tier():
    return run.tier_configs(json.loads((ROOT / "bench/configs/zamba2-7b.json").read_text()))["L"]


def test_hybrid_shape_at_published_widths():
    """Layers 0-23: a mamba layer 78.4 M matmul weights, a shared block 334
    M, an application's adapter and linear 17.0 M, four applications; 45.1
    MB of recurrent state a slot; 112 KB of K/V a token; 6.6 GFLOP a decode
    token before the head and the attention scores."""
    s = work_hybrid.hybrid_shape(_l_tier())
    d, mamba, block, app = 3584, 78_389_248, 333_971_456, 16_973_824
    assert s.attn_apps == 4 and s.q_width == 32 * 224
    assert s.state_bytes == 24 * (4 * 112 * 64 * 64 + 2 * 3 * 7424)
    assert s.kv_bytes_per_token == 4 * 2 * 32 * 224 * 2 == 114_688
    conv_ssm = 2 * 4 * 7424 + 5 * 112 * 64 * 64
    assert s.body_flops == 24 * (2 * mamba + conv_ssm) + 4 * 2 * (block + app)
    assert 6.5e9 < s.body_flops < 6.7e9
    # the shared blocks are read once per step, though applied twice each
    assert s.weight_bytes == 2 * (24 * (mamba + 4 * 7424) + 2 * block + 4 * app
                                  + d * 32000)


def test_decode_and_prefill_count_live_work_only():
    s = work_hybrid.hybrid_shape(_l_tier())
    f, b = work_hybrid.decode_work(s, [(20, 2), (5, 1)])
    attn = 4 * s.q_width * s.attn_apps * (21 + 22 + 6)
    assert f == 3 * (s.body_flops + 2 * s.head_params) + attn
    assert b == (21 + 22 + 6) * s.kv_bytes_per_token + 3 * 2 * s.state_bytes \
        + 2 * s.weight_bytes
    f, b = work_hybrid.prefill_work(s, [10, 6], saved=0, restored=0)
    attn = 4 * s.q_width * s.attn_apps * (10 * 11 // 2 + 6 * 7 // 2)
    assert f == 16 * s.body_flops + 2 * 2 * s.head_params + attn
    assert b == s.weight_bytes + 16 * s.kv_bytes_per_token + 2 * s.state_bytes
    assert work_hybrid.prefill_work(s, [10], saved=10, restored=1) == (0.0, 0.0)


def test_reader_finds_nothing_outside_a_hybrid_cell():
    spec = {"per_layer": [{"name": "hybrid.tick_roofline", "unit": "%"}]}
    read, _ = run.metric_readers(spec)["hybrid.tick_roofline"]
    assert read(SimpleNamespace(trace=None, peak={}, config={"family": "zamba2"})) is None
    assert read(SimpleNamespace(trace=object(), peak={}, config={"family": "dense"})) is None


def test_tiny_hybrid_cell_is_correct(monkeypatch):
    """The tiny Zamba2 configuration served through ``bench/run.py``'s
    set-up, window and reference check, as the zamba2-7b cell is."""
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", DATA)
    spec = {"configs": [{"name": "tiny-zamba2", "file": "bench/tests/data/tiny-zamba2.json"}],
            "workloads": [{"name": "tiny.cell", "config": "tiny-zamba2",
                           "traffic": "tiny-chat", "chips": 1}],
            "per_layer": []}
    out = run.run_cell(spec, args(2**31 + 21, seconds=1.5), chips=1)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
