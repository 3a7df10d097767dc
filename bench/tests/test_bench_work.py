"""FLOPs and bytes of useful work, against hand counts for the tiny dense
configuration (d 64, 2 layers, 4 heads of 16, 2 KV heads, d_ff 128, vocab
512)."""
import json

import pytest

from helpers import DATA
from bench import work


def cfg(name):
    return json.loads((DATA / f"tiny-{name}.json").read_text())


def test_dense_shape_by_hand():
    s = work.tier_shape(cfg("dense"))
    per_layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert s.body_params == 2 * per_layer
    assert s.head_params == 64 * 512
    assert s.weight_bytes == 2 * (2 * per_layer + 64 * 512)
    assert s.kv_bytes_per_token == 2 * 2 * 2 * 16 * 2
    assert s.q_width == 64 and s.attn_apps == 2


def test_prefill_by_hand():
    s = work.tier_shape(cfg("dense"))
    f, b = work.prefill_work(s, [10, 6], saved=0, restored=0)
    attn = 4 * 64 * 2 * (10 * 11 // 2 + 6 * 7 // 2)
    assert f == 2 * s.body_params * 16 + 2 * s.head_params * 2 + attn
    assert b == s.weight_bytes + 16 * s.kv_bytes_per_token
    # a whole-prompt hit computes nothing; a partial one only its suffix
    assert work.prefill_work(s, [10], saved=10, restored=1) == (0.0, 0.0)
    f, _ = work.prefill_work(s, [10, 6], saved=4, restored=0)
    assert f == 2 * s.body_params * 12 + 2 * s.head_params * 2


def test_decode_by_hand():
    s = work.tier_shape(cfg("dense"))
    f, b = work.decode_work(s, [(20, 2), (5, 1)])
    per = 2 * (s.body_params + s.head_params)
    ctx = [21, 22, 6]
    assert f == 3 * per + sum(4 * 64 * 2 * c for c in ctx)
    assert b == 2 * s.weight_bytes + sum(ctx) * s.kv_bytes_per_token
    assert work.decode_work(s, []) == (0.0, 0.0)


@pytest.mark.parametrize("flops,nbytes", [(197e12, 1.0), (1.0, 819e9)])
def test_least_time_is_the_binding_roof(flops, nbytes):
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.least_time(flops, nbytes, peak) == pytest.approx(1.0)
