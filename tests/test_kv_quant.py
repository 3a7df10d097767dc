"""Quantized paged KV pool (int8 per-page-per-head scales): per-family
int8-vs-bf16 decode fidelity bounds, fused-dequant kernel vs jnp-path
agreement, scale rows moving with pages through copy_pages / COW, pool
gauges + scalar-prefetch bound hardening, and the serving-layer contract
(one compiled stream executable, one host sync per tick) in BOTH dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import HIConfig
from repro.configs.registry import ARCHS
from repro.models import model_zoo as zoo
from repro.serving import engine as engine_mod
from repro.serving.batcher import Request
from repro.serving.engine import build_engine
from repro.serving.kv_pool import KVPool

FAMS = ["qwen2-1.5b", "gemma3-1b", "deepseek-moe-16b", "mamba2-370m",
        "zamba2-2.7b"]
PAGE = 8


def _quant_pair(cfg, slots=2, npg=6, prompt_len=16, seed=0):
    """Prefill the same prompts into a bf16 and an int8 paged cache."""
    params = zoo.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (slots, prompt_len)),
                       jnp.int32)
    lens = jnp.full((slots,), prompt_len, jnp.int32)
    block = jnp.asarray(
        np.arange(1, slots * npg + 1, dtype=np.int32).reshape(slots, npg))
    out = {}
    for dt in (jnp.bfloat16, jnp.int8):
        cache = zoo.init_paged_cache(cfg, slots, slots * npg + 1, PAGE, dt)
        lg, cache = zoo.prefill_paged(params, cfg, toks, lens,
                                      jnp.arange(slots, dtype=jnp.int32),
                                      block, cache)
        out[str(jnp.dtype(dt))] = (lg, cache)
    pos = jnp.full((slots,), prompt_len, jnp.int32)
    return params, out, block, pos


@pytest.mark.parametrize("arch", FAMS)
def test_int8_decode_fidelity_per_family(arch):
    """int8 pools track the bf16 pools within tolerance: bounded logit
    error and high TEACHER-FORCED greedy top-1 agreement (both paths fed
    the bf16 argmax each step, isolating per-decision fidelity from
    compounding divergence).  The pure-SSM family has no pages to quantize,
    so it must stay EXACT."""
    cfg = ARCHS[arch].reduced()
    params, out, block, pos = _quant_pair(cfg)
    (lg_b, cache_b), (lg_q, cache_q) = out["bfloat16"], out["int8"]
    slots, steps = lg_b.shape[0], 8
    max_err = float(jnp.max(jnp.abs(lg_b - lg_q)))
    match = int(jnp.sum(jnp.argmax(lg_b, -1) == jnp.argmax(lg_q, -1)))
    total = slots
    tok = jnp.argmax(lg_b, -1)[:, None].astype(jnp.int32)
    for i in range(steps):
        lg_b, cache_b = zoo.decode_step_paged(params, cfg, tok, pos + i,
                                              block, cache_b)
        lg_q, cache_q = zoo.decode_step_paged(params, cfg, tok, pos + i,
                                              block, cache_q)
        max_err = max(max_err, float(jnp.max(jnp.abs(lg_b - lg_q))))
        match += int(jnp.sum(jnp.argmax(lg_b, -1) == jnp.argmax(lg_q, -1)))
        total += slots
        tok = jnp.argmax(lg_b, -1)[:, None].astype(jnp.int32)
    if arch == "mamba2-370m":        # no KV pages -> int8 mode is a no-op
        assert match == total and max_err == 0.0
    else:
        assert match / total >= 0.8, f"{arch}: agreement {match}/{total}"
        assert max_err <= 0.3, f"{arch}: logit error {max_err}"


def test_int8_fused_dequant_kernel_matches_jnp():
    """The Pallas kernels with the fused dequant (scale operand riding the
    block-table index_map) agree with the jnp dequant-gather path, single
    token and chunked."""
    cfg = ARCHS["qwen2-1.5b"].reduced()
    params, out, block, pos = _quant_pair(cfg)
    _, cache = out["int8"]
    tok = jnp.asarray([[7], [11]], jnp.int32)
    lg_j, _ = zoo.decode_step_paged(params, cfg, tok, pos, block, cache)
    lg_k, _ = zoo.decode_step_paged(params, cfg, tok, pos, block, cache,
                                    use_kernel=True)
    np.testing.assert_allclose(np.asarray(lg_k), np.asarray(lg_j),
                               rtol=2e-2, atol=2e-2)
    chunk = jnp.asarray([[7, 3, 5], [11, 2, 9]], jnp.int32)
    cj, _, _ = zoo.forward_chunk_paged(params, cfg, chunk, pos, block, cache)
    ck, _, _ = zoo.forward_chunk_paged(params, cfg, chunk, pos, block, cache,
                                       use_kernel=True)
    np.testing.assert_allclose(np.asarray(ck), np.asarray(cj),
                               rtol=2e-2, atol=2e-2)


def test_int8_sharing_and_chunking_equivalence_tolerance():
    """Under int8 the bitwise sharing/chunking invariants become
    tolerance-based: one chunk pass tracks sequential decode steps on the
    same quantized pool (identical writes -> identical pool bytes; logits
    match within interpret-mode tolerance)."""
    cfg = ARCHS["qwen2-1.5b"].reduced()
    params, out, block, pos = _quant_pair(cfg)
    _, cache = out["int8"]
    c = 3
    rng = np.random.default_rng(1)
    chunk = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, c)), jnp.int32)
    cache_ref, ref = cache, []
    for i in range(c):
        lg, cache_ref = zoo.decode_step_paged(params, cfg, chunk[:, i:i + 1],
                                              pos + i, block, cache_ref)
        ref.append(lg)
    ref = jnp.stack(ref, axis=1)
    out_c, cache_c, _ = zoo.forward_chunk_paged(params, cfg, chunk, pos,
                                                block, cache)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
    # pool proximity, not byte equality: deeper-layer K/V of later chunk
    # tokens depend on whether earlier chunk tokens were read back
    # quantized (step loop) or in-pass (chunk), and the step loop
    # additionally requantizes already-rounded levels when a later token
    # raises the page scale — so scales agree to tolerance and the
    # quantized levels within a couple of grid steps
    for k in ("ks", "vs"):
        np.testing.assert_allclose(np.asarray(cache_c[k]),
                                   np.asarray(cache_ref[k]),
                                   rtol=5e-3, atol=5e-5)
    for k in ("kp", "vp"):
        d = np.abs(np.asarray(cache_c[k], np.int32) -
                   np.asarray(cache_ref[k], np.int32))
        assert d.max() <= 2, f"{k}: quantized bytes diverge by {d.max()}"


def test_copy_pages_int8_pool_with_scales():
    """copy_pages derives out_shape/dtype from its pool argument: an int8
    pool copies as int8, and the (L, P, K) scale tensor goes through the
    SAME kernel so a COW'd page carries its scale row."""
    from repro.kernels import ops as kops
    from repro.models import layers as L

    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.integers(-127, 128, (2, 6, 4, 2 * 3)), jnp.int8)
    scale = jnp.asarray(rng.random((2, 6, 2)), jnp.float32)
    src = jnp.asarray([4, 2, 0], jnp.int32)
    dst = jnp.asarray([1, 3, 0], jnp.int32)
    out = np.asarray(kops.copy_pages(pool, src, dst))
    assert out.dtype == np.int8
    np.testing.assert_array_equal(out, np.asarray(L.cow_copy_pages(pool, src,
                                                                   dst)))
    np.testing.assert_array_equal(out[:, 1], np.asarray(pool[:, 4]))
    out_s = np.asarray(kops.copy_pages(scale, src, dst))
    assert out_s.dtype == np.float32
    np.testing.assert_array_equal(out_s,
                                  np.asarray(L.cow_copy_scales(scale, src,
                                                               dst)))
    np.testing.assert_array_equal(out_s[:, 1], np.asarray(scale[:, 4]))
    np.testing.assert_array_equal(out_s[:, 0], np.asarray(scale[:, 0]))


def test_pool_gauges_report_quant_footprint():
    """kv_bytes_total / bytes_per_slot / kv_bits gauges: the int8 pool
    (pages + scale rows) fits in <= 0.55x the bf16 bytes at the same
    slot/page config."""
    cfg = ARCHS["qwen2-1.5b"].reduced()
    kw = dict(num_slots=4, max_context=32, page_size=8)
    g16 = KVPool(cfg, dtype=jnp.bfloat16, **kw).gauges()
    g8 = KVPool(cfg, dtype=jnp.int8, **kw).gauges()
    assert g16["kv_bits"] == 16 and g8["kv_bits"] == 8
    assert g16["kv_bytes_total"] == g16["bytes_per_slot"] * 4
    assert g8["kv_bytes_total"] <= 0.55 * g16["kv_bytes_total"]
    for g in (g16, g8):        # numeric-only contract (telemetry counters)
        assert all(isinstance(v, int) for v in g.values())


def test_block_table_wider_than_prefetch_bound_raises():
    """A page_size/max_context pair implying a block-table row wider than
    the kernels' scalar-prefetch block must fail loudly at pool
    construction, not read garbage in the kernel."""
    from repro.kernels.decode_attention import MAX_PREFETCH_PAGES

    cfg = ARCHS["qwen2-1.5b"].reduced()
    too_wide = 8 * (MAX_PREFETCH_PAGES + 1)
    with pytest.raises(ValueError, match="MAX_PREFETCH_PAGES"):
        KVPool(cfg, num_slots=1, max_context=too_wide, page_size=8,
               num_pages=4)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_stream_one_compile_one_sync_both_dtypes(monkeypatch, kv_dtype):
    """The quantized pool changes bytes, not structure: serve_stream keeps
    ONE compiled executable across buckets and exactly one device->host
    sync per tick in either kv_dtype, with pool invariants (including
    scale-row accounting) checked after every tick."""
    calls = []
    real = engine_mod._host_fetch
    monkeypatch.setattr(engine_mod, "_host_fetch",
                        lambda tree: (calls.append(1), real(tree))[1])
    cfg = ARCHS["qwen2-1.5b"].reduced()
    eng = build_engine(cfg, HIConfig(theta=0.6, capacity_factor=1.0),
                       max_new_tokens=3, cache_len=32)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, L).astype(np.int32),
                    max_new_tokens=3) for i, L in enumerate([8, 16, 8])]
    eng.serve_stream(reqs, buckets=(8, 16), num_slots=2, page_size=8,
                     kv_dtype=kv_dtype, validate=True)
    assert eng.stats["stream_compiles"] == 1
    assert len(calls) == eng.stats["stream_ticks"] > 0
    pool = eng._stream[1].srt.pool
    assert pool.kv_dtype == ("int8" if kv_dtype == "int8" else "bfloat16")
    assert ("ks" in pool.buffers) == (kv_dtype == "int8")


def test_int8_prefix_sharing_cow_moves_scale_rows():
    """End to end through the serving stack: repeated-prefix traffic with a
    non-page-aligned bucket forces full restores + COW tail-page copies on
    an int8 pool; invariants (scale accounting included) hold every tick
    and restored continuations match the unshared engine's tokens."""
    cfg = ARCHS["qwen2-1.5b"].reduced()
    hi = HIConfig(theta=0.6, capacity_factor=1.0)
    rng = np.random.default_rng(3)
    base = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)

    def mk(n):
        return np.concatenate(
            [base, rng.integers(0, cfg.vocab_size, n).astype(np.int32)])

    p1, p2 = mk(4), mk(8)
    reqs = [Request(i, p, max_new_tokens=3)
            for i, p in enumerate([p1, p2, p1, p2, p1])]
    kw = dict(buckets=(12, 16), num_slots=2, page_size=8, kv_dtype="int8",
              validate=True)
    eng_on = build_engine(cfg, hi, max_new_tokens=3, cache_len=32)
    on = eng_on.serve_stream(reqs, prefix_sharing=True, **kw)
    eng_off = build_engine(cfg, hi, max_new_tokens=3, cache_len=32)
    off = eng_off.serve_stream(reqs, prefix_sharing=False, **kw)
    stats = eng_on._stream[1].prefix_stats
    assert stats["full_hits"] > 0 and stats["cow_copies"] > 0
    # same pool dtype both sides -> identical quantized pages for identical
    # traffic: restored/aliased continuations stay token-identical
    for rid in off:
        np.testing.assert_array_equal(on[rid]["tokens"], off[rid]["tokens"])
    eng_on._stream[1].srt.pool.check_invariants()
    eng_on._stream[1].lrt.pool.check_invariants()
