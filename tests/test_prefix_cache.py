"""Prefix-sharing page pool, end to end: greedy serve_stream outputs are
token-identical with sharing ON vs OFF per decoder family (while actually
hitting the cache), repeated escalations skip L-tier prefill, pool-exhaustion
backpressure admits via retry without leaking pages, and the L-queue latency
drop policy (arXiv:2112.11413) keeps the S answer for expired escalations."""
import numpy as np
import pytest

from repro.configs.base import HIConfig
from repro.configs.registry import ARCHS
from repro.serving.batcher import Request
from repro.serving.engine import build_engine

STEPS = 3

# one reduced config per decoder family: dense (partial-hit + COW capable),
# moe (batch-coupled routing), ssm + hybrid (whole-prompt snapshot/restore),
# plus the benchmarked hybrid (zamba2-7b)
FAMS = ["qwen2-1.5b", "deepseek-moe-16b", "mamba2-370m", "zamba2-2.7b",
        "zamba2-7b"]


def _repeated_prefix_traffic(cfg, seed=3):
    """Shared 8-token system prefix + repeats: p1 lands in the 12-bucket
    (partial tail page -> copy-on-write on restore), p2/p3 in the 16-bucket
    (page-aligned).  Repeats exercise full restores; p2 vs p3 share only the
    prefix pages (partial hit, attention families)."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)

    def mk(n):
        return np.concatenate(
            [base, rng.integers(0, cfg.vocab_size, n).astype(np.int32)])

    p1, p2, p3 = mk(4), mk(8), mk(8)
    order = [p1, p2, p3, p1, p2, p3, p1, p2]
    return [Request(i, p, max_new_tokens=STEPS) for i, p in enumerate(order)]


def _assert_stream_equal(on, off):
    assert set(on) == set(off)
    for rid in off:
        np.testing.assert_array_equal(on[rid]["tokens"], off[rid]["tokens"])
        np.testing.assert_array_equal(on[rid]["s_tokens"],
                                      off[rid]["s_tokens"])
        assert on[rid]["offloaded"] == off[rid]["offloaded"]
        assert on[rid]["served_remote"] == off[rid]["served_remote"]
        np.testing.assert_allclose(on[rid]["confidence"],
                                   off[rid]["confidence"], atol=1e-6)


@pytest.mark.parametrize("arch", FAMS)
def test_sharing_equivalence_per_family(arch):
    """Greedy outputs must be bitwise token-identical with prefix sharing on
    vs off on mixed-bucket repeated-prefix traffic — while the cache actually
    hits (full restores for every family; partial hits + COW for the dense
    family via the non-page-aligned 12 bucket) and both engines keep ONE
    compiled stream executable."""
    cfg = ARCHS[arch].reduced()
    hi = HIConfig(theta=0.6, capacity_factor=1.0)
    reqs = _repeated_prefix_traffic(cfg)
    kw = dict(buckets=(12, 16), num_slots=2, page_size=8)

    eng_on = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32)
    on = eng_on.serve_stream(reqs, prefix_sharing=True, **kw)
    eng_off = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32)
    off = eng_off.serve_stream(reqs, prefix_sharing=False, **kw)

    _assert_stream_equal(on, off)
    sched = eng_on._stream[1]
    stats = sched.prefix_stats
    assert stats["full_hits"] > 0                  # repeats restored
    assert stats["tokens_saved"] > 0
    assert eng_on.stats["prefill_tokens_saved"] == stats["tokens_saved"]
    if arch == "qwen2-1.5b":
        assert stats["hits"] > stats["full_hits"]  # partial hits too
        assert stats["cow_copies"] > 0             # 12-bucket tail page
    sched.srt.pool.check_invariants()
    sched.lrt.pool.check_invariants()
    assert eng_on.stats["stream_compiles"] == 1
    assert eng_off.stats["stream_compiles"] == 1


def test_warm_cache_replay_stays_equivalent():
    """A second serve_stream call reuses the scheduler AND its prefix index:
    every repeated prompt full-restores (S and L tier), outputs stay
    identical to a sharing-off engine, and invariants hold after drain."""
    cfg = ARCHS["qwen2-1.5b"].reduced()
    hi = HIConfig(theta=1.1, capacity_factor=1.0)   # everything escalates
    reqs = _repeated_prefix_traffic(cfg)
    kw = dict(buckets=(12, 16), num_slots=2, page_size=8)
    eng_on = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32)
    eng_off = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32)
    eng_on.serve_stream(reqs, prefix_sharing=True, **kw)
    sched = eng_on._stream[1]
    hits0 = sched.prefix_stats["full_hits"]
    l_saved0 = sched.lrt.pool.stats["tokens_saved"]
    on = eng_on.serve_stream(reqs, prefix_sharing=True, **kw)
    off = eng_off.serve_stream(reqs, prefix_sharing=False, **kw)
    _assert_stream_equal(on, off)
    # warm replay: every admission on BOTH tiers is a full restore, so the
    # repeated escalations skipped L-tier prefill compute entirely
    assert sched.prefix_stats["full_hits"] >= hits0 + len(reqs)
    assert sched.lrt.pool.stats["tokens_saved"] > l_saved0
    assert eng_on.stats["stream_compiles"] == 1     # replay never recompiles
    sched.srt.pool.check_invariants()
    sched.lrt.pool.check_invariants()


def test_pool_exhaustion_backpressure_retries():
    """Traffic sized to exhaust the page pool mid-run: admission must retry
    (requeue at the head) instead of crashing, serve every request exactly
    once, and leak no pages (invariants after drain)."""
    from repro.serving.batcher import AdmissionQueue
    from repro.serving.scheduler import ContinuousScheduler

    cfg = ARCHS["qwen2-1.5b"].reduced()
    hi = HIConfig(theta=0.0, capacity_factor=1.0)
    eng = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32)
    # 3 slots but pages for ~1.5 full-context sequences: slots outnumber
    # pages, so admission hits pool exhaustion while slots are still free
    sched = ContinuousScheduler(
        eng.s, eng.l, hi, max_prompt_len=16, max_new_tokens=STEPS,
        num_slots=3, l_slots=2, page_size=8, decode_block=2,
        prefix_sharing=True, num_pages=6)
    queue = AdmissionQueue(buckets=(16,), page_size=8)
    rng = np.random.default_rng(5)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, 16).astype(np.int32),
                    max_new_tokens=STEPS) for i in range(6)]
    for r in reqs:
        queue.submit(r)
    results = sched.run(queue)
    assert set(results) == set(range(6))
    assert all(len(r["tokens"]) == STEPS for r in results.values())
    sched.srt.pool.check_invariants()
    sched.lrt.pool.check_invariants()
    # after drain every slot's pages are back (only index retention remains)
    assert sched.srt.busy == 0 and sched.lrt.busy == 0


def test_latency_budget_drop_policy():
    """arXiv:2112.11413: an escalation past its latency budget is dropped
    from the L queue — the S-tier answer stands, the record is flagged, and
    stats['dropped'] counts it; unbudgeted requests still escalate."""
    cfg = ARCHS["qwen2-1.5b"].reduced()
    hi = HIConfig(theta=1.1, capacity_factor=1.0)   # everything escalates
    rng = np.random.default_rng(9)
    expired = Request(0, rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                      max_new_tokens=STEPS, latency_budget=0.0)
    patient = Request(1, rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                      max_new_tokens=STEPS, latency_budget=None)
    eng = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32)
    out = eng.serve_stream([expired, patient], buckets=(8,), num_slots=2,
                           page_size=8)
    assert out[0]["dropped"] and not out[0]["served_remote"]
    assert out[0]["offloaded"]                      # it WANTED to escalate
    np.testing.assert_array_equal(out[0]["tokens"], out[0]["s_tokens"])
    assert not out[1]["dropped"] and out[1]["served_remote"]
    assert eng.stats["dropped"] == 1

    # the S answer must be exactly what an unbudgeted run produces on S
    eng2 = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32)
    ref = eng2.serve_stream([Request(0, expired.prompt,
                                     max_new_tokens=STEPS)],
                            buckets=(8,), num_slots=2, page_size=8)
    np.testing.assert_array_equal(out[0]["tokens"], ref[0]["s_tokens"])


def test_same_tick_row_recycling_keeps_restore_intact():
    """Regression: with a single prefix-cache row, a tick that BOTH restores
    from the row and (via same-tick LRU eviction) recycles it for a new
    admission's save must restore the PRE-SAVE state — the recurrent
    families read the snapshot before this tick's save scatter lands."""
    cfg = ARCHS["mamba2-370m"].reduced()
    hi = HIConfig(theta=0.0, capacity_factor=1.0)
    rng = np.random.default_rng(11)
    p1 = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    p2 = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    kw = dict(buckets=(16,), num_slots=2, page_size=8, prefix_entries=1)
    outs = {}
    for sharing in (True, False):
        eng = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32)
        eng.serve_stream([Request(0, p1, max_new_tokens=STEPS)],
                         prefix_sharing=sharing, **kw)
        # one tick admits BOTH: rid 1 restores row 0, rid 2 evicts + reuses it
        outs[sharing] = eng.serve_stream(
            [Request(1, p1, max_new_tokens=STEPS),
             Request(2, p2, max_new_tokens=STEPS)],
            prefix_sharing=sharing, **kw)
    _assert_stream_equal(outs[True], outs[False])


def test_cow_kernel_matches_jnp_path():
    """The Pallas page-copy kernel (scalar-prefetched source map) must match
    the jnp scatter for dense and hybrid pool layouts, including padded
    (0, 0) no-op pairs."""
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    from repro.models import layers as L

    rng = np.random.default_rng(0)
    for shape in [(2, 6, 4, 2 * 3), (3, 5, 8, 1 * 4)]:
        pool = jnp.asarray(rng.normal(size=shape), jnp.float32)
        src = jnp.asarray([4, 2, 0], jnp.int32)
        dst = jnp.asarray([1, 3, 0], jnp.int32)
        out_k = np.asarray(kops.copy_pages(pool, src, dst))
        out_j = np.asarray(L.cow_copy_pages(pool, src, dst))
        np.testing.assert_array_equal(out_k, out_j)
        np.testing.assert_array_equal(out_k[:, 1], np.asarray(pool[:, 4]))
        np.testing.assert_array_equal(out_k[:, 3], np.asarray(pool[:, 2]))
        np.testing.assert_array_equal(out_k[:, 0], np.asarray(pool[:, 0]))
