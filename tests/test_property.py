"""Property-based tests (hypothesis) on the system's invariants."""
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed (see requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import calibrate
from repro.core.cost import per_sample_cost, total_cost
from repro.core.router import route, scatter_merge
from repro.models import moe as moe_mod

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------
@given(st.integers(2, 200), st.floats(0.0, 0.999), st.integers(0, 2 ** 31 - 1))
def test_cost_bounds_and_monotonicity(n, beta, seed):
    rng = np.random.default_rng(seed)
    off = rng.random(n) < 0.5
    s_ok = rng.random(n) < 0.7
    l_ok = rng.random(n) < 0.95
    c = np.asarray(per_sample_cost(jnp.asarray(off), jnp.asarray(s_ok),
                                   jnp.asarray(l_ok), beta))
    # per-sample cost in [0, 1 + beta]
    assert (c >= -1e-6).all() and (c <= 1.0 + beta + 1e-6).all()
    # total cost is monotone nondecreasing in beta (same decisions)
    t1 = float(total_cost(jnp.asarray(off), jnp.asarray(s_ok),
                          jnp.asarray(l_ok), beta))
    t2 = float(total_cost(jnp.asarray(off), jnp.asarray(s_ok),
                          jnp.asarray(l_ok), min(beta + 0.1, 0.999)))
    assert t2 >= t1 - 1e-6


@given(st.integers(5, 300), st.floats(0.01, 0.99), st.integers(0, 2 ** 31 - 1))
def test_brute_force_theta_never_beaten(n, beta, seed):
    """theta* from the sweep must beat every random threshold."""
    rng = np.random.default_rng(seed)
    conf = rng.random(n)
    s_ok = rng.random(n) < conf        # calibrated-ish
    th, c = calibrate.brute_force_theta(conf, s_ok, beta)
    for t in rng.random(16):
        naive = np.sum(np.where(conf < t, beta, 1.0 - s_ok))
        assert c <= naive + 1e-9


# ---------------------------------------------------------------------------
# router invariants
# ---------------------------------------------------------------------------
@given(st.integers(2, 128), st.integers(1, 128), st.integers(0, 2 ** 31 - 1))
def test_route_invariants(n, cap_raw, seed):
    cap = min(cap_raw, n)
    rng = np.random.default_rng(seed)
    conf = jnp.asarray(rng.random(n).astype(np.float32))
    mask = conf < 0.5
    d = route(mask, conf, cap)
    served = np.asarray(d.served_remote)
    maskn = np.asarray(mask)
    # served is a subset of the policy mask
    assert not (served & ~maskn).any()
    # capacity respected
    assert served.sum() <= cap
    # conservation: served + dropped = wanted
    assert served.sum() + int(d.dropped) == int(maskn.sum())
    # indices are unique
    idx = np.asarray(d.indices)
    assert len(set(idx.tolist())) == len(idx)


@given(st.integers(2, 64), st.integers(0, 2 ** 31 - 1))
def test_scatter_merge_identity_off_served(n, seed):
    rng = np.random.default_rng(seed)
    conf = jnp.asarray(rng.random(n).astype(np.float32))
    mask = conf < 0.4
    cap = max(1, n // 2)
    d = route(mask, conf, cap)
    s_out = jnp.asarray(rng.integers(0, 100, n))
    l_out = jnp.asarray(rng.integers(100, 200, cap))
    merged = np.asarray(scatter_merge(s_out, l_out, d))
    served = np.asarray(d.served_remote)
    # non-served positions keep the S output
    np.testing.assert_array_equal(merged[~served], np.asarray(s_out)[~served])
    # served positions hold an L output
    assert (merged[served] >= 100).all()


# ---------------------------------------------------------------------------
# MoE dispatch conservation
# ---------------------------------------------------------------------------
@given(st.integers(2, 6), st.integers(1, 2), st.integers(0, 2 ** 31 - 1))
def test_moe_capacity_math(e, k, seed):
    k = min(k, e)
    t = 32
    cap = moe_mod.moe_capacity(t, e, k, 1.0)
    assert cap * e >= t * k          # full capacity covers all assignments


# ---------------------------------------------------------------------------
# SSD semantics under random shapes
# ---------------------------------------------------------------------------
@given(st.integers(1, 2), st.sampled_from([16, 24, 40]), st.integers(1, 3),
       st.sampled_from([4, 8]), st.sampled_from([4, 8]),
       st.integers(0, 2 ** 31 - 1))
def test_ssd_chunked_equals_recurrence(b, l, h, p, n, seed):
    from repro.kernels import ref
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, l, h, p)), jnp.float32)
    dt = jnp.asarray(rng.random((b, l, h)), jnp.float32) * 0.5
    A = -jnp.asarray(rng.random(h), jnp.float32) - 0.3
    B = jnp.asarray(rng.normal(size=(b, l, 1, n)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, l, 1, n)), jnp.float32)
    y_c, _ = ref.ssd_ref(x, dt, A, B, C, chunk=16)
    y_n = ref.ssd_naive_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_n),
                               rtol=5e-3, atol=5e-3)


# ---------------------------------------------------------------------------
# confidence metric ranges
# ---------------------------------------------------------------------------
@given(st.integers(1, 32), st.integers(2, 50), st.integers(0, 2 ** 31 - 1))
def test_confidence_ranges(n, c, seed):
    from repro.core.confidence import confidence
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=(n, c)) * 5, jnp.float32)
    for metric in ("max_prob", "margin", "entropy"):
        v = np.asarray(confidence(logits, metric))
        assert (v >= -1e-5).all() and (v <= 1.0 + 1e-5).all()
    # max_prob lower bound: 1/C
    mp = np.asarray(confidence(logits, "max_prob"))
    assert (mp >= 1.0 / c - 1e-6).all()


# ---------------------------------------------------------------------------
# quantized pool: allocator invariants with scale rows attached
# ---------------------------------------------------------------------------
@given(st.lists(st.tuples(st.sampled_from(["alloc", "free", "admit"]),
                          st.integers(0, 3),
                          st.sampled_from([8, 12, 16, 20])),
                min_size=1, max_size=25),
       st.integers(0, 5))
def test_int8_pool_invariants_under_random_ops(ops, nprompts):
    """Random alloc / free / prefix-admission (aliasing, COW tail pages,
    eviction) sequences on an int8 pool keep every refcount
    invariant PLUS the scale-row accounting (`check_invariants` asserts the
    fp32 scale tensors stay one-row-per-physical-page beside the int8
    pools).  Hashed prompts repeat, so admissions alias retained pages and
    non-page-aligned buckets schedule COW copies."""
    from repro.configs.registry import ARCHS
    from repro.serving.kv_pool import KVPool

    cfg = ARCHS["qwen2-1.5b"].reduced()
    pool = KVPool(cfg, num_slots=4, max_context=32, page_size=8,
                  dtype=jnp.int8, prefix_entries=2)
    pool.check_invariants()
    for tick, (op, slot, bucket) in enumerate(ops):
        try:
            if op == "alloc":
                pool.alloc(slot, bucket + 4, tick=tick)
            elif op == "free":
                pool.free(slot)
            elif op == "admit":
                # small prompt-identity space -> repeats hit the index;
                # 12/20 buckets have partial tail pages -> COW on restore
                pid = (slot + bucket) % max(nprompts + 1, 1)
                hashes = [bytes([pid, i]) for i in range(4)]
                full = bytes([pid, 0xFF, bucket])
                pool.admit_prefix(slot, bucket + 4, bucket, hashes, full,
                                  tick)
        except ValueError:
            pass          # exhaustion / double-free
        pool.check_invariants()
    for slot in list(pool.held_slots):
        pool.free(slot)
    pool.check_invariants()
