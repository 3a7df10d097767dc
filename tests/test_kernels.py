"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret=True)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# hi_gate
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("metric", ["max_prob", "margin", "entropy"])
@pytest.mark.parametrize("n,c", [(8, 10), (33, 7), (64, 101), (16, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_hi_gate_sweep(metric, n, c, dtype):
    logits = jnp.asarray(RNG.normal(size=(n, c)) * 3).astype(dtype)
    conf_k, pred_k, off_k = ops.hi_gate(logits, 0.55, metric)
    conf_r, pred_r, off_r = ref.hi_gate_ref(logits, 0.55, metric)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(conf_k, conf_r, rtol=tol, atol=tol)
    # argmax/threshold can differ only at exact ties — none with random data
    assert (pred_k == pred_r).all()
    assert (off_k == off_r).all()


def test_hi_gate_threshold_semantics():
    logits = jnp.asarray([[10.0, -10.0], [0.1, 0.0]])
    conf, pred, off = ops.hi_gate(logits, 0.9, "max_prob")
    assert off[0] == 0 and off[1] == 1       # confident kept, uncertain offloads
    assert pred[0] == 0


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,s,h,k,d", [
    (1, 128, 4, 1, 16), (2, 256, 8, 2, 32), (2, 192, 6, 6, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, s, h, k, d, dtype):
    q = jnp.asarray(RNG.normal(size=(b, 1, h, d))).astype(dtype)
    ck = jnp.asarray(RNG.normal(size=(b, s, k, d))).astype(dtype)
    cv = jnp.asarray(RNG.normal(size=(b, s, k, d))).astype(dtype)
    pos = s // 3
    valid = jnp.arange(s) <= pos
    out = ops.decode_attention(q, ck, cv, valid, block_s=64)
    outr = ref.decode_attention_ref(q, ck, cv, valid)
    tol = 3e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(outr, np.float32),
                               rtol=tol, atol=tol)


def test_decode_attention_sliding_window():
    """A window mask must exactly drop old positions."""
    b, s, h, k, d = 1, 128, 2, 1, 16
    q = jnp.asarray(RNG.normal(size=(b, 1, h, d)), jnp.float32)
    ck = jnp.asarray(RNG.normal(size=(b, s, k, d)), jnp.float32)
    cv = jnp.asarray(RNG.normal(size=(b, s, k, d)), jnp.float32)
    pos, win = 100, 16
    kpos = jnp.arange(s)
    valid = (kpos <= pos) & (pos - kpos < win)
    out = ops.decode_attention(q, ck, cv, valid, block_s=32)
    outr = ref.decode_attention_ref(q, ck, cv, valid)
    np.testing.assert_allclose(out, outr, rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,l,h,p,n,chunk", [
    (1, 64, 2, 8, 4, 16), (2, 96, 4, 16, 8, 32), (1, 80, 3, 8, 16, 16),
])
def test_ssd_kernel_vs_chunked_ref(b, l, h, p, n, chunk):
    x = jnp.asarray(RNG.normal(size=(b, l, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.random((b, l, h)), jnp.float32) * 0.5
    A = -jnp.asarray(RNG.random(h), jnp.float32) - 0.2
    B = jnp.asarray(RNG.normal(size=(b, l, 1, n)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(b, l, 1, n)), jnp.float32)
    y_k, hT_k = ops.ssd(x, dt, A, B, C, chunk=chunk)
    y_r, hT_r = ref.ssd_ref(x, dt, A, B, C, chunk=chunk)
    np.testing.assert_allclose(y_k, y_r, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(hT_k, hT_r, rtol=3e-4, atol=3e-4)


def test_ssd_chunked_matches_naive_recurrence():
    """The chunked algorithm IS the recurrence (state-space duality)."""
    b, l, h, p, n = 2, 48, 2, 8, 4
    x = jnp.asarray(RNG.normal(size=(b, l, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.random((b, l, h)), jnp.float32) * 0.5
    A = -jnp.asarray(RNG.random(h), jnp.float32) - 0.2
    B = jnp.asarray(RNG.normal(size=(b, l, 1, n)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(b, l, 1, n)), jnp.float32)
    y_r, _ = ref.ssd_ref(x, dt, A, B, C, chunk=16)
    y_n = ref.ssd_naive_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y_r), np.asarray(y_n),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("path", ["kernel", "chunked"])
def test_ssd_groups_match_naive_recurrence(path):
    """Two B/C groups, head j reading group j // (h / 2): the kernel and the
    chunked jnp path both equal the recurrence."""
    b, l, h, p, n, g = 1, 80, 4, 8, 8, 2
    x = jnp.asarray(RNG.normal(size=(b, l, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.random((b, l, h)), jnp.float32) * 0.5
    A = -jnp.asarray(RNG.random(h), jnp.float32) - 0.2
    B = jnp.asarray(RNG.normal(size=(b, l, g, n)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(b, l, g, n)), jnp.float32)
    ssd = ops.ssd if path == "kernel" else ref.ssd_ref
    y, _ = ssd(x, dt, A, B, C, chunk=16)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.ssd_naive_ref(x, dt, A, B, C)),
                               rtol=2e-3, atol=2e-3)


def test_ssd_chunk_independence():
    """Chunk size must not change the result."""
    b, l, h, p, n = 1, 64, 2, 8, 4
    x = jnp.asarray(RNG.normal(size=(b, l, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.random((b, l, h)), jnp.float32) * 0.5
    A = -jnp.asarray(RNG.random(h), jnp.float32) - 0.5
    B = jnp.asarray(RNG.normal(size=(b, l, 1, n)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(b, l, 1, n)), jnp.float32)
    y16, _ = ops.ssd(x, dt, A, B, C, chunk=16)
    y64, _ = ops.ssd(x, dt, A, B, C, chunk=64)
    np.testing.assert_allclose(y16, y64, rtol=3e-4, atol=3e-4)


def test_streamed_decode_matches_sdpa():
    """The jnp streaming decode path (local serving) == full-row attention."""
    from repro.models import layers as L
    b, s, h, k, d = 2, 8192, 8, 2, 32
    q = jnp.asarray(RNG.normal(size=(b, 1, h, d)), jnp.float32)
    ck = jnp.asarray(RNG.normal(size=(b, s, k, d)), jnp.float32)
    cv = jnp.asarray(RNG.normal(size=(b, s, k, d)), jnp.float32)
    valid = jnp.arange(s) <= 5000
    out_s = L._decode_attn_streamed(q, ck, cv, valid, 2048)
    out_f = L._sdpa(q, ck, cv, valid[None, None, :])
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_f),
                               rtol=3e-5, atol=3e-5)


def test_decode_attention_paged_matches_gather_oracle():
    """The paged kernel (block table as scalar-prefetch, physical pages DMA'd
    by the index_map) == gathering the pages and running dense attention."""
    from repro.models import layers as L
    rng = np.random.default_rng(3)
    b, h, k, d, page, npg, P = 3, 4, 2, 16, 8, 4, 13
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    pk = jnp.asarray(rng.standard_normal((P, page, k * d)), jnp.float32)
    pv = jnp.asarray(rng.standard_normal((P, page, k * d)), jnp.float32)
    block = jnp.asarray(rng.integers(0, P, (b, npg)), jnp.int32)
    pos = jnp.asarray([5, 17, 31], jnp.int32)       # per-slot depths
    valid = jnp.arange(npg * page)[None, :] <= pos[:, None]
    out = ops.decode_attention_paged(q, pk, pv, block, valid)
    kk = pk[block].reshape(b, npg * page, k, d)
    vv = pv[block].reshape(b, npg * page, k, d)
    ref_out = L._sdpa(q, kk, vv, valid[:, None, :])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=3e-5, atol=3e-5)
