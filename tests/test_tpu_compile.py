"""Every Pallas kernel the serving tick can call, compiled for a TPU v5e at
real widths (qwen2-1.5b's and zamba2-7b's L tiers and their s_variant(4) S
tiers; zamba2-7b's grouped SSD heads) with ``interpret=False``.

Nothing runs: the TPU compiler compiles for a described v5e:2x2 topology,
which refuses what the chip would refuse (block shapes off the (8, 128)
tiling, ops Mosaic cannot lower, VMEM overruns).  The topology is described
inside a module fixture, never at import, so every xdist worker collects the
same tests and only the one running this file loads the TPU library.
"""
import dataclasses
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import ARCHS
from repro.kernels import decode_attention as da
from repro.kernels import hi_gate as hg
from repro.kernels import ssd_scan as ssd
from repro.models import model_zoo
from repro.serving import scheduler as sch

QWEN = ARCHS["qwen2-1.5b"]
ZAMBA = ARCHS["zamba2-7b"]
TIERS = {"L": QWEN, "S": QWEN.s_variant(4),
         # head_dim 224 is off the 128-lane tiling; 32 K/V heads
         "zamba2-L": ZAMBA, "zamba2-S": ZAMBA.s_variant(4)}
SLOTS, PAGE = 8, 16
PAGES_PER_SLOT = -(-(512 + 32) // PAGE)      # longest bucket + max_new
POOL_PAGES = 1 + SLOTS * PAGES_PER_SLOT      # + the null page 0


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip; the persistent compile cache is off meanwhile
    (entries written for a described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; returns the HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_shapes(cfg, c: int, pool_dtype):
    """q, pools, block table, mask (+ int8 scales); the decode entry point
    (c = 1) takes its mask without the chunk axis."""
    h, kh, d = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    pool = ((POOL_PAGES, PAGE, kh * d), pool_dtype)
    valid = (SLOTS,) + (() if c == 1 else (c,)) + (PAGES_PER_SLOT * PAGE,)
    shapes = [((SLOTS, c, h, d), jnp.bfloat16), pool, pool,
              ((SLOTS, PAGES_PER_SLOT), jnp.int32), (valid, jnp.bool_)]
    if pool_dtype == jnp.int8:
        shapes += [((POOL_PAGES, kh), jnp.float32)] * 2
    return shapes


def _with_scales(kernel):
    def f(q, pk, pv, blk, valid, *scales):
        kw = dict(scale_k=scales[0], scale_v=scales[1]) if scales else {}
        return kernel(q, pk, pv, blk, valid, interpret=False, **kw)
    return f


@pytest.mark.parametrize("tier", sorted(TIERS))
@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_decode_compiles(one_chip, tier, pool_dtype):
    hlo = _compile(_with_scales(da.decode_attention_paged_pallas), one_chip,
                   *_paged_shapes(TIERS[tier], 1, pool_dtype))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_chunk_paged_compiles(one_chip, pool_dtype):
    # chunk 8 is the chunk lane's default width
    hlo = _compile(_with_scales(da.decode_attention_chunk_paged_pallas),
                   one_chip, *_paged_shapes(QWEN, 8, pool_dtype))
    assert "tpu_custom_call" in hlo


def test_contiguous_decode_compiles(one_chip):
    kh, d = QWEN.num_kv_heads, QWEN.resolved_head_dim
    cache = ((SLOTS, 1024, kh, d), jnp.bfloat16)
    hlo = _compile(
        lambda q, k, v, valid: da.decode_attention_pallas(
            q, k, v, valid, interpret=False),
        one_chip, ((SLOTS, 1, QWEN.num_heads, d), jnp.bfloat16), cache, cache,
        ((1024,), jnp.bool_))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("what", ["pages", "scales"])
def test_copy_pages_compiles(one_chip, what):
    kh, d = QWEN.num_kv_heads, QWEN.resolved_head_dim
    pool = ((QWEN.num_layers, POOL_PAGES, PAGE, kh * d), jnp.bfloat16)
    if what == "scales":
        pool = ((QWEN.num_layers, POOL_PAGES, kh), jnp.float32)
    hlo = _compile(lambda p, src: da.copy_pages_pallas(p, src,
                                                       interpret=False),
                   one_chip, pool, ((POOL_PAGES,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("metric", ["max_prob", "margin", "entropy"])
@pytest.mark.parametrize("rows", [SLOTS, 4 * SLOTS])
def test_hi_gate_compiles(one_chip, metric, rows):
    hlo = _compile(lambda logits, theta: hg.hi_gate_pallas(
        logits, theta, metric, interpret=False),
        one_chip, ((rows, QWEN.vocab_size), jnp.float32), ((), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_ssd_chunk_compiles_zamba2(one_chip):
    """zamba2-7b's 112 heads of 64, state 64, chunk 256, two B/C groups."""
    z = ZAMBA
    h, p, n, q, g = (z.ssm_nheads, z.ssm_head_dim, z.ssm_state, z.ssm_chunk,
                     z.ssm_ngroups)
    nc = 512 // q                               # a 512-token prompt
    hlo = _compile(lambda x, dt, a, b, c: ssd.ssd_chunk_pallas(
        x, dt, a, b, c, interpret=False),
        one_chip, ((1, nc, q, h, p), jnp.float32), ((1, nc, q, h), jnp.float32),
        ((h,), jnp.float32), ((1, nc, g, q, n), jnp.float32),
        ((1, nc, g, q, n), jnp.float32))
    assert "tpu_custom_call" in hlo


# ---------------------------------------------------------------------------
# the scheduler's tick: no copy of a whole K/V pool
# ---------------------------------------------------------------------------

_COPY = re.compile(r"^\s*(?:ROOT )?%?(\S+) = \w+\[([\d,]*)\]\S* "
                   r"(copy|transpose)\(", re.M)


def _two_layers(cfg):
    """``cfg`` cut to two layers at its own widths; a hybrid applies its
    shared blocks at both."""
    ids = (0, 1) if cfg.hybrid_layer_ids else ()
    return dataclasses.replace(cfg, num_layers=2, hybrid_layer_ids=ids)


def _tick_pool_copies(sharding, s_cfg, l_cfg, *, sharing: bool):
    """Compile both tiers' ticks (``scheduler._tier_tick_fn``) as the
    scheduler composes them, pools donated, for the described chip; returns
    the ``copy``/``transpose`` instructions whose result has a K/V pool's
    shape, and the pool shapes."""
    slots, page, prompt, new, block = 16, 16, 64, 16, 4
    ctx = -(-(prompt + new + block - 1) // page) * page
    on_chip = partial(jax.tree.map, lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding))
    ticks, args, pools = [], [], set()
    for name, cfg, n in (("S", s_cfg, slots), ("L", l_cfg, slots // 2)):
        rt = sch._TierRuntime(cfg, n, ctx, page, 4, jnp.bfloat16,
                              prefix_entries=2 * n if sharing else 0,
                              max_prompt_len=prompt, name=name, alloc=False)
        ticks.append(sch._tier_tick_fn(name, cfg, "max_prob", False, block,
                                       rt.sharing))
        args.append(on_chip((model_zoo.init_params_spec(cfg),
                             rt.tick_inputs(prompt), rt.pool_operand())))
        pools |= {tuple(rt.pool.buffers[k].shape) for k in ("kp", "vp")}
    (s_tick, l_tick), ((sp, si, s_pool), (lp, li, l_pool)) = ticks, args

    def tick(s_params, l_params, theta, s_in, l_in, s_pool, l_pool):
        s_out, s_pool = s_tick(s_params, theta, s_in, s_pool)
        l_out, l_pool = l_tick(l_params, theta, l_in, l_pool)
        return {"s": s_out, "l": l_out}, s_pool, l_pool

    hlo = jax.jit(tick, donate_argnums=(5, 6)).lower(
        sp, lp, jax.ShapeDtypeStruct((), jnp.float32, sharding=sharding),
        si, li, s_pool, l_pool).compile().as_text()
    found = [(name, op, dims) for name, dims, op in _COPY.findall(hlo)
             if tuple(int(d) for d in dims.split(",") if d) in pools]
    return found, pools


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "zamba2-7b"])
def test_tick_copies_no_pool(one_chip, arch):
    """The tick XLA compiles for the chip reads and writes every K/V pool
    where it lies: no lane (COW, admission, decode append, page gather)
    wants another tiling of the pool than the others, so no whole pool is
    copied, whatever each cell's K and head_dim: qwen2-1.5b's L and S tiers
    (K 2 and 1, head_dim 128, prefix sharing on) and zamba2-7b's hybrid
    tiers (K 32 and 8, head_dim 224, sharing off as in its cell)."""
    cfg = ARCHS[arch]
    found, pools = _tick_pool_copies(
        one_chip, _two_layers(cfg.s_variant(4)), _two_layers(cfg),
        sharing=arch == "qwen2-1.5b")
    assert len(pools) == 2, pools
    assert not found, found
