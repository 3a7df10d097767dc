"""The paged decode pass carries each tier's stacked KV pools through its
layer scan and updates them in place: bitwise the form that sliced each
layer's pools in as scan inputs and stacked them back out (kept below as a
reference; for the hybrid, its layers unrolled with every pool and state
sliced out), for every family with KV pages and both pool dtypes, and the
compiled scheduler tick holds no pool-sized temporary for it."""
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.configs.base import HIConfig, HYBRID, MOE
from repro.configs.registry import ARCHS
from repro.models import layers as L
from repro.models import hybrid, mamba2, model_zoo as zoo
from repro.models.moe import moe_ffn_auto
from repro.models.transformer import layer_windows
from repro.serving.engine import build_engine
from repro.serving.scheduler import ContinuousScheduler

PAGE, SLOTS, NPG, PROMPT, STEPS = 8, 3, 4, 16, 3


def _xs_ys_decode(params, cfg, token, pos, block, cache, write_block):
    """The replaced form: each layer's pools go into the layer scan as xs
    (a one-layer slice) and come back stacked as ys."""
    s_tot = block.shape[1] * PAGE
    attn = partial(L.attention_decode_paged, block=block, pos=pos,
                   num_heads=cfg.num_heads, num_kv=cfg.num_kv_heads,
                   head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
                   write_block=write_block)

    def attend(ap, ln, x, kv, win):
        a, kv = attn(ap, L.rmsnorm(ln, x, cfg.norm_eps),
                     {k: v[None] for k, v in kv.items()}, 0, window=win)
        return x + a, {k: v[0] for k, v in kv.items()}

    if cfg.family == HYBRID:
        return _hybrid_unrolled_decode(params, cfg, token, pos, block, cache,
                                       write_block)

    def body(x, xs):
        lp, win, kv = xs
        x, kv = attend(lp["attn"], lp["ln1"], x, kv, win)
        xn = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
        if cfg.family != MOE:
            return x + L.swiglu(lp["mlp"], xn), kv
        y = moe_ffn_auto(lp, cfg, xn, 2.0)[0]
        for extra in ("shared", "dense"):
            if extra in lp:
                y = y + L.swiglu(lp[extra], xn)
        return x + y, kv

    win = (jnp.full(cache["kp"].shape[:1], s_tot, jnp.int32)
           if cfg.family == MOE else layer_windows(cfg, s_tot))
    h, ys = lax.scan(body, params["embed"][token],
                     (params["layers"], win, L.paged_kv(cache)))
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h[:, 0, :] @ params["lm_head"]).astype(jnp.float32)
    return logits, {**cache, **ys}


def _hybrid_unrolled_decode(params, cfg, token, pos, block, cache,
                            write_block):
    """The hybrid's layers one by one in Python: each application's pools and
    each layer's recurrent state sliced out, and stacked back at the end."""
    ids = list(cfg.hybrid_layer_ids)
    e = params["embed"][token]
    h = e
    kv = L.paged_kv(cache)
    new_kv = {k: [] for k in kv}
    states, convs = [], []
    for i in range(cfg.num_layers):
        u = jnp.zeros_like(h)
        if i in ids:
            j = ids.index(i)
            sp = params["shared"][j % cfg.num_mem_blocks]
            t = L.rmsnorm(sp["ln1"], jnp.concatenate([h, e], -1), cfg.norm_eps)
            a, kvj = L.attention_decode_paged(
                sp["attn"], t, {k: v[j][None] for k, v in kv.items()}, 0,
                block, pos, num_heads=cfg.num_heads, num_kv=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
                write_block=write_block, q_scale=hybrid.Q_SCALE)
            for k, v in kvj.items():
                new_kv[k].append(v[0])
            u = hybrid._shared_out(sp, params["apps"][j], cfg, a)
        lp = jax.tree.map(lambda a: a[i], params["mamba"])
        y, st, cw = mamba2.mamba_block_step(
            lp, cfg, L.rmsnorm(lp["ln"], h + u, cfg.norm_eps),
            cache["state"][i], cache["conv"][i])
        h = h + y
        states.append(st)
        convs.append(cw)
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h[:, 0, :] @ params["lm_head"]).astype(jnp.float32)
    return logits, {"state": jnp.stack(states), "conv": jnp.stack(convs),
                    **{k: jnp.stack(v) for k, v in new_kv.items()}}


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "deepseek-moe-16b",
                                  "zamba2-2.7b"])
def test_carried_pool_decode_matches_xs_ys_form(arch, dtype):
    """STEPS paged decode steps of slots at different depths, one slot's
    appends masked to the null page: logits and every pool and scale
    bitwise equal to the xs/ys form's."""
    cfg = ARCHS[arch].reduced()
    params = zoo.init_params(jax.random.PRNGKey(0), cfg)
    cache = zoo.init_paged_cache(cfg, SLOTS, SLOTS * NPG + 1, PAGE,
                                 jnp.dtype(dtype))
    block = jnp.arange(1, SLOTS * NPG + 1, dtype=jnp.int32).reshape(SLOTS, NPG)
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (SLOTS, PROMPT)),
                       jnp.int32)
    _, cache = zoo.prefill_paged(params, cfg, toks,
                                 jnp.full((SLOTS,), PROMPT, jnp.int32),
                                 jnp.arange(SLOTS, dtype=jnp.int32), block,
                                 cache)
    pos = jnp.asarray([PROMPT, PROMPT - 5, PROMPT - 9], jnp.int32)
    wblock = block.at[1].set(0)
    new = jax.jit(lambda c, t, p: zoo.decode_step_paged(
        params, cfg, t, p, block, c, write_block=wblock))
    ref = jax.jit(lambda c, t, p: _xs_ys_decode(params, cfg, t, p, block, c,
                                                wblock))
    got, want = cache, cache
    tok = toks[:, -1:]
    for i in range(STEPS):
        lg_got, got = new(got, tok, pos + i)
        lg_want, want = ref(want, tok, pos + i)
        np.testing.assert_array_equal(np.asarray(lg_got), np.asarray(lg_want))
        tok = jnp.argmax(lg_got, -1).astype(jnp.int32)[:, None]
    assert sorted(got) == sorted(want) == sorted(cache)
    for k in cache:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]),
                                      err_msg=k)
    assert not np.array_equal(np.asarray(got["kp"]), np.asarray(cache["kp"]))


_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?(\S+) = \w+\[([\d,]*)\]\S* "
    r"(broadcast|copy|dynamic-update-slice)\(", re.M)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_tick_decode_updates_pools_in_place(kv_dtype):
    """The compiled tick holds no ``broadcast`` of either tier's whole pool
    shape (the stacked output of a layer scan that emits its pools as ys),
    and its decode lanes no ``copy`` or ``dynamic-update-slice`` of a pool
    or of one layer's slice of it.  Shapes are compared without dtypes: the
    CPU backend computes bf16 pools in float32."""
    cfg = ARCHS["qwen2-1.5b"].reduced()
    eng = build_engine(cfg, HIConfig(theta=0.6, capacity_factor=1.0),
                       max_new_tokens=3, cache_len=32)
    sched = ContinuousScheduler(eng.s, eng.l, eng.hi, max_prompt_len=16,
                                max_new_tokens=3, num_slots=2, l_slots=2,
                                page_size=8, decode_block=2,
                                kv_dtype=kv_dtype)
    whole, layer = set(), set()
    for rt in (sched.srt, sched.lrt):
        for buf in L.paged_kv(rt.pool.buffers).values():
            whole.add(tuple(buf.shape))
            layer.add(tuple(buf.shape[1:]))
    lane_of = sched.lane_of_ops()
    found = []
    for name, dims, op in _INSTR.findall(sched._exec.as_text()):
        shape = tuple(int(d) for d in dims.split(",") if d)
        in_decode = lane_of.get(name) in ("S/decode", "L/decode")
        if (op == "broadcast" and shape in whole) or \
                (in_decode and (shape in whole or shape in layer)):
            found.append((name, op, shape, lane_of.get(name)))
    assert not found, found
