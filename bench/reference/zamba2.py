"""Plain float32 reference of Zamba2 (Mamba2 layers with shared transformer
blocks), and the weights the benchmark makes for it.

The equations follow the released ``modeling_zamba2`` (transformers 4.57,
``torch_forward`` of the mixer) and arXiv:2411.15242.  With h the input of
layer i and e the token embedding, a layer whose index is in
``hybrid_layer_ids`` (the j-th such layer) first applies shared block
j % ``num_mem_blocks``:

    t = RMSNorm(concat(h, e))                         width 2d
    a = o_proj(softmax(q k^T * (head_dim / 2) ** -0.5) v)   RoPE on q and k
    m = down(gelu(gate) * up),  [gate | up] = x W_gu + B_j(A_j(x)),  x = RMSNorm(a)
    u = Linear_j(m)                                   no residual in the block
    h = h + Mamba_i(RMSNorm(h + u))

and every other layer computes h + Mamba_i(RMSNorm(h)).  The Mamba2 mixer:
in_proj -> [z | xBC | dt]; xBC through a causal depthwise conv (width
``mamba_d_conv``, with bias) and silu; x in ``n_mamba_heads`` heads, B and C in
``mamba_ngroups`` groups (head j reads group j // (heads / groups)); dt =
max(softplus(dt + dt_bias), time_step_min); the state is advanced one token
at a time, s_t = exp(dt A) s_{t-1} + dt x_t B_t^T, y_t = C_t s_t + D x_t,
which shares no algorithm with the program's chunked prompt pass; then the
gated RMSNorm of y * silu(z) over each group's channels and out_proj.  A final
RMSNorm and the head tied to the embedding give the logits.

Departures, each deliberate:

* only the first ``num_hidden_layers`` layers run (the configuration's cut),
  with the adapters and linears of the applications among them;
* dt takes ``time_step_min`` as a floor, as ``torch_forward`` clamps it; the
  CUDA path of the same file applies no floor when ``time_step_limit`` is
  unset;
* no attention mask or padding: every row is one unpadded sequence;
* float32 throughout where the published model runs bfloat16;
* the weights are random (``init``), the head tied to the embedding.

It is written in straightforward ``jax.numpy`` from the configuration file's
keys and imports nothing of the system under test.  ``init`` makes the
weights in the parameter layout the serving program takes (see PERF.md,
"Interface with the program"), so both read the very same arrays.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02          # initializer_range of the published config


def hybrid_ids(cfg: dict) -> list:
    """The hybrid layers among the configuration's first layers."""
    return [i for i in cfg["hybrid_layer_ids"] if i < cfg["num_hidden_layers"]]


def _check(cfg: dict) -> None:
    d = cfg["hidden_size"]
    assert cfg["attention_hidden_size"] == 2 * d
    assert cfg["attention_head_dim"] * cfg["num_attention_heads"] == 2 * d
    assert cfg["n_mamba_heads"] * cfg["mamba_headdim"] == cfg["mamba_expand"] * d
    assert cfg["use_shared_mlp_adapter"] and not cfg["use_shared_attention_adapter"]
    assert not cfg["add_bias_linear"] and cfg["use_conv_bias"]
    assert cfg["time_step_limit"] is None and not cfg["use_long_context"]
    assert cfg["hidden_act"] == "gelu"
    assert [i for i, t in enumerate(cfg["layers_block_type"][:cfg["num_hidden_layers"]])
            if t == "hybrid"] == hybrid_ids(cfg)


def program_fields(cfg: dict) -> dict:
    """The serving program's configuration fields for this architecture."""
    _check(cfg)
    dh = cfg["attention_head_dim"]
    return dict(family="hybrid", num_layers=cfg["num_hidden_layers"],
                d_model=cfg["hidden_size"], vocab_size=cfg["vocab_size"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"], head_dim=dh,
                d_ff=cfg["intermediate_size"],
                rope_theta=float(cfg["rope_theta"]) if cfg["use_mem_rope"] else 0.0,
                ssm_state=cfg["mamba_d_state"], ssm_expand=cfg["mamba_expand"],
                ssm_head_dim=cfg["mamba_headdim"], ssm_chunk=cfg["chunk_size"],
                ssm_conv_width=cfg["mamba_d_conv"],
                ssm_ngroups=cfg["mamba_ngroups"],
                ssm_dt_min=float(cfg["time_step_min"]),
                hybrid_layer_ids=tuple(hybrid_ids(cfg)),
                num_mem_blocks=cfg["num_mem_blocks"],
                adapter_rank=cfg["adapter_rank"],
                norm_eps=float(cfg["rms_norm_eps"]),
                tie_embeddings=cfg["tie_word_embeddings"])


def _widths(cfg: dict):
    d, g, n = cfg["hidden_size"], cfg["mamba_ngroups"], cfg["mamba_d_state"]
    di = cfg["mamba_expand"] * d
    return d, di, di + 2 * g * n, cfg["n_mamba_heads"]


def init(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    """Seeded weights: matrices normal(0, 0.02), the mixer's A, D and dt
    bias as the published initializer sets them (A = -(1..heads), D = 1, dt
    log-uniform in [time_step_min, time_step_max]), the conv as torch's
    default (uniform within 1/sqrt(width)), norm scales near one (so that
    those paths are exercised), the head the transpose of the embedding."""
    _check(cfg)
    d, di, cd, nh = _widths(cfg)
    v, f, r, w = (cfg["vocab_size"], cfg["intermediate_size"], cfg["adapter_rank"],
                  cfg["mamba_d_conv"])
    hq = cfg["num_attention_heads"] * cfg["attention_head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["attention_head_dim"]
    n, nb, na = cfg["num_hidden_layers"], cfg["num_mem_blocks"], len(hybrid_ids(cfg))
    ks = iter(jax.random.split(key, 16 + 8 * nb + 3 * na))

    def normal(*shape):
        return (INIT_STD * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)

    def scale(*shape):
        return (1.0 + 0.1 * jax.random.uniform(next(ks), shape, jnp.float32,
                                               -1.0, 1.0)).astype(dtype)

    def uniform(bound, *shape):
        return (bound * jax.random.uniform(next(ks), shape, jnp.float32, -1.0, 1.0)
                ).astype(dtype)

    lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
    dt = jnp.maximum(jnp.exp(lo + (hi - lo) * jax.random.uniform(next(ks), (n, nh))),
                     cfg["time_step_floor"])
    embed = normal(v, d)
    mamba = {"ln": {"scale": scale(n, d)},
             # in_proj's three parts, [z | xBC | dt]
             "in_z": normal(n, d, di), "in_xbc": normal(n, d, cd), "in_dt": normal(n, d, nh),
             "conv_w": uniform(1 / math.sqrt(w), n, w, cd),
             "conv_b": uniform(1 / math.sqrt(w), n, cd),
             "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
                                       (n, nh)),
             "D": jnp.ones((n, nh), jnp.float32),
             "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),       # softplus^-1(dt)
             "norm": {"scale": scale(n, di)}, "out_proj": normal(n, di, d)}
    shared = [{"ln1": {"scale": scale(2 * d)},
               "attn": {"wq": normal(2 * d, hq), "wk": normal(2 * d, hkv),
                        "wv": normal(2 * d, hkv), "wo": normal(hq, d)},
               "ln2": {"scale": scale(d)},
               "mlp": {"wi": normal(d, 2 * f), "wo": normal(f, d)}} for _ in range(nb)]
    apps = [{"lora_a": normal(d, r), "lora_b": normal(r, 2 * f), "linear": normal(d, d)}
            for _ in range(na)]
    return {"embed": embed, "mamba": mamba, "shared": shared, "apps": apps,
            "final_norm": {"scale": scale(d)}, "lm_head": embed.T}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotary embedding, rotate-half layout; x: (B, T, heads, dh)."""
    dh, t = x.shape[-1], x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def shared_block(h, e, sp, ap, cfg: dict):
    """One application of a shared block: u (B, T, d)."""
    b, t, _ = h.shape
    nh, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["attention_head_dim"])
    eps = cfg["rms_norm_eps"]
    a = sp["attn"]
    x = rms_norm(jnp.concatenate([h, e], axis=-1), sp["ln1"]["scale"], eps)
    q = (x @ a["wq"]).reshape(b, t, nh, dh)
    k = (x @ a["wk"]).reshape(b, t, kv, dh)
    v = (x @ a["wv"]).reshape(b, t, kv, dh)
    if cfg["use_mem_rope"]:
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, nh // kv, axis=2)
    v = jnp.repeat(v, nh // kv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (dh / 2) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = rms_norm(o.reshape(b, t, nh * dh) @ a["wo"], sp["ln2"]["scale"], eps)
    gu = x @ sp["mlp"]["wi"] + (x @ ap["lora_a"]) @ ap["lora_b"]
    gate, up = jnp.split(gu, 2, axis=-1)
    m = (jax.nn.gelu(gate, approximate=False) * up) @ sp["mlp"]["wo"]
    return m @ ap["linear"]


def mixer(x, lp, cfg: dict):
    """The Mamba2 mixer over (B, T, d), its state advanced token by token."""
    b, t, _ = x.shape
    d, di, cd, nh = _widths(cfg)
    g, n, p, w = (cfg["mamba_ngroups"], cfg["mamba_d_state"], cfg["mamba_headdim"],
                  cfg["mamba_d_conv"])
    z, xbc, dt = x @ lp["in_z"], x @ lp["in_xbc"], x @ lp["in_dt"]
    pad = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
    xbc = sum(pad[:, i:i + t] * lp["conv_w"][i] for i in range(w)) + lp["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :di].reshape(b, t, nh, p)
    rep = nh // g
    B = jnp.repeat(xbc[..., di:di + g * n].reshape(b, t, g, n), rep, axis=2)
    C = jnp.repeat(xbc[..., di + g * n:].reshape(b, t, g, n), rep, axis=2)
    dt = jnp.maximum(jax.nn.softplus(dt + lp["dt_bias"]), cfg["time_step_min"])
    A = -jnp.exp(lp["A_log"])

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp                       # (B,H,P) (B,H,N) (B,H,N) (B,H)
        s = jnp.exp(dt_t * A)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)

    s0 = jnp.zeros((b, nh, p, n), jnp.float32)
    _, y = jax.lax.scan(step, s0, (xs.swapaxes(0, 1), B.swapaxes(0, 1),
                                   C.swapaxes(0, 1), dt.swapaxes(0, 1)))
    y = y.swapaxes(0, 1) + lp["D"][:, None] * xs
    y = (y.reshape(b, t, di) * jax.nn.silu(z)).reshape(b, t, g, di // g)
    y = rms_norm(y, 1.0, 1e-5).reshape(b, t, di) * lp["norm"]["scale"]
    return y @ lp["out_proj"]


def forward(w: dict, cfg: dict, tokens, at, cast=None):
    """Logits (B, P, V) float32 at positions ``at`` (B, P) of ``tokens``
    (B, T), with every weight upcast to float32 as it is used."""
    f32 = cast or (lambda a: a.astype(jnp.float32))
    eps = cfg["rms_norm_eps"]
    e = f32(w["embed"])[tokens]
    ids = hybrid_ids(cfg)
    bounds = ids + [cfg["num_hidden_layers"]]

    def layer(h, lp):
        lp = jax.tree.map(f32, lp)
        return h + mixer(rms_norm(h, lp["ln"]["scale"], eps), lp, cfg), None

    def run(h, lo, hi):
        stack = jax.tree.map(lambda a: a[lo:hi], w["mamba"])
        return jax.lax.scan(layer, h, stack)[0]

    h = run(e, 0, bounds[0]) if bounds[0] else e
    for j, lo in enumerate(ids):
        u = shared_block(h, e, jax.tree.map(f32, w["shared"][j % cfg["num_mem_blocks"]]),
                         jax.tree.map(f32, w["apps"][j]), cfg)
        first = jax.tree.map(lambda a: f32(a[lo]), w["mamba"])
        h = h + mixer(rms_norm(h + u, first["ln"]["scale"], eps), first, cfg)
        if bounds[j + 1] > lo + 1:
            h = run(h, lo + 1, bounds[j + 1])
    h = jnp.take_along_axis(h, at[:, :, None], axis=1)
    h = rms_norm(h, f32(w["final_norm"]["scale"]), eps)
    return h @ f32(w["lm_head"])

