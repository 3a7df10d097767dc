"""Plain float32 reference of a dense GQA decoder (Qwen2), and the weights the
benchmark makes for it.

The equations follow the published Qwen2 architecture (arXiv:2407.10671 and
the released ``modeling_qwen2``): RMSNorm pre-norm, rotary embeddings on the
query and key halves (rotate-half layout), grouped-query attention with QKV
bias, a SwiGLU MLP, a final RMSNorm and a head tied to the embedding.  It is
written in straightforward ``jax.numpy`` from the configuration file's keys
and imports nothing of the system under test.

``init`` makes the weights in the parameter layout the serving program takes
(one stacked tree per tier; see PERF.md, "Interface with the program"), so
both the program and this reference read the very same arrays.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02          # initializer_range of the published config


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def program_fields(cfg: dict) -> dict:
    """The serving program's configuration fields for this architecture."""
    return dict(family="dense", num_layers=cfg["num_hidden_layers"],
                d_model=cfg["hidden_size"], vocab_size=cfg["vocab_size"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=head_dim(cfg), d_ff=cfg["intermediate_size"],
                qkv_bias=cfg["qkv_bias"], rope_theta=float(cfg["rope_theta"]),
                norm_eps=float(cfg["rms_norm_eps"]))


def init(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    """Seeded weights: normal(0, 0.02) matrices, small random biases and norm
    scales near one (so that those paths are exercised), the output head the
    transpose of the embedding (``tie_word_embeddings``)."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    n = cfg["num_hidden_layers"]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std=INIT_STD):
        return (std * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)

    def scale(shape):
        return (1.0 + 0.1 * jax.random.uniform(next(ks), shape, jnp.float32,
                                               -1.0, 1.0)).astype(dtype)

    embed = normal((v, d))
    attn = {"wq": normal((n, d, h * dh)), "wk": normal((n, d, kv * dh)),
            "wv": normal((n, d, kv * dh)), "wo": normal((n, h * dh, d))}
    if cfg["qkv_bias"]:
        attn.update(bq=normal((n, h * dh), 0.1), bk=normal((n, kv * dh), 0.1),
                    bv=normal((n, kv * dh), 0.1))
    return {
        "embed": embed,
        "layers": {"ln1": {"scale": scale((n, d))}, "attn": attn,
                   "ln2": {"scale": scale((n, d))},
                   "mlp": {"wi": normal((n, d, f)), "wg": normal((n, d, f)),
                           "wo": normal((n, f, d))}},
        "final_norm": {"scale": scale((d,))},
        "lm_head": (embed.T if cfg["tie_word_embeddings"] else normal((d, v))),
    }


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


def attention(q, k, v):
    """Causal grouped-query attention; q (B,T,H,dh), k/v (B,T,K,dh)."""
    rep = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def block(x, lp, cfg: dict):
    """One decoder layer in float32."""
    b, t, _ = x.shape
    h, kv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    a = lp["attn"]
    xn = rms_norm(x, lp["ln1"]["scale"], eps)
    q, k, v = xn @ a["wq"], xn @ a["wk"], xn @ a["wv"]
    if cfg["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q.reshape(b, t, h, dh), theta)
    k = rope(k.reshape(b, t, kv, dh), theta)
    o = attention(q, k, v.reshape(b, t, kv, dh)).reshape(b, t, h * dh)
    x = x + o @ a["wo"]
    m = lp["mlp"]
    xn = rms_norm(x, lp["ln2"]["scale"], eps)
    return x + (jax.nn.silu(xn @ m["wg"]) * (xn @ m["wi"])) @ m["wo"]


def forward(w: dict, cfg: dict, tokens, at, cast=None):
    """Logits (B, P, V) float32 at positions ``at`` (B, P) of ``tokens``
    (B, T), with every weight upcast to float32 one layer at a time."""
    f32 = cast or (lambda a: a.astype(jnp.float32))
    x = f32(w["embed"])[tokens]

    def layer(x, lp):
        return block(x, jax.tree.map(f32, lp), cfg), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = jnp.take_along_axis(x, at[:, :, None], axis=1)
    x = rms_norm(x, f32(w["final_norm"]["scale"]), cfg["rms_norm_eps"])
    return x @ f32(w["lm_head"])
