"""The Zamba2 reference against the published code, and the serving program
against the reference, at a tiny size on the CPU (``data/tiny-zamba2.json``:
two shared blocks applied at layers 1, 3 and 6 of 7, two B/C groups, adapter
rank 8).

The weights come from ``bench/reference/zamba2.py: init`` with its
initializer's spread raised from 0.02 to 0.15: at width 32 that gives each
matrix about unit gain, so the shared blocks' output is of the hidden
state's size and an error there shows in the logits."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import DATA
from bench.reference import zamba2 as Z

CFG = json.loads((DATA / "tiny-zamba2.json").read_text())
UNIT_GAIN_STD = 0.15


def _weights(monkeypatch, dtype, seed=0):
    monkeypatch.setattr(Z, "INIT_STD", UNIT_GAIN_STD)
    return Z.init(CFG, jax.random.PRNGKey(seed), dtype)


def _hf_model(w, cfg):
    """``Zamba2ForCausalLM`` holding the weights ``w``.  Its chunk covers the
    whole test sequence: the released ``torch_forward`` sums the
    inter-chunk decay over the wrong chunk axis (``modeling_zamba2``, the
    ``decay_chunk`` contraction, which ``modeling_mamba2`` transposes
    first), so a prompt longer than one chunk does not follow the
    equations there."""
    torch = pytest.importorskip("torch")
    from transformers import Zamba2Config, Zamba2ForCausalLM

    hc = Zamba2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        layers_block_type=cfg["layers_block_type"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"], mamba_ngroups=cfg["mamba_ngroups"],
        n_mamba_heads=cfg["n_mamba_heads"], chunk_size=64,
        intermediate_size=cfg["intermediate_size"], hidden_act=cfg["hidden_act"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        num_mem_blocks=cfg["num_mem_blocks"], use_shared_attention_adapter=False,
        adapter_rank=cfg["adapter_rank"], use_mem_rope=cfg["use_mem_rope"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        time_step_min=cfg["time_step_min"], time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"], tie_word_embeddings=True)
    hc._attn_implementation = "eager"
    model = Zamba2ForCausalLM(hc).eval()
    w = jax.tree.map(lambda a: np.asarray(a, np.float32), w)
    ids = Z.hybrid_ids(cfg)

    def put(param, value):
        param.copy_(torch.from_numpy(np.ascontiguousarray(value)))

    with torch.no_grad():
        put(model.model.embed_tokens.weight, w["embed"])
        put(model.lm_head.weight, w["lm_head"].T)
        put(model.model.final_layernorm.weight, w["final_norm"]["scale"])
        for i, layer in enumerate(model.model.layers):
            dec = layer.mamba_decoder if i in ids else layer
            mx, lp = dec.mamba, jax.tree.map(lambda a: a[i], w["mamba"])
            put(dec.input_layernorm.weight, lp["ln"]["scale"])
            put(mx.in_proj.weight,
                np.concatenate([lp["in_z"], lp["in_xbc"], lp["in_dt"]], axis=-1).T)
            put(mx.conv1d.weight, lp["conv_w"].T[:, None, :])
            put(mx.conv1d.bias, lp["conv_b"])
            for name in ("dt_bias", "A_log", "D"):
                put(getattr(mx, name), lp[name])
            put(mx.norm.weight, lp["norm"]["scale"])
            put(mx.out_proj.weight, lp["out_proj"].T)
            if i not in ids:
                continue
            j = ids.index(i)
            blk, sp, ap = (layer.shared_transformer,
                           w["shared"][j % cfg["num_mem_blocks"]], w["apps"][j])
            put(blk.input_layernorm.weight, sp["ln1"]["scale"])
            put(blk.pre_ff_layernorm.weight, sp["ln2"]["scale"])
            for name in "qkvo":
                put(getattr(blk.self_attn, f"{name}_proj").weight, sp["attn"][f"w{name}"].T)
            put(blk.feed_forward.gate_up_proj.weight, sp["mlp"]["wi"].T)
            put(blk.feed_forward.down_proj.weight, sp["mlp"]["wo"].T)
            adapter = blk.feed_forward.gate_up_proj_adapter_list[j]
            put(adapter[0].weight, ap["lora_a"].T)
            put(adapter[1].weight, ap["lora_b"].T)
            put(layer.linear.weight, ap["linear"].T)
    return model


def test_reference_matches_published_zamba2(monkeypatch):
    """float32 logits at every position of two 12-token rows: the
    published model and the reference, one set of weights, agree within
    2e-4 (float32 summation order; the logits' spread is about 1)."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    w = _weights(monkeypatch, jnp.float32)
    model = _hf_model(w, CFG)
    toks = np.random.default_rng(1).integers(0, CFG["vocab_size"], (2, 12))
    with torch.no_grad():
        want = model(input_ids=torch.from_numpy(toks), use_cache=False).logits.numpy()
    with jax.default_matmul_precision("highest"):
        got = np.asarray(Z.forward(w, CFG, jnp.asarray(toks),
                                   jnp.tile(jnp.arange(12), (2, 1))))
    assert want.std() > 0.3
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)


PROMPTS, STEPS, PAGE, PAGES = (10, 13), 6, 8, 4

# max |program - reference| over every compared logit, and why:
# float32: the program's chunked SSD (chunk 4, so the 16-token prompt
# crosses chunks) against the reference's token-by-token recurrence, and
# fused against separate matmuls: summation order only.
# bfloat16: weights, activations, conv window and K/V rounded to bf16 in the
# program (relative 2^-8 per rounding, through 7 unit-gain layers) against
# float32 on the same weights: 0.12 at most on this seed, 0.08 at the 99th
# percentile, for logits of spread about 1.
TOL = {"float32": 2e-4, "bfloat16": 0.2}


@pytest.mark.parametrize("dtype", sorted(TOL))
def test_program_prefill_and_decode_match_reference(monkeypatch, dtype):
    """The program's paged prefill of two prompts of different lengths
    (right-padded to one bucket), then STEPS paged decode steps
    teacher-forced on a fixed continuation, against the reference's full
    forward over prompt and continuation."""
    from repro.configs.base import ModelConfig
    from repro.models import model_zoo as zoo

    w = _weights(monkeypatch, jnp.dtype(dtype), seed=3)
    mc = ModelConfig(name="tiny-zamba2", **Z.program_fields(CFG))
    rng = np.random.default_rng(4)
    seqs = [rng.integers(0, CFG["vocab_size"], n + STEPS) for n in PROMPTS]
    bucket = 16
    toks = np.zeros((2, bucket), np.int32)
    for r, (s, n) in enumerate(zip(seqs, PROMPTS)):
        toks[r, :n] = s[:n]
    lens = jnp.asarray(PROMPTS, jnp.int32)
    block = jnp.arange(1, 2 * PAGES + 1, dtype=jnp.int32).reshape(2, PAGES)
    cache = zoo.init_paged_cache(mc, 2, 2 * PAGES + 1, PAGE, jnp.dtype(dtype))
    logits, cache = zoo.prefill_paged(w, mc, jnp.asarray(toks), lens,
                                      jnp.arange(2, dtype=jnp.int32), block, cache)
    got = [np.asarray(logits)]
    step = jax.jit(lambda c, t, p: zoo.decode_step_paged(w, mc, t, p, block, c))
    for k in range(STEPS - 1):
        tok = jnp.asarray([[s[n + k]] for s, n in zip(seqs, PROMPTS)], jnp.int32)
        logits, cache = step(cache, tok, lens + k)
        got.append(np.asarray(logits))
    got = np.stack(got, axis=1)                        # (2, STEPS, V)

    t_max = max(PROMPTS) + STEPS
    full = np.zeros((2, t_max), np.int32)
    for r, s in enumerate(seqs):
        full[r, :len(s)] = s
    at = jnp.asarray([[n - 1 + k for k in range(STEPS)] for n in PROMPTS], jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(Z.forward(w, CFG, jnp.asarray(full), at))
    assert want.std() > 0.3
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL[dtype])
