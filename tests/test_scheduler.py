"""Continuous-batching scheduler: drain-equivalence on mixed-length traffic,
one compiled executable across buckets, the per-tick single-sync guarantee,
per-request temperature / max-new-tokens / EOS semantics, and the lane
scopes of the compiled tick."""
import numpy as np
import pytest

from repro.configs.base import HIConfig
from repro.configs.registry import ARCHS
from repro.serving import engine as engine_mod
from repro.serving.batcher import Request
from repro.serving.engine import build_engine

STEPS = 3
# the dense family and the benchmarked hybrid (Mamba2 + shared attention)
ARCH_IDS = ["qwen2-1.5b", "zamba2-7b"]


def _mixed_requests(cfg, lens, rng, steps=STEPS, temperature=0.0):
    return [Request(i, rng.integers(0, cfg.vocab_size, L).astype(np.int32),
                    max_new_tokens=steps, temperature=temperature)
            for i, L in enumerate(lens)]


def _drain_by_bucket(eng, reqs, temps=False):
    """Reference: serve uniform-bucket batches through the drain path."""
    out = {}
    for L in sorted({len(r.prompt) for r in reqs}):
        sub = [r for r in reqs if len(r.prompt) == L]
        toks = np.stack([r.prompt for r in sub])
        seeds = np.asarray([r.request_id for r in sub], np.int32)
        res = eng.serve(toks, seeds=seeds)
        for j, r in enumerate(sub):
            out[r.request_id] = {k: res[k][j] for k in res}
    return out


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mamba2-370m", "zamba2-7b"])
def test_stream_matches_drain_on_mixed_lengths(arch):
    """serve_stream must produce token-identical greedy outputs (S tokens,
    merged tokens, confidence, offload set) to the drain path on mixed-length
    traffic — while compiling exactly ONE executable across all buckets."""
    cfg = ARCHS[arch].reduced()
    hi = HIConfig(theta=0.6, capacity_factor=1.0)   # no capacity drops
    rng = np.random.default_rng(7)
    reqs = _mixed_requests(cfg, [8, 16, 8, 16, 8], rng)

    eng_d = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32)
    drain = _drain_by_bucket(eng_d, reqs)
    eng_s = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32)
    stream = eng_s.serve_stream(reqs, buckets=(8, 16), num_slots=3,
                                page_size=8)

    assert set(stream) == {r.request_id for r in reqs}
    for rid, rec in stream.items():
        np.testing.assert_array_equal(rec["tokens"], drain[rid]["tokens"])
        np.testing.assert_array_equal(rec["s_tokens"], drain[rid]["s_tokens"])
        assert rec["offloaded"] == bool(drain[rid]["offloaded"])
        np.testing.assert_allclose(rec["confidence"],
                                   drain[rid]["confidence"], atol=1e-5)
    # the paged pool removed the bucket from every device shape
    assert eng_s.stats["stream_compiles"] == 1
    # the drain path needed one executable per bucket
    assert eng_d.stats["compiles"] == 2


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_stream_single_sync_per_tick(arch, monkeypatch):
    """Each scheduler tick performs exactly ONE device->host sync, through
    the engine's ``_host_fetch`` — no hidden fetches in admission,
    escalation, or completion handling."""
    calls = []
    real = engine_mod._host_fetch
    monkeypatch.setattr(engine_mod, "_host_fetch",
                        lambda tree: (calls.append(1), real(tree))[1])
    cfg = ARCHS[arch].reduced()
    eng = build_engine(cfg, HIConfig(theta=0.6, capacity_factor=1.0),
                       max_new_tokens=STEPS, cache_len=32)
    reqs = _mixed_requests(cfg, [8, 16, 8], np.random.default_rng(0))
    eng.serve_stream(reqs, buckets=(8, 16), num_slots=2, page_size=8)
    assert len(calls) == eng.stats["stream_ticks"] > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_stream_temperature_matches_drain(arch):
    """Per-request seeded sampling: temp > 0 continuations are reproducible
    across the two schedulers (keys depend only on request id + token index,
    not slot / tick / batch row)."""
    cfg = ARCHS[arch].reduced()
    hi = HIConfig(theta=0.0, capacity_factor=1.0)   # S-only: compare S tokens
    rng = np.random.default_rng(5)
    reqs = _mixed_requests(cfg, [8, 8, 16], rng, temperature=0.7)

    eng_d = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32,
                         temperature=0.7)
    drain = _drain_by_bucket(eng_d, reqs)
    eng_s = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32)
    stream = eng_s.serve_stream(reqs, buckets=(8, 16), num_slots=2,
                                page_size=8)
    for rid, rec in stream.items():
        np.testing.assert_array_equal(rec["tokens"], drain[rid]["tokens"])
    # and the sampled path actually differs from greedy
    eng_g = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32)
    greedy = _drain_by_bucket(eng_g, reqs)
    assert any(not np.array_equal(greedy[r]["tokens"], drain[r]["tokens"])
               for r in drain)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_stream_per_request_max_new_and_eos(arch):
    """Unlike the drain path's engine-wide step count, the scheduler honours
    per-request max_new_tokens and stops early on EOS, freeing the slot."""
    cfg = ARCHS[arch].reduced()
    hi = HIConfig(theta=0.0, capacity_factor=1.0)
    rng = np.random.default_rng(9)
    r_short = Request(0, rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                      max_new_tokens=2)
    r_long = Request(1, rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                     max_new_tokens=6)
    eng = build_engine(cfg, hi, max_new_tokens=6, cache_len=32)
    out = eng.serve_stream([r_short, r_long], buckets=(8,), num_slots=2,
                           page_size=8)
    assert len(out[0]["tokens"]) == 2
    assert len(out[1]["tokens"]) == 6

    # EOS: run greedy once, then replay with eos_id = the first token
    first = int(out[1]["tokens"][0])
    r_eos = Request(1, r_long.prompt, max_new_tokens=6, eos_id=first)
    eng2 = build_engine(cfg, hi, max_new_tokens=6, cache_len=32)
    out2 = eng2.serve_stream([r_eos], buckets=(8,), num_slots=2, page_size=8)
    assert len(out2[1]["tokens"]) == 1
    assert int(out2[1]["tokens"][0]) == first


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_stream_slot_count_smaller_than_traffic(arch):
    """More requests than slots: admission must recycle slots (the
    continuous part) and still serve everyone exactly once."""
    cfg = ARCHS[arch].reduced()
    hi = HIConfig(theta=1.1, capacity_factor=1.0)   # everything escalates
    rng = np.random.default_rng(4)
    reqs = _mixed_requests(cfg, [8] * 5, rng)
    eng = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32)
    out = eng.serve_stream(reqs, buckets=(8,), num_slots=2, page_size=8)
    assert len(out) == 5
    assert all(rec["offloaded"] and rec["served_remote"]
               for rec in out.values())
    assert eng.stats["offloaded"] == 5


# build name -> (scheduler settings, the lanes each tier traces)
_BUILDS = {
    "plain": ({}, {"admit", "decode"}),
    "sharing_chunk": ({"prefix_sharing": True, "chunk_prefill": True},
                      {"cow", "admit", "prefix", "chunk", "decode"}),
    "chunk_only": ({"chunk_prefill": True}, {"admit", "chunk", "decode"}),
    "int8": ({"kv_dtype": "int8"}, {"admit", "decode"}),
    "mesh_1x1": ({"mesh": (1, 1)}, {"admit", "decode"}),
}


# the hybrid on a serving mesh is a known defect (ROADMAP.md), so the mesh
# build runs on the dense family only
@pytest.mark.parametrize("arch, build", [
    (arch, build) for arch in ARCH_IDS for build in _BUILDS
    if build != "mesh_1x1" or arch == "qwen2-1.5b"])
def test_lane_of_ops_names_every_cond_and_while(arch, build):
    """Every ``conditional`` and ``while`` of the compiled tick lies in
    exactly one lane scope, and each tier's lanes that this build traces
    appear in the map."""
    import re

    from repro.launch.mesh import make_serving_mesh
    from repro.serving.scheduler import ContinuousScheduler

    build, lanes = _BUILDS[build]
    cfg = ARCHS[arch].reduced()
    eng = build_engine(cfg, HIConfig(theta=0.6, capacity_factor=1.0),
                       max_new_tokens=STEPS, cache_len=32)
    kw = dict(build)
    if "mesh" in kw:
        kw["mesh"] = make_serving_mesh(*kw["mesh"])
    sched = ContinuousScheduler(eng.s, eng.l, eng.hi, max_prompt_len=16,
                                max_new_tokens=STEPS, num_slots=2, l_slots=2,
                                page_size=8, decode_block=2, **kw)
    text = sched._exec.as_text()
    loops = re.findall(r"^\s*(?:ROOT )?%?(\S+) = \S+.*? (?:conditional|while)\(",
                       text, re.M)
    lane_of = sched.lane_of_ops()
    assert loops and all(name in lane_of for name in loops), \
        [n for n in loops if n not in lane_of]
    want = {f"{t}/{lane}" for t in ("S", "L") for lane in lanes}
    assert set(lane_of.values()) == want
