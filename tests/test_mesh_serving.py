"""Mesh-sharded tier-split serving (scheduler ``mesh=`` / engine
``serve_stream(mesh=...)``).

The contract under test, per invariant:

* a (1, 1) DEBUG mesh is semantics-free — greedy outputs are
  token-identical (bitwise at kv_dtype="bf16") to the single-device path,
  in both kv_dtype modes, with prefix sharing and chunked prefill on;
* ``stream_compiles`` stays 1 and the tick keeps exactly ONE host fetch
  with every mesh feature on (the staging buffer, the shard_map'd S tier,
  the GSPMD-sharded L tier add operands and lanes, never syncs);
* every per-replica KV-pool shard passes ``check_invariants`` (and holds
  no slots) after an escalation-heavy faulted run — the transfer staging
  path leaks nothing;
* data=2: two S replicas, each owning a disjoint slot slice + its own pool
  shard, still reproduce the single-device tokens (subprocess with 8
  forced host devices — the established tests/test_tier_split.py pattern).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs.base import HIConfig
from repro.configs.registry import ARCHS
from repro.launch.mesh import make_serving_mesh
from repro.serving.batcher import Request
from repro.serving.engine import build_engine
from repro.serving import engine as engine_mod
from repro.serving.faults import FaultSchedule, RetryPolicy

STEPS = 4
KW = dict(buckets=(8,), num_slots=2, page_size=8)


def _reqs(cfg, n):
    rng = np.random.default_rng(0)
    return [Request(i, rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=STEPS) for i in range(n)]


@pytest.fixture(scope="module")
def cfg():
    return ARCHS["qwen2-1.5b"].reduced()


@pytest.fixture(scope="module")
def ref(cfg):
    """Single-device reference records per kv_dtype (theta 0.5 mixes local
    finishes with escalations, so the staging path is load-bearing)."""
    out = {}
    for kv in ("bf16", "int8"):
        e = build_engine(cfg, HIConfig(theta=0.5, capacity_factor=1.0),
                         max_new_tokens=STEPS, cache_len=32)
        out[kv] = e.serve_stream(_reqs(cfg, 6), validate=True,
                                 kv_dtype=kv, **KW)
    return out


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_debug_mesh_token_identity(cfg, ref, kv):
    """(1, 1) mesh: the shard_map'd S tier, the sharded L tier, and the
    double-buffered escalation staging produce BITWISE the single-device
    greedy tokens, statuses, and offload decisions — in both KV modes."""
    e = build_engine(cfg, HIConfig(theta=0.5, capacity_factor=1.0),
                     max_new_tokens=STEPS, cache_len=32)
    out = e.serve_stream(_reqs(cfg, 6), validate=True, kv_dtype=kv,
                         mesh=make_serving_mesh(1, 1), **KW)
    assert out.keys() == ref[kv].keys()
    for rid, a in ref[kv].items():
        b = out[rid]
        assert np.array_equal(a["tokens"], b["tokens"]), rid
        assert a["status"] == b["status"]
        assert a["offloaded"] == b["offloaded"]
    assert e.stats["stream_compiles"] == 1


def test_mesh_one_fetch_per_tick_all_features(cfg, monkeypatch):
    """With prefix sharing + chunked prefill + the mesh staging path all on,
    the tick discipline holds: ONE compile, exactly ONE host fetch per tick."""
    calls = {"n": 0}
    real = engine_mod._host_fetch

    def counting(tree):
        calls["n"] += 1
        return real(tree)

    monkeypatch.setattr(engine_mod, "_host_fetch", counting)
    e = build_engine(cfg, HIConfig(theta=0.5, capacity_factor=1.0),
                     max_new_tokens=STEPS, cache_len=32)
    e.serve_stream(_reqs(cfg, 6), validate=True, prefix_sharing=True,
                   chunk_prefill=True, chunk_size=4,
                   mesh=make_serving_mesh(1, 1), **KW)
    assert e.stats["stream_compiles"] == 1
    assert calls["n"] == e.stats["stream_ticks"] > 0


def test_mesh_pool_shards_clean_after_faulted_escalations(cfg):
    """Escalation-heavy faulted traffic (theta > 1: everything wants L;
    losses + an outage exercise retry/breaker/degrade): afterwards every
    replica pool shard and the L pool pass check_invariants with no held
    slots, and every record terminates with a legal status."""
    fs = FaultSchedule(seed=7, loss_prob=0.3, delay_ticks=1, delay_jitter=2,
                       outages=((4, 8),))
    rp = RetryPolicy(max_retries=2, backoff_base_ticks=1, backoff_cap_ticks=4,
                     breaker_threshold=3, breaker_cooldown_ticks=4)
    e = build_engine(cfg, HIConfig(theta=1.1, capacity_factor=1.0),
                     max_new_tokens=STEPS, cache_len=32)
    out = e.serve_stream(_reqs(cfg, 8), faults=fs, retry=rp, validate=True,
                         mesh=make_serving_mesh(1, 1), **KW)
    assert len(out) == 8
    assert all(r["status"] in ("ok", "degraded_local", "dropped", "rejected")
               for r in out.values())
    sched = e._stream[1]
    for rt in (*sched.srts, sched.lrt):
        rt.pool.check_invariants()
        assert all(r is None for r in rt.slot_req)
    assert e.stats["stream_compiles"] == 1


def test_mesh_rejects_bad_configs(cfg):
    """Guard rail: a mesh without the serving axes fails loudly at
    construction."""
    import jax
    from jax.sharding import Mesh

    from repro.serving.scheduler import ContinuousScheduler
    e = build_engine(cfg, HIConfig(theta=0.5, capacity_factor=1.0),
                     max_new_tokens=STEPS, cache_len=32)
    bad = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("x", "model"))
    with pytest.raises(ValueError, match="serving mesh needs axes"):
        ContinuousScheduler(e.s, e.l, e.hi, max_prompt_len=8,
                            max_new_tokens=STEPS, num_slots=2, page_size=8,
                            mesh=bad)


_DATA2_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    assert len(jax.devices()) == 8
    from repro.configs.base import HIConfig
    from repro.configs.registry import ARCHS
    from repro.launch.mesh import make_serving_mesh
    from repro.serving.batcher import Request
    from repro.serving.engine import build_engine

    STEPS = 4
    KW = dict(buckets=(8,), num_slots=2, page_size=8)
    cfg = ARCHS["qwen2-1.5b"].reduced()
    def reqs(n):
        rng = np.random.default_rng(0)
        return [Request(i, rng.integers(0, cfg.vocab_size, 8)
                        .astype(np.int32), max_new_tokens=STEPS)
                for i in range(n)]
    hi = HIConfig(theta=0.5, capacity_factor=1.0)
    e1 = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32)
    ref = e1.serve_stream(reqs(8), validate=True, **KW)
    # data=2 (replica-sliced S slots), then the full (2, 2) mesh with the
    # L tier's params + KV pages sharded over model
    for shape in ((2, 1), (2, 2)):
        e2 = build_engine(cfg, hi, max_new_tokens=STEPS, cache_len=32)
        out = e2.serve_stream(reqs(8), validate=True,
                              mesh=make_serving_mesh(*shape), **KW)
        for rid in ref:
            assert np.array_equal(ref[rid]["tokens"], out[rid]["tokens"]), \\
                (shape, rid)
            assert ref[rid]["status"] == out[rid]["status"]
        assert e2.stats["stream_compiles"] == 1
        sched = e2._stream[1]
        assert len(sched.srts) == shape[0]
        for rt in (*sched.srts, sched.lrt):
            rt.pool.check_invariants()
    print("MESH_DATA2_OK")
""")


def test_data2_replica_equivalence_subprocess():
    """data=2 / (2, 2) meshes on a forced 8-device host reproduce the
    single-device tokens with one compile and clean per-shard pools."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _DATA2_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=540)
    assert "MESH_DATA2_OK" in out.stdout, out.stdout + out.stderr
