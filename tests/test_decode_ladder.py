"""The decode lane reads K/V for a tier's occupied slots only: each
attention layer gathers pages for them at the narrowest width of a ladder
derived from the slot count (``scheduler.decode_widths``).  On a 16-slot
tier whose live slots sit at scattered indices and whose live count moves
16 -> 3 -> 12 -> 3 (widths 16 -> 8 -> 16 -> 8): greedy tokens and
confidences equal the full-width lane's, pad rows write only the null
page, the tick stays one compile and one host fetch, and
``decode_rows@<tier>`` records the width run."""
import bisect

import jax
import numpy as np
import pytest

from repro.configs.base import HIConfig
from repro.configs.registry import ARCHS
from repro.models import layers as L
from repro.serving import engine as engine_mod
from repro.serving import scheduler as sched_mod
from repro.serving.batcher import AdmissionQueue, Request
from repro.serving.engine import build_engine
from repro.serving.scheduler import ContinuousScheduler, decode_widths
from repro.serving.telemetry import Telemetry

SLOTS, PAGE, K, SHORT, LONG = 16, 8, 2, 3, 11
ARCH_IDS = ["qwen2-1.5b", "deepseek-moe-16b", "zamba2-7b"]


@pytest.mark.parametrize("n, widths", [
    (2, (2,)), (8, (8,)), (12, (8, 12)), (16, (8, 16)), (48, (16, 24, 48)),
    (64, (16, 32, 64)), (128, (32, 64, 128))])
def test_decode_widths(n, widths):
    assert decode_widths(n) == widths


class _Waves(AdmissionQueue):
    """Requests that join the queue in waves: ``waves[t]`` when the
    scheduler has dispatched ``t`` ticks."""

    def __init__(self, sched, waves):
        super().__init__(buckets=(8, 16),
                         page_size=PAGE if sched.prefix_sharing else None)
        self._sched, self._waves = sched, dict(waves)

    def __len__(self):
        for t in sorted(t for t in self._waves
                        if t <= self._sched.counters.ticks):
            for req in self._waves.pop(t):
                self.submit(req)
        return super().__len__()


def _waves(cfg):
    """Tick 0: 16 requests, long ones at slots 0, 5 and 15 (a short one
    answers in its admission tick); tick 2: 9 short ones into the gaps."""
    rng = np.random.default_rng(5)

    def req(rid, steps):
        n = 8 if rid % 2 else 16
        return Request(rid, rng.integers(0, cfg.vocab_size, n)
                       .astype(np.int32), max_new_tokens=steps)

    first = [req(i, LONG if i in (0, 5, SLOTS - 1) else SHORT)
             for i in range(SLOTS)]
    return {0: first, 2: [req(SLOTS + i, SHORT) for i in range(9)]}


def _serve(arch, full_width, **build):
    """Serve the waves; record, per dispatched tick and tier, the live
    slots, the width index run, the gauges, and the pages that changed."""
    cfg = ARCHS[arch].reduced()
    eng = build_engine(cfg, HIConfig(theta=0.6, capacity_factor=1.0),
                       max_new_tokens=LONG, cache_len=32)
    with pytest.MonkeyPatch.context() as mp:
        if full_width:
            mp.setattr(sched_mod, "decode_widths", lambda n: (n,))
        sched = ContinuousScheduler(
            eng.s, eng.l, eng.hi, max_prompt_len=16, max_new_tokens=LONG,
            num_slots=SLOTS, l_slots=SLOTS, page_size=PAGE,
            admit_width=SLOTS, decode_block=K, **build)
    ticks, fetches = [], []
    run_exec = sched._exec

    def traced_exec(s_par, l_par, theta, s_in, l_in, s_pool, l_pool):
        before = [jax.tree.map(np.array, p) for p in (s_pool, l_pool)]
        out = run_exec(s_par, l_par, theta, s_in, l_in, s_pool, l_pool)
        tick = {}
        for tier, tin, pre, post in zip("SL", (s_in, l_in), before, out[1:]):
            tick[tier] = _tier_tick(tin, pre, jax.tree.map(np.array, post))
        ticks.append(tick)
        return out

    real_fetch = engine_mod._host_fetch
    if "mesh" not in build:
        sched._exec = traced_exec
    tel = Telemetry()
    sched.set_telemetry(tel)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "_host_fetch",
                   lambda tree: (fetches.append(1), real_fetch(tree))[1])
        results = sched.run(_Waves(sched, _waves(cfg)))
    for tick, rec in zip(ticks, tel.ticks):
        for tier in "SL":
            tick[tier]["gauge"] = rec.gauges[f"decode_rows@{tier}"]
    return {"gauges": [rec.gauges for rec in tel.ticks],"results": results, "ticks": ticks, "fetches": len(fetches),
            "n_ticks": len(tel.ticks), "compiles": sched.stats["compiles"],
            "widths": {"S": sched.srt.widths, "L": sched.lrt.widths}}


def _tier_tick(tin, pre, post):
    """One tier's dispatch: its live slots (the ``decode_slot`` entries
    before the sentinel), the width index, and the physical pages whose
    bytes changed (any layer, K or V)."""
    dslot = np.asarray(tin["decode_slot"])
    live = dslot[dslot < len(dslot)]
    changed = set()
    for k, a in L.paged_kv(pre["core"]).items():
        b = L.paged_kv(post["core"])[k]
        diff = (a != b).reshape(a.shape[0], a.shape[1], -1).any(axis=(0, 2))
        changed |= set(np.flatnonzero(diff).tolist())
    block = np.asarray(tin["block"])
    return {"live": live.tolist(), "branch": int(tin["decode_branch"]),
            "owned": set(block[live].ravel().tolist()) - {0},
            "pages": changed}


@pytest.fixture(scope="module", params=ARCH_IDS)
def served(request):
    return {"ladder": _serve(request.param, False),
            "full": _serve(request.param, True)}


def test_ladder_runs_narrow_and_wide_ticks(served):
    """The waves drive the S tier through live counts 16, 3, 12 and 3 at
    scattered slots, so its decode lane runs both widths of (8, 16)."""
    run = served["ladder"]
    assert run["widths"] == {"S": (8, 16), "L": (8, 16)}
    s_live = [t["S"]["live"] for t in run["ticks"]]
    assert s_live[:4] == [list(range(SLOTS)), [0, 5, SLOTS - 1],
                          [0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, SLOTS - 1],
                          [0, 5, SLOTS - 1]]
    assert [t["S"]["gauge"] for t in run["ticks"][:4]] == [16, 8, 16, 8]
    assert {t["L"]["gauge"] for t in run["ticks"]} >= {8, 16}


def _assert_same_answers(served):
    got, want = served["ladder"]["results"], served["full"]["results"]
    assert set(got) == set(want) and len(got) == SLOTS + 9
    for rid in want:
        for k in ("tokens", "s_tokens"):
            np.testing.assert_array_equal(got[rid][k], want[rid][k])
        assert got[rid]["confidence"] == want[rid]["confidence"]
        assert got[rid]["offloaded"] == want[rid]["offloaded"]


def test_ladder_matches_full_width_lane(served):
    """Greedy tokens (S and merged) and confidences are identical to the
    full-width lane's for every request."""
    _assert_same_answers(served)


@pytest.fixture(scope="module", params=[
    {"chunk_prefill": True, "chunk_size": 8},
    {"chunk_prefill": True, "chunk_size": 8, "prefix_sharing": True}],
    ids=["chunk_prefill", "chunk_prefill_sharing"])
def served_other(request):
    return {"ladder": _serve("qwen2-1.5b", False, **request.param),
            "full": _serve("qwen2-1.5b", True, **request.param)}


def test_ladder_matches_full_width_in_chunk_builds(served_other):
    """The chunk lane's mid-prefill slots read K/V through the same ladder,
    with prefix sharing off and on: answers identical to the full-width
    lane's, narrow ticks run, and pages outside the live slots' and the
    null page stay untouched."""
    _assert_same_answers(served_other)
    ticks = served_other["ladder"]["ticks"]
    assert any(0 < t["S"]["gauge"] < SLOTS for t in ticks)
    for tick in ticks:
        for t in tick.values():
            assert t["pages"] <= t["owned"] | {0}, t


def test_pad_rows_write_only_the_null_page(served):
    """Every page that changed in a tick, narrow or full, belongs to a live
    slot or is the null page 0: every page of the pool that no live slot
    owns is byte-identical before and after the tick."""
    run = served["ladder"]
    narrow = 0
    for tick in run["ticks"]:
        for tier in "SL":
            t = tick[tier]
            assert t["pages"] <= t["owned"] | {0}, (tier, t)
            narrow += bool(t["live"]) and t["gauge"] < SLOTS
    assert narrow >= 3


def test_ladder_keeps_one_compile_and_one_fetch_per_tick(served):
    run = served["ladder"]
    assert run["compiles"] == 1
    assert run["fetches"] == run["n_ticks"] == len(run["ticks"]) > 0


def test_decode_rows_gauge_is_the_ladder_width(served):
    """``decode_rows@<tier>`` is the narrowest width that holds the tick's
    live slots, 0 when the tier has none, and the tick reads at that
    width's index; the full-width build records its slot count whenever a
    slot is live."""
    for name, run in served.items():
        for tick in run["ticks"]:
            for tier in "SL":
                t, widths = tick[tier], run["widths"][tier]
                k = bisect.bisect_left(widths, len(t["live"]))
                assert t["branch"] == k, (name, tier, t)
                assert t["gauge"] == (widths[k] if t["live"] else 0)


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_attention_rows_read_only_the_listed_slots(kv_dtype, use_kernel):
    """``attention_decode_paged`` with ``rows``: the listed rows' outputs
    equal the full read's at every width of the ladder, the other rows
    read zeros, and the pools are updated exactly as the full read updates
    them (every row appends).  The kernel grids over rows, so its outputs
    match bitwise; the jnp path compiles its einsums inside a switch, at
    another batch, and may sum in another order: they match to float32
    rounding."""
    import jax.numpy as jnp

    from repro.models import model_zoo as zoo

    cfg = ARCHS["qwen2-1.5b"].reduced()
    b, npg = 16, 3
    params = zoo.init_params(jax.random.PRNGKey(0), cfg)["layers"]["attn"]
    params = jax.tree.map(lambda a: a[0], params)
    cache = zoo.init_paged_cache(cfg, b, b * npg + 1, PAGE, jnp.dtype(kv_dtype))
    kv = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(1), a.shape).astype(a.dtype)
        if a.dtype != jnp.float32 else jnp.abs(jax.random.normal(
            jax.random.PRNGKey(2), a.shape)) + 0.01, L.paged_kv(cache))
    live = np.asarray([0, 5, 6, 11, b - 1])
    block = np.zeros((b, npg), np.int32)
    block[live] = 1 + np.arange(len(live) * npg).reshape(-1, npg)
    pos = np.zeros((b,), np.int32)
    pos[live] = [3, 17, 9, 22, 12]
    slots = np.full((b,), b, np.int32)
    slots[: len(live)] = live
    x = jax.random.normal(jax.random.PRNGKey(3), (b, 1, cfg.d_model))

    def attend(rows):
        return L.attention_decode_paged(
            params, x, kv, 1, jnp.asarray(block), jnp.asarray(pos),
            num_heads=cfg.num_heads, num_kv=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            use_kernel=use_kernel, rows=rows)

    want, want_kv = jax.jit(lambda: attend(None))()
    widths = decode_widths(b)
    for k in range(len(widths)):
        got, got_kv = jax.jit(lambda s, i: attend((s, i, widths)))(
            jnp.asarray(slots), jnp.asarray(k))
        got, ref = np.asarray(got), np.asarray(want)
        read = live if widths[k] < b else np.arange(b)
        if use_kernel:
            np.testing.assert_array_equal(got[read], ref[read])
        else:
            np.testing.assert_allclose(got[read], ref[read], rtol=1e-5,
                                       atol=1e-6)
        rest = np.setdiff1d(np.arange(b), read)
        assert not got[rest].any()
        for name in want_kv:
            np.testing.assert_array_equal(np.asarray(got_kv[name]),
                                          np.asarray(want_kv[name]))


_MESH_SCRIPT = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, sys.argv[1])
import test_decode_ladder as t
from repro.launch.mesh import make_serving_mesh

mesh = make_serving_mesh(2, 2)
served = {"ladder": t._serve("qwen2-1.5b", False, mesh=mesh),
          "full": t._serve("qwen2-1.5b", True, mesh=mesh)}
t._assert_same_answers(served)
rows = {k: {g[k] for g in served["ladder"]["gauges"]}
        for k in ("decode_rows@S0", "decode_rows@L")}
assert all(v >= {8, 16} for v in rows.values()), rows
print("MESH_LADDER_OK")
"""


def test_ladder_matches_full_width_on_a_serving_mesh():
    """On a (2, 2) mesh of forced host devices (S replicas under
    ``shard_map``, the L tier's K/V heads split over ``model``) the first S
    replica, which admission fills first, and the L tier run narrow and
    full ticks, and the answers equal the full-width lane's."""
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, PYTHONPATH=os.path.join(here, "..", "src"))
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT, here], env=env,
                         capture_output=True, text=True, timeout=540)
    assert "MESH_LADDER_OK" in out.stdout, out.stdout + out.stderr[-3000:]
