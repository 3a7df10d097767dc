"""Bring-up smoke run of the HI serving path on a TPU.

One process; every weight and prompt is generated from ``--seed``; nothing is
written outside the checkout (the compile cache goes where
``repro.launch.compile_cache`` says).

  python chip_smoke.py               # one chip: qwen2-1.5b at full width
  python chip_smoke.py --four-chips  # (2, 2) serving mesh vs one device

One-chip phases, in order: device check, build (full-width L tier and its
``s_variant(4)`` S tier, bf16), serve (``serve_stream`` with default
features, theta calibrated so that about 3/8 of the requests escalate),
reference (paged prefill and teacher-forced decode logits of both tiers vs
a float32 ``model_zoo.forward``), kernels (the same traffic with
``use_kernel=True``: the tick must contain compiled Pallas kernels, and the
Pallas read must meet the reference bound and agree with the jnp read).
``--four-chips`` runs only the mesh path and its comparison.

The last stdout line is ``{"ok": true, "device": {...}}``, printed only when
every phase passed on a TPU; a failed check raises and exits non-zero
without it.  Times printed along the way are smoke facts, not metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import HIConfig  # noqa: E402
from repro.configs.registry import ARCHS  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model_zoo  # noqa: E402
from repro.serving.batcher import Request  # noqa: E402
from repro.serving.engine import HIEngine, build_engine  # noqa: E402

ARCH = "qwen2-1.5b"
N_REQUESTS = 16
PROMPT_LENS = (64, 512)          # inclusive range of prompt lengths
BUCKETS = (128, 512)
MAX_NEW = 32
NUM_SLOTS = 8
PAGE = 16
ESC_QUANTILE = 0.375             # share of requests whose S confidence is
#                                  below theta, i.e. that escalate to L
N_REF = 4                        # prompts per tier in the reference phase
# Tolerances, fixed before the first chip run.  Logits are compared per row
# by relative L2 error, ||x - ref|| / ||ref||, over the whole vocabulary;
# every bf16 path (jnp read, Pallas read, model-sharded L) is held to the
# float32 reference or to the one-device path by the same bound.
REF_REL_TOL = 5e-2
# Greedy agreement is teacher-forced: both paths see the same tokens at every
# step, so each of the N_REF * TF_STEPS decode positions is one independent
# argmax comparison.  A wrong page or head mapping agrees almost never; two
# correct bf16 reads differ only at near-ties of random-init logits.
TF_STEPS = 16                    # teacher-forced decode steps per prompt
TF_AGREE_MIN = 0.8
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(AssertionError):
    """A smoke check failed; the script exits non-zero."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    say(f"PASS {what}")


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu() -> dict:
    """Device check: a TPU backend, or exit non-zero (never a CPU fallback)."""
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"[chip_smoke] no TPU: JAX reports {dev}; refusing to run",
              file=sys.stderr)
        raise SystemExit(1)
    say(f"device {dev}")
    return dev


def result_line() -> str:
    """The final stdout line — only ever produced on a TPU."""
    dev = device_info()
    if dev["platform"] != "tpu":
        raise SmokeFailure(f"phases ran on {dev['platform']!r}, not a TPU: "
                           "no result")
    return json.dumps({"ok": True, "device": dev})


class CompileClock:
    """Sums the backend compile seconds JAX reports while installed."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.seconds += duration


def tree_bytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


def make_requests(cfg, seed: int, n: int, lens, max_new: int):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(lens[0], lens[1] + 1, n)
    return [Request(i, rng.integers(0, cfg.vocab_size, int(s))
                    .astype(np.int32), max_new_tokens=max_new)
            for i, s in enumerate(sizes)]


def serve(eng: HIEngine, reqs, theta: float, buckets, mesh=None):
    """One ``serve_stream`` call at ``theta`` with default features."""
    eng.hi = dataclasses.replace(eng.hi, theta=theta)
    t0 = time.perf_counter()
    out = eng.serve_stream(reqs, buckets=buckets, num_slots=NUM_SLOTS,
                           page_size=PAGE, mesh=mesh)
    return out, time.perf_counter() - t0


def check_records(out, reqs, what: str) -> int:
    """Exactly one record per request, all ``ok``; returns the L-served
    count."""
    check(sorted(out) == [r.request_id for r in reqs],
          f"{what}: one record per request")
    statuses = [out[r.request_id]["status"] for r in reqs]
    check(all(s == "ok" for s in statuses),
          f"{what}: all {len(statuses)} statuses ok")
    n_l = sum(bool(out[r.request_id]["served_remote"]) for r in reqs)
    check(n_l >= 1, f"{what}: {n_l} request(s) served by L")
    return n_l


def calibrate_theta(eng: HIEngine, reqs, buckets) -> float:
    """Place theta at the ESC_QUANTILE of the S tier's request confidences
    (theta = -1 escalates nothing, so this pass is S-only)."""
    out, _ = serve(eng, reqs, -1.0, buckets)
    confs = np.asarray([out[r.request_id]["confidence"] for r in reqs])
    theta = float(np.quantile(confs, ESC_QUANTILE))
    say(f"theta {theta:.6g} at quantile {ESC_QUANTILE} of S confidences")
    return theta


def token_agreement(a, b, reqs) -> float:
    """Position-wise share of equal S-tier tokens between two free-running
    serves (reported, not checked: one near-tie flip changes every later
    token of the request, so it measures compounding, not correctness)."""
    same = total = 0
    for r in reqs:
        x, y = a[r.request_id]["s_tokens"], b[r.request_id]["s_tokens"]
        n = min(len(x), len(y))
        same += int(np.sum(x[:n] == y[:n]))
        total += max(len(x), len(y))
    return same / max(total, 1)


def rel_l2(x, ref) -> np.ndarray:
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return (np.linalg.norm(x - ref, axis=-1)
            / np.maximum(np.linalg.norm(ref, axis=-1), 1e-30))


def teacher_forced_inputs(cfg, seed: int, s_max: int, lens):
    """N_REF prompts (right-padded to ``s_max``) and TF_STEPS fed tokens."""
    rng = np.random.default_rng(seed + 1)
    hi = max(lens[0], min(lens[1], s_max - TF_STEPS))
    lengths = rng.integers(min(lens[0], hi), hi + 1, N_REF).astype(np.int32)
    tokens = np.zeros((N_REF, s_max), np.int32)
    for i, n in enumerate(lengths):
        tokens[i, :n] = rng.integers(0, cfg.vocab_size, n)
    teacher = rng.integers(0, cfg.vocab_size,
                           (N_REF, TF_STEPS)).astype(np.int32)
    return tokens, lengths, teacher


def paged_logits(cfg, params, inputs, *, use_kernel: bool = False,
                 shardings=None) -> np.ndarray:
    """The paged path under teacher forcing: ``prefill_paged`` of the
    prompts, then TF_STEPS ``decode_step_paged`` steps feeding the teacher
    tokens.  Returns fp32 logits (N_REF, 1 + TF_STEPS, V) for positions
    len-1 .. len-1+TF_STEPS.  ``shardings`` = (params, cache) shardings
    place both on a mesh."""
    tokens, lengths, teacher = map(jnp.asarray, inputs)
    n = tokens.shape[0]
    pps = -(-tokens.shape[1] // PAGE)                 # pages per slot
    block = 1 + jnp.arange(n * pps, dtype=jnp.int32).reshape(n, pps)
    cache = model_zoo.init_paged_cache(cfg, n, 1 + n * pps, PAGE)

    def run(p, c):
        pre, c = model_zoo.prefill_paged(p, cfg, tokens, lengths,
                                         jnp.arange(n), block, c)

        def step(c, xs):
            tok, k = xs
            logits, c = model_zoo.decode_step_paged(
                p, cfg, tok[:, None], lengths + k, block, c,
                use_kernel=use_kernel)
            return c, logits

        _, dec = jax.lax.scan(step, c, (teacher.T, jnp.arange(TF_STEPS)))
        return jnp.concatenate([pre[:, None], dec.transpose(1, 0, 2)], 1)

    if shardings is None:
        return np.asarray(jax.jit(run)(params, cache))
    p_sh, c_sh = shardings
    return np.asarray(jax.jit(run, in_shardings=shardings)(
        jax.device_put(params, p_sh), jax.device_put(cache, c_sh)))


def reference_logits(cfg, params, inputs) -> np.ndarray:
    """The same positions from a float32 ``model_zoo.forward`` over prompt +
    teacher tokens, params upcast, at highest matmul precision."""
    tokens, lengths, teacher = inputs
    seq = tokens.copy()
    rows = np.arange(len(lengths))[:, None]
    cols = lengths[:, None] + np.arange(TF_STEPS)[None, :]
    seq[rows, cols] = teacher
    at = np.concatenate([lengths[:, None] - 1, cols], 1)

    def ref(p, t):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        return model_zoo.forward(p32, cfg, {"tokens": t})[0][rows, at]

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(ref)(params, seq))


def check_close(x, ref, what: str) -> None:
    """Every position within REF_REL_TOL; top-1 agreement reported."""
    err = rel_l2(x, ref)
    top1 = float(np.mean(x.argmax(-1) == ref.argmax(-1)))
    check(bool(np.all(err <= REF_REL_TOL)),
          f"{what}: rel L2 max {err.max():.4f} mean {err.mean():.4f} <= "
          f"{REF_REL_TOL} (top-1 agree {top1:.3f})")


def check_top1(x, y, what: str) -> None:
    agree = float(np.mean(x.argmax(-1) == y.argmax(-1)))
    check(agree >= TF_AGREE_MIN,
          f"{what}: teacher-forced greedy agreement {agree:.3f} >= "
          f"{TF_AGREE_MIN}")


def smoke(cfg, *, seed: int = 0, n_requests: int = N_REQUESTS,
          prompt_lens=PROMPT_LENS, buckets=BUCKETS,
          max_new: int = MAX_NEW) -> str:
    """All one-chip phases after the device check; returns the result line
    (which refuses unless the phases ran on a TPU)."""
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        return _smoke_phases(cfg, clock, seed, n_requests, prompt_lens,
                             buckets, max_new)
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)


def _smoke_phases(cfg, clock, seed, n_requests, prompt_lens, buckets,
                  max_new) -> str:
    # -- build --------------------------------------------------------------
    hi = HIConfig()
    t0 = time.perf_counter()
    eng = build_engine(cfg, hi, rng=jax.random.PRNGKey(seed),
                       dtype=jnp.bfloat16, max_new_tokens=max_new)
    jax.block_until_ready((eng.s.params, eng.l.params))
    tiers = (("L", eng.l), ("S", eng.s))
    for name, tier in tiers:
        say(f"build: {name} tier {tier.cfg.name} {tier.cfg.num_layers}L "
            f"d_model {tier.cfg.d_model} vocab {tier.cfg.vocab_size}: "
            f"{tree_bytes(tier.params) / 2**30:.3f} GiB of "
            f"{jax.tree.leaves(tier.params)[0].dtype} params")
    say(f"build: {time.perf_counter() - t0:.1f}s, peak device memory "
        f"{peak_bytes()}")

    # -- serve ----------------------------------------------------------------
    reqs = make_requests(cfg, seed, n_requests, prompt_lens, max_new)
    compile0 = clock.seconds
    theta = calibrate_theta(eng, reqs, buckets)
    out, wall = serve(eng, reqs, theta, buckets)
    n_l = check_records(out, reqs, "serve")
    check(eng.stats["stream_compiles"] == 1, "serve: stream_compiles == 1")
    say(f"serve (smoke, not a metric): {len(reqs)} requests, {n_l} on L, "
        f"{int(eng.stats['stream_ticks'])} ticks over both calls; tick "
        f"compile {clock.seconds - compile0:.1f}s; second call wall "
        f"{wall:.2f}s; peak device memory {peak_bytes()}")

    # -- reference ------------------------------------------------------------
    inputs, refs, jnp_reads = {}, {}, {}
    for name, tier in tiers:
        inputs[name] = tf = teacher_forced_inputs(tier.cfg, seed,
                                                  max(buckets), prompt_lens)
        refs[name] = reference_logits(tier.cfg, tier.params, tf)
        jnp_reads[name] = paged_logits(tier.cfg, tier.params, tf)
        check_close(jnp_reads[name], refs[name],
                    f"reference: {name} paged prefill + {TF_STEPS} decode "
                    "logits vs float32 forward")

    # -- kernels --------------------------------------------------------------
    keng = HIEngine(eng.s, eng.l, eng.hi, cache_len=eng.cache_len,
                    max_new_tokens=max_new, use_kernel=True)
    compile0 = clock.seconds
    kout, kwall = serve(keng, reqs, theta, buckets)
    check_records(kout, reqs, "kernels")
    check(keng.stats["stream_compiles"] == 1, "kernels: stream_compiles == 1")
    hlo = keng._stream[1]._exec.as_text()
    compiled = "tpu_custom_call" in hlo
    say(f"kernels: tick compile {clock.seconds - compile0:.1f}s, wall "
        f"{kwall:.2f}s (smoke); tpu_custom_call in tick HLO: {compiled}")
    if device_info()["platform"] == "tpu":
        check(compiled, "kernels: compiled Pallas kernels in the tick HLO")
    for name, tier in tiers:
        k = paged_logits(tier.cfg, tier.params, inputs[name], use_kernel=True)
        check_close(k, refs[name], f"kernels: {name} Pallas paged logits vs "
                    "float32 forward")
        check_top1(k[:, 1:], jnp_reads[name][:, 1:],
                   f"kernels: {name} Pallas vs jnp read")
    say(f"kernels: free-running served S-tier token agreement with the jnp "
        f"path {token_agreement(kout, out, reqs):.3f} (reported, not "
        f"checked); backend compile total {clock.seconds:.1f}s; peak device "
        f"memory {peak_bytes()}")
    return result_line()


def four_chips(cfg, *, seed: int = 0) -> str:
    """``serve_stream`` on a (2, 2) serving mesh (two data-parallel S
    replicas, L tier sharded over ``model``) against ``mesh=None`` on the
    same requests."""
    from repro.launch.mesh import make_serving_mesh
    from repro.sharding import specs as sh

    check(len(jax.devices()) >= 4, f"four devices ({len(jax.devices())})")
    mesh = make_serving_mesh(2, 2)
    eng = build_engine(cfg, HIConfig(), rng=jax.random.PRNGKey(seed),
                       dtype=jnp.bfloat16, max_new_tokens=MAX_NEW)
    meng = HIEngine(eng.s, eng.l, eng.hi, cache_len=eng.cache_len,
                    max_new_tokens=MAX_NEW)
    reqs = make_requests(cfg, seed, N_REQUESTS, PROMPT_LENS, MAX_NEW)
    theta = calibrate_theta(eng, reqs, BUCKETS)
    single, wall1 = serve(eng, reqs, theta, BUCKETS)
    meshed, wall4 = serve(meng, reqs, theta, BUCKETS, mesh=mesh)
    check_records(single, reqs, "single device")
    check_records(meshed, reqs, "mesh (2, 2)")
    check(meng.stats["stream_compiles"] == 1, "mesh: stream_compiles == 1")
    say(f"wall (smoke, not a metric): single {wall1:.2f}s, mesh {wall4:.2f}s;"
        f" free-running S-tier token agreement "
        f"{token_agreement(meshed, single, reqs):.3f} (reported)")

    # shards really sit on four distinct devices
    sched = meng._stream[1]
    for what, tree in (("L params", sched._l_params),
                       ("L KV pool", sched.lrt.pool.buffers),
                       ("S replica pools", sched._s_pool)):
        devs = set()
        for leaf in jax.tree.leaves(tree):
            devs |= set(leaf.sharding.device_set)
        check(len(devs) == 4, f"mesh: {what} span {len(devs)} devices")
    kp = sched.lrt.pool.buffers["kp"]            # (L, P, page, K*Dh)
    dh = sched.l.cfg.resolved_head_dim
    local_k = kp.addressable_shards[0].data.shape[3] // dh
    check(local_k * 2 == kp.shape[3] // dh,
          f"mesh: L pages split over model ({local_k} of {kp.shape[3] // dh} "
          "kv heads per shard)")

    # the model-sharded L tier computes what one device computes
    l_cfg, l_params = eng.l.cfg, eng.l.params
    tf = teacher_forced_inputs(l_cfg, seed, max(BUCKETS), PROMPT_LENS)
    one = paged_logits(l_cfg, l_params, tf)
    cache0 = jax.eval_shape(lambda: model_zoo.init_paged_cache(
        l_cfg, N_REF, 1 + N_REF * (max(BUCKETS) // PAGE), PAGE))
    four = paged_logits(l_cfg, l_params, tf, shardings=(
        sh.param_shardings(l_params, mesh, fsdp=False),
        sh.paged_cache_shardings(l_cfg, mesh, cache0)))
    check_close(four, one, "mesh: model-sharded L paged logits vs one device")
    check_top1(four[:, 1:], one[:, 1:], "mesh: model-sharded L vs one device")
    return result_line()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (2, 2) serving-mesh path and its "
                         "single-device comparison (needs 4 chips)")
    args = ap.parse_args(argv)
    require_tpu()
    say(f"compile cache: {enable_compile_cache()}")
    cfg = ARCHS[ARCH]
    line = (four_chips(cfg, seed=args.seed) if args.four_chips
            else smoke(cfg, seed=args.seed))
    print(line, flush=True)


if __name__ == "__main__":
    main()
