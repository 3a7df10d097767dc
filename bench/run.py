"""Run one benchmark cell once on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<name>.json``, with its reference under
``bench/reference/<family>.py``) and a traffic mix
(``bench/traffic/<name>.json``).  Set-up makes both tiers' weights on the
device from the seed, calibrates theta with an S-only pass on a scheduler of
its own, builds the window's scheduler (its one tick program comes from the
persistent compile cache after the first run), and serves ``warm_s`` seconds
of the cell's traffic.  The window
then serves the traffic open-loop for ``--seconds``; the requests due in it
are drained, the float32 reference checks a seeded sample of their answers,
and the last stdout line is one JSON result.  With ``--trace 1`` the result
holds the per-layer metrics (``bench/metrics/<name>.py``) instead of the
end-to-end ones, read from a profiler trace of part of the window.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.cache import use_compile_cache  # noqa: E402

if __name__ == "__main__":
    use_compile_cache()

import numpy as np  # noqa: E402

from bench import check, loop, peaks, trace_reduce, traffic, work  # noqa: E402

METRICS_DIR = ROOT / "bench" / "metrics"
DRAIN_S = 60.0            # longest wait for the window's last answers
TRACE_S = 2.0             # the trace covers the window's last seconds
STREAM_TRAFFIC, STREAM_SAMPLE = 0, 2
CALIB_IDS = 10_000_000    # request ids of the calibration pass start here


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(spec: dict, name: str):
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    return cell, config, traffic.load(cell["traffic"])


def tier_configs(config: dict) -> dict:
    """Configuration dicts of the L tier and of the S tier (the L tier's
    keys overridden by ``s_tier``)."""
    l_cfg = {k: v for k, v in config.items() if k != "s_tier"}
    return {"L": l_cfg, "S": {**l_cfg, **config["s_tier"]}}


def metric_readers(spec: dict) -> dict:
    """name -> (read(run), unit) of every per-layer metric; a reader that
    finds nothing to read in a cell returns None, and the metric is left
    out of that cell's line."""
    out = {}
    for m in spec["per_layer"]:
        path = METRICS_DIR / f"{m['name']}.py"
        mod_spec = importlib.util.spec_from_file_location(f"metric_{m['name']}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        out[m["name"]] = (mod.read, m["unit"])
    return out


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        peak = max(peak, (d.memory_stats() or {}).get("peak_bytes_in_use", 0))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(peak)}


class CompileClock:
    """Backend compile seconds and persistent-cache hits while installed."""

    def __init__(self):
        import jax
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self) -> str:
        return (f"{self.seconds:.1f}s compiling, {self.hits} cache hits, "
                f"{self.misses} misses")


def build(config: dict, seed: int):
    """Weights of both tiers (one jitted call, bf16, on the device) and the
    program's tier models over them."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ModelConfig
    from repro.serving.engine import TierModel

    fam = importlib.import_module(f"bench.reference.{config['family']}")
    cfgs = tier_configs(config)
    sv = config["serving"]
    dtype = jnp.dtype(sv["dtype"])
    key = int(np.random.default_rng([seed, 7]).integers(2**31))

    def make(k):
        kl, ks = jax.random.split(jax.random.PRNGKey(k))
        return fam.init(cfgs["L"], kl, dtype), fam.init(cfgs["S"], ks, dtype)

    t = time.monotonic()
    l_w, s_w = jax.block_until_ready(jax.jit(make)(np.uint32(key)))
    say(f"set-up: {time.monotonic() - t:.1f}s weights")
    tiers = {}
    for name, w in (("L", l_w), ("S", s_w)):
        mc = ModelConfig(name=f"{config['name']}-{name}", **fam.program_fields(cfgs[name]))
        tiers[name] = TierModel(mc, w)
    return fam, cfgs, {"L": l_w, "S": s_w}, tiers


def scheduler(tiers: dict, sv: dict):
    """The program's scheduler with the configuration's serving settings;
    its tick program comes from the compile cache."""
    from repro.configs.base import HIConfig
    from repro.serving.scheduler import ContinuousScheduler
    return ContinuousScheduler(
        tiers["S"], tiers["L"], HIConfig(metric=sv["confidence_metric"]),
        max_prompt_len=sv["max_prompt_len"], max_new_tokens=sv["max_new_tokens"],
        num_slots=sv["s_slots"], l_slots=sv["l_slots"], page_size=sv["page_size"],
        admit_width=sv["admit_width"], decode_block=sv["decode_block"],
        prefix_sharing=sv["prefix_sharing"], chunk_prefill=sv["chunk_prefill"],
        kv_dtype=sv["kv_dtype"])


def admission_queue(sv: dict):
    from repro.serving.batcher import AdmissionQueue
    step = sv["bucket_step"]
    buckets = tuple(range(step, sv["max_prompt_len"] + 1, step))
    return AdmissionQueue(buckets=buckets,
                          page_size=sv["page_size"] if sv["prefix_sharing"] else None)


def requests(arrivals, first_id: int):
    from repro.serving.batcher import Request
    return [Request(first_id + i, a.prompt, max_new_tokens=a.max_new)
            for i, a in enumerate(arrivals)]


def calibrate(tiers: dict, config: dict, arrivals) -> float:
    """theta at the configured quantile of the S tier's confidences over an
    S-only pass (theta = -1 escalates nothing) of the prompts of ``arrivals``,
    in due order, under request ids of their own.  Calibrating on the very
    prompts the window serves makes the escalated share the configured one
    for every seed.  The pass runs on a scheduler of its own, dropped with
    its pools, so that the window finds none of these prompts cached."""
    sv = config["serving"]
    q = admission_queue(sv)
    for r in requests(arrivals, CALIB_IDS):
        q.submit(r)
    out = scheduler(tiers, sv).run(q, theta=-1.0)
    gc.collect()
    confs = np.asarray([rec["confidence"] for rec in out.values()])
    return float(np.quantile(confs, sv["theta_quantile"]))


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def run_cell(spec: dict, args, *, chips: int, device_kind: str | None = None,
             fault=None, controls=()) -> dict:
    """Set-up, window, drain, metrics and the correctness check of one run.
    ``fault`` (tests only) is called with the scheduler before the window;
    ``controls`` (``bench/limits.py``) names controls of ``check.CONTROLS``
    whose numbers are read on the same sample."""
    cell, config, mix = load_cell(spec, args.workload)
    sv = config["serving"]
    peak = peaks.peaks(device_kind) if device_kind else None
    t = time.monotonic()
    clock = CompileClock()
    fam, cfgs, weights, tiers = build(config, args.seed)
    say(f"set-up: {t - T_START:.1f}s to start, {time.monotonic() - t:.1f}s weights")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None

    warm = float(mix["warm_s"])
    arrivals = traffic.generate(mix, config["vocab_size"], args.seed,
                                (0.0, warm, warm + args.seconds, warm + args.seconds + DRAIN_S),
                                stream=STREAM_TRAFFIC)
    q = admission_queue(sv)
    reqs = requests(arrivals, 0)
    for r in reqs:
        q.submit(r)
    adms = [q.pop() for _ in range(len(reqs))]

    t = time.monotonic()
    theta = calibrate(tiers, config, [a for a in arrivals if a.due_s < warm + args.seconds])
    say(f"set-up: {time.monotonic() - t:.1f}s calibration")
    t = time.monotonic()
    sched = scheduler(tiers, sv)
    say(f"set-up: {time.monotonic() - t:.1f}s scheduler ({clock})")
    if fault is not None:
        fault(sched)

    t_traffic = time.monotonic() + 0.2
    for adm, a in zip(adms, arrivals):
        adm.submit_time = t_traffic + a.due_s
    w0 = t_traffic + warm
    w1 = w0 + args.seconds
    in_window = {adm.request.request_id for adm in adms if w0 <= adm.submit_time < w1}
    # the trace covers the end of the window: writing it out stalls the
    # loop, which then only delays the drain; host-side per-layer metrics
    # read the untraced part [w0, host_end)
    host_end = w1 - TRACE_S if args.trace else w1
    sink = loop.Sink(trace=(host_end, w1, trace_dir) if args.trace else None)
    sink.sched = sched
    sink.saved.append((float("-inf"), sink.pool_counters()))   # the baseline
    sched.set_telemetry(sink)
    sink.pending = set(in_window)
    done_at = sink.done_at

    def stop() -> bool:
        now = time.monotonic()
        return now >= w1 and (not sink.pending or now > w1 + DRAIN_S)

    queue = loop.DueQueue(adms, stop=stop)
    setup_s = w0 - T_START
    results, late = loop.drive(sched, queue, theta)
    sink.finish()
    device = device_info(chips)
    ticks = list(sink.ticks)
    saved = list(sink.saved)
    sink.sched = None
    sched.set_telemetry(None)

    # ---- records of the window's requests --------------------------------
    by_id = {adm.request.request_id: adm for adm in adms}
    records, answer, local, failed = {}, [], [], 0
    for rid in sorted(in_window):
        rec = results.get(rid)
        adm = by_id[rid]
        if rec is None or rec["status"] != "ok" or rid not in done_at:
            failed += 1
            answer.append(DRAIN_S + args.seconds)
            continue
        t = done_at[rid] - adm.submit_time
        answer.append(t)
        if not rec["served_remote"]:
            local.append(t)
        records[rid] = dict(rec, prompt=adm.request.prompt,
                            max_new=adm.request.max_new_tokens)
    answer_errors = failed + sum(
        len(r["tokens"]) != r["max_new"] or len(r["s_tokens"]) != r["max_new"]
        for r in records.values())
    delivered = sum(len(results[rid]["tokens"]) for rid, t in done_at.items()
                    if w0 <= t < w1 and rid in results)
    n_esc = sum(bool(r["offloaded"]) for r in records.values())
    lat = np.asarray(late) if late else np.zeros(1)
    say(f"{cell['name']} seed {args.seed}: theta {theta:.6g}; {len(in_window)} requests "
        f"due in the window, {failed} failed, {n_esc} escalated; "
        f"{len(ticks)} ticks; wake-up lateness p50 {1e3 * np.median(lat):.3f} ms "
        f"max {1e3 * lat.max():.3f} ms over {len(late)} idle waits")

    run = SimpleNamespace(
        cell=cell, config=config, cfgs=cfgs, window=(w0, host_end),
        records={r: v for r, v in records.items() if by_id[r].submit_time < host_end},
        results=results, by_id=by_id,
        in_window={r for r in in_window if by_id[r].submit_time < host_end},
        ticks=ticks, saved=saved, window_ticks=[t for t in ticks if w0 <= t.t0 < host_end],
        traces=sink.traces, peak=peak, trace=None, traced=sink.traced, clock=sink.clock,
        decode_block=sv["decode_block"], shapes={k: work.tier_shape(v) for k, v in cfgs.items()})
    run.work = tick_work(run)

    out = {"attempted": len(in_window), "failed": failed, "device": device,
           "diag": diagnostics(answer_by_due(by_id, in_window, done_at, w0, w1), sink)}
    if args.trace:
        files = list(Path(trace_dir).rglob("*.xplane.pb"))
        if not files or sink.traced is None or sink.traced[1] is None:
            raise RuntimeError("the profiler trace was not written")
        run.trace = trace_reduce.load(str(files[0]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        t0, t1 = sink.traced
        out["device"].update(busy_s=run.trace.busy_ns / 1e9, window_s=t1 - t0)
        metrics = {}
        for name, (read, unit) in metric_readers(spec).items():
            v = read(run)
            if v is not None:
                metrics[name] = {"value": float(v), "unit": unit}
        out["metrics"] = metrics
        out["breakdown"] = breakdown(run)
    else:
        out["metrics"] = {
            "answer_ms_p50": {"value": 1e3 * percentile(answer, 50), "unit": "ms"},
            "answer_ms_p95": {"value": 1e3 * percentile(answer, 95), "unit": "ms"},
            "local_answer_ms_p95": {"value": 1e3 * percentile(local or [DRAIN_S], 95),
                                    "unit": "ms"},
            "tokens_per_s": {"value": delivered / args.seconds, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }

    # ---- correctness: the reference, once the program's state is freed ----
    del sched, sink, queue, results
    gc.collect()
    rng = np.random.default_rng([args.seed, STREAM_SAMPLE])
    picked = check.sample(records, rng)
    t_max = sv["max_prompt_len"] + sv["max_new_tokens"]
    refs = {k: (check.Reference(fam, cfgs[k], weights[k], t_max, sv["max_new_tokens"]), None)
            for k in ("S", "L")}
    t_ref = time.monotonic()
    numbers = check.compare(refs, picked, records, theta, answer_errors, config["limits"])
    n_tok = sum(len(records[r]["s_tokens"]) + (len(records[r]["tokens"])
                if records[r]["served_remote"] else 0) for r in picked)
    say(f"reference over {len(picked)} requests, {n_tok} served tokens: "
        f"{time.monotonic() - t_ref:.1f}s")
    out["correct"] = check.passed(numbers)
    out["compared"] = numbers
    out["controls"] = {}
    for name in controls:
        ctl = {k: (ref, check.Reference(fam, cfgs[k], weights[k], t_max, sv["max_new_tokens"],
                                        cast=check.CONTROLS[name]))
               for k, (ref, _) in refs.items()}
        out["controls"][name] = check.compare(ctl, picked, records, theta, 0,
                                              config["limits"], control=True)
    return out


def answer_by_due(by_id, in_window, done_at, w0, w1):
    """(due offset in the window, answer seconds) of the answered requests."""
    return sorted((by_id[r].submit_time - w0, done_at[r] - by_id[r].submit_time)
                  for r in in_window if r in done_at)


def diagnostics(pairs, sink) -> dict:
    """Backlog signs for the load sweep: admission wait, and the median
    answer time of the window's last quarter over its first quarter."""
    waits = [s.t1 - s.t0 for tr in sink.traces.values() for s in tr.spans
             if s.kind == "queued" and tr.rid < CALIB_IDS]
    q = max(1, len(pairs) // 4)
    first = np.median([a for _, a in pairs[:q]]) if pairs else float("nan")
    last = np.median([a for _, a in pairs[-q:]]) if pairs else float("nan")
    return {"admit_wait_p95_ms": 1e3 * percentile(waits, 95) if waits else None,
            "trend": float(last / first)}


def tick_work(run) -> list:
    """(t0, flops, bytes) of every tick's useful work (see ``work``)."""
    spans = {}
    for tr in run.traces.values():
        for sp in tr.spans:
            if sp.kind in ("admitted", "decode_block"):
                spans.setdefault(sp.t0, []).append((tr.rid, sp))
    saved = dict(run.saved)
    prev = run.saved[0][1]
    out = []
    for tk in run.ticks:
        cur = saved.get(tk.t0)
        flops = nbytes = 0.0
        for tier in ("S", "L"):
            shape = run.shapes[tier]
            prompts, slots = [], []
            for rid, sp in spans.get(tk.t0, ()):
                if sp.tier != tier or rid not in run.by_id:
                    continue
                adm = run.by_id[rid]
                n, m = adm.bucket, adm.request.max_new_tokens
                if sp.kind == "admitted":
                    prompts.append(n)
                else:
                    j, k = sp.args["j"], run.decode_block
                    emitted = max(0, min(k, m - 1 - k * j))
                    if emitted:
                        slots.append((n + k * j, emitted))
            d_saved = d_full = 0
            if cur is not None:
                d_saved = cur[tier][0] - prev[tier][0]
                d_full = cur[tier][1] - prev[tier][1]
            f, b = work.prefill_work(shape, prompts, d_saved, d_full)
            f2, b2 = work.decode_work(shape, slots)
            flops += f + f2
            nbytes += b + b2
        if cur is not None:
            prev = cur
        out.append((tk, flops, nbytes))
    return out


def breakdown(run) -> dict:
    red = run.trace
    ops = sorted(red.op_ns.items(), key=lambda kv: -kv[1])[:10]
    t0, t1 = run.traced
    lo = red.clock_ns + (t0 - run.clock) * 1e9
    hi = red.clock_ns + (t1 - run.clock) * 1e9
    gaps = trace_reduce.idle_gaps(red, (lo, hi), run.ticks, run.clock)
    return {"device_ops": [[name.split(" = ")[0], ns / 1e9 / red.devices] for name, ns in ops],
            "idle_gaps": gaps}


def main(argv=None) -> int:
    args = parse(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, _, _ = load_cell(spec, args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        say(f"needs {cell['chips']} TPU chip(s); JAX reports {len(devs)} "
            f"{devs[0].platform} device(s): no result")
        return 3
    out = run_cell(spec, args, chips=cell["chips"], device_kind=devs[0].device_kind)
    say(f"backlog: {json.dumps(out['diag'])}")
    for name, n in out["compared"].items():
        say(f"compared {name} {n['value']!r} limit {n['limit']!r}")
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"], "device": out["device"]}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["compared"] = out["compared"]
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
