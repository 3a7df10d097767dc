"""Device time of the scheduler tick's lanes, and the scheduler's host
phases, from a JAX profiler trace.

The program runs each lane of each tier of its tick under a named scope
(``S/decode``, ``L/admit``, ...), and ``ContinuousScheduler.lane_of_ops()``
maps the compiled tick's instruction names to those lanes.  The trace's
``XLA Ops`` events carry the same instruction names, so on device 0:

* a lane's time is the union of the intervals of its operations' events
  inside runs of the tick program (an event nested in another, a ``while``
  in its ``conditional``, falls inside its parent's interval);
* ``other`` is the tick program's time that no lane covers: operations XLA
  added without a scope, and gaps inside the program;
* the host phases are the ``sched.<phase>`` annotations and ``sched.tick``
  steps that the program's telemetry writes on the host plane;
* blocking is the device's idle time inside the host phases that do not
  wait for it (all but ``host_fetch``).

As a script on a TPU it serves one cell traced, as ``bench/run.py --trace 1``
does, and prints the harness's line with this reduction of the same trace::

    python3 bench/lanes.py --workload qwen2-1.5b.chat --seed <n> --seconds 51 --trace 1

``python3 bench/lanes.py --fixture <dir>`` records the small trace and lane
map the tests check this module on (``bench/tests/data/tiny-lanes.*``).
"""
from __future__ import annotations

import json
import sys
from bisect import bisect_left, bisect_right
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce  # noqa: E402

# per-layer quantity -> the lanes whose union it is
GROUPS = {
    "tick.l_decode_ms": ("L/decode", "L/verify"),
    "tick.s_decode_ms": ("S/decode",),
    "tick.l_admit_ms": ("L/cow", "L/admit", "L/prefix", "L/chunk"),
    "tick.s_admit_ms": ("S/cow", "S/admit", "S/prefix", "S/chunk"),
}
PHASE = "sched."
TICK = "sched.tick"
WAITS = ("sched.host_fetch",)      # the phase in which the host waits for the device
HOST_PHASES = ("fault_tick", "build_operands", "dispatch", "postprocess")


@dataclass
class Lanes:
    runs: int = 0                   # executions of the tick program on device 0
    module_ns: float = 0.0          # their device time
    lane_iv: dict = field(default_factory=dict)      # lane -> merged intervals
    op_ns: dict = field(default_factory=dict)        # instruction -> ns, tick runs only
    busy: list = field(default_factory=list)         # merged busy intervals, device 0
    host: list = field(default_factory=list)         # (name, start, end) sched.* events

    @property
    def lane_ns(self) -> dict:
        return {lane: float(sum(e - s for s, e in iv)) for lane, iv in self.lane_iv.items()}

    @property
    def other_ns(self) -> float:
        return self.module_ns - sum(self.lane_ns.values())


def instruction(event_name: str) -> str:
    """``%conditional.2 = (...) conditional(...)`` -> ``conditional.2``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def reduce_lanes(pd, lanes: dict, program: str = "tick") -> Lanes:
    """Lane times of the runs of the program whose name holds ``program``,
    with ``lanes`` from ``ContinuousScheduler.lane_of_ops()``."""
    plane = sorted(trace_reduce._device_planes(pd), key=lambda p: p.name)[0]
    runs, ops = [], []
    for line in plane.lines:
        if line.name == trace_reduce.MODULES_LINE:
            runs = [(ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events
                    if program in ev.name]
        elif line.name == trace_reduce.OPS_LINE:
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, instruction(ev.name))
                   for ev in line.events]
    runs.sort()
    starts = [s for s, _ in runs]
    red = Lanes(runs=len(runs), module_ns=sum(e - s for s, e in runs))
    red.busy = trace_reduce.merge([(s, e) for s, e, _ in ops])
    by_lane = {}
    for s, e, name in ops:
        i = bisect_right(starts, s) - 1
        if i < 0 or s >= runs[i][1]:
            continue                     # another program's operation
        red.op_ns[name] = red.op_ns.get(name, 0.0) + (e - s)
        lane = lanes.get(name)
        if lane is not None:
            by_lane.setdefault(lane, []).append((s, e))
    red.lane_iv = {lane: trace_reduce.merge(iv) for lane, iv in by_lane.items()}
    for p in pd.planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                red.host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                             for ev in line.events if ev.name.startswith(PHASE)]
    red.host.sort(key=lambda h: h[1])
    return red


def idle_ns(busy: list, spans) -> float:
    """Nanoseconds of ``spans`` ((start, end) pairs, disjoint) in which no
    interval of ``busy`` (merged, sorted) is running."""
    starts = [s for s, _ in busy]
    ends = [e for _, e in busy]
    idle = 0.0
    for a, b in spans:
        covered = 0.0
        for i in range(bisect_right(ends, a), bisect_left(starts, b)):
            covered += max(0.0, min(b, ends[i]) - max(a, starts[i]))
        idle += max(0.0, (b - a) - covered)
    return idle


def summary(red: Lanes) -> dict:
    """Per tick run: each group's and the lanes' milliseconds, the
    remainder, and the host phases of the traced ticks on the trace's own
    clock."""
    if not red.runs:
        return {}
    per_run = 1e6 * red.runs
    lane_ns = red.lane_ns
    out = {name: sum(lane_ns.get(lane, 0.0) for lane in group) / per_run
           for name, group in GROUPS.items()}
    out["tick.other_ms"] = red.other_ns / per_run
    out["tick.device_ms"] = red.module_ns / per_run
    out["lanes_ms"] = {lane: ns / per_run for lane, ns in sorted(lane_ns.items())}
    ticks = [h for h in red.host if h[0] == TICK]
    phases = [h for h in red.host if h[0] != TICK]
    if ticks:
        n = len(ticks)
        out["sched.ticks"] = n
        out["sched.blocking_ms_per_tick"] = idle_ns(
            red.busy, [(s, e) for name, s, e in phases if name not in WAITS]) / n / 1e6
        out["sched.host_ms_per_tick"] = sum(
            e - s for name, s, e in phases if name[len(PHASE):] in HOST_PHASES) / n / 1e6
        out["sched.phase_ms_per_tick"] = {
            name: sum(e - s for x, s, e in phases if x == name) / n / 1e6
            for name in sorted({x for x, _, _ in phases})}
    return out


def top_ops(red: Lanes, lanes: dict, n: int = 12) -> list:
    """The ``n`` instructions with the most device time in tick runs (nested
    events counted again inside their parents), with their lanes."""
    ops = sorted(red.op_ns.items(), key=lambda kv: -kv[1])[:n]
    return [[name, lanes.get(name, "-"), ns / 1e9] for name, ns in ops]


def l_decode_roofline(run, red: Lanes):
    """Share of the roofline that the L decode lane reaches over the ticks
    of the traced span: the least time (``bench/work.py``) of the work of
    the L tier's ``decode_block`` spans, over the lane's device time, in
    percent."""
    from bench import work

    t0, t1 = run.traced
    k = run.decode_block
    slots = {}
    for tr in run.traces.values():
        adm = run.by_id.get(tr.rid)
        for sp in tr.spans if adm is not None else ():
            if sp.kind == "decode_block" and sp.tier == "L" and t0 <= sp.t0 < t1:
                j = sp.args["j"]
                emitted = max(0, min(k, adm.request.max_new_tokens - 1 - k * j))
                if emitted:
                    slots.setdefault(sp.t0, []).append((adm.bucket + k * j, emitted))
    least = sum(work.least_time(*work.decode_work(run.shapes["L"], s), run.peak)
                for s in slots.values())
    lane_s = red.lane_ns.get("L/decode", 0.0) / 1e9
    return 100.0 * least / lane_s if least > 0 and lane_s > 0 else None


def untraced_phases(run) -> dict:
    """Host milliseconds per tick of each telemetry phase over the window's
    untraced part, where ``sched.host_ms_per_tick`` is read."""
    ticks = run.window_ticks
    out = {}
    for tk in ticks:
        for name, a, b in tk.segments:
            out[name] = out.get(name, 0.0) + 1e3 * (b - a) / len(ticks)
    return out


def escalation_times(run) -> dict:
    """Median and 95th percentile, in ms, over the requests of the window
    that L answered: the ``l_queued`` wait and the L residency
    (``l_verify``)."""
    import numpy as np

    out = {}
    for kind in ("l_queued", "l_verify"):
        d = [s.t1 - s.t0 for rid in run.in_window if rid in run.traces
             for s in run.traces[rid].spans if s.kind == kind]
        if d:
            out[kind] = [1e3 * float(np.percentile(d, q)) for q in (50, 95)] + [len(d)]
    return out


def _varint(b: bytes, i: int):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _enc(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append(v & 0x7F | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _fields(b: bytes):
    """(field number, value, encoded field) of each field of a protobuf
    message in wire format."""
    i = 0
    while i < len(b):
        start = i
        key, i = _varint(b, i)
        if key & 7 == 0:
            v, i = _varint(b, i)
        elif key & 7 == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        else:
            n = 8 if key & 7 == 1 else 4
            v, i = b[i:i + n], i + n
        yield key >> 3, v, b[start:i]


def _sub(field: int, body: bytes) -> bytes:
    return _enc(field << 3 | 2) + _enc(len(body)) + body


def trim_xspace(data: bytes, device: str = "/device:TPU:0") -> bytes:
    """An ``XSpace`` cut to what the reductions read: the ``XLA Modules``
    and ``XLA Ops`` lines of ``device``, the host's ``sched.*`` events, and
    event metadata cut to ids and names (its stats hold HLO source
    locations).  Field numbers are those of ``xplane.proto``."""
    planes = []
    for f, plane, _ in _fields(data):
        pf = list(_fields(plane)) if f == 1 else []
        name = next((v for g, v, _ in pf if g == 2), b"").decode()
        if name != device and name != "/host:CPU":
            continue
        meta = {}
        for g, v, _ in pf:
            if g == 4:                                   # map<int64, XEventMetadata>
                entry = {k: x for k, x, _ in _fields(v)}
                meta[entry[1]] = entry[2]
        names = {k: next((x for h, x, _ in _fields(m) if h == 2), b"") for k, m in meta.items()}
        body = [raw for g, _, raw in pf if g in (1, 2, 5, 6)]
        used = set()
        for g, line, _ in pf:
            lf = list(_fields(line)) if g == 3 else []
            lname = next((v for h, v, _ in lf if h == 2), b"").decode()
            keep = []
            for h, v, raw in lf:
                if h == 4:                               # XEvent
                    mid = next(x for k, x, _ in _fields(v) if k == 1)
                    if (lname in (trace_reduce.MODULES_LINE, trace_reduce.OPS_LINE)
                            if name == device else names[mid].startswith(PHASE.encode())):
                        used.add(mid)
                        keep.append(raw)
            if keep:
                body.append(_sub(3, b"".join([raw for h, _, raw in lf if h != 4] + keep)))
        for k in sorted(used):
            m = b"".join(raw for h, _, raw in _fields(meta[k]) if h in (1, 2, 4))
            body.append(_sub(4, b"\x08" + _enc(k) + _sub(2, m)))
        planes.append(_sub(1, b"".join(body)))
    return b"".join(planes)


def record_fixture(out_dir: str) -> None:
    """A few ticks of the tiny test configuration, every request escalated,
    traced after a warm-up run: ``tiny-lanes.xplane.pb`` (cut by
    :func:`trim_xspace`) and ``tiny-lanes.json`` (the lane map of the
    instructions in the trace, and the telemetry's tick and phase
    counts)."""
    import shutil
    import tempfile

    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from bench import run
    from repro.serving.batcher import Request
    from repro.serving.telemetry import Telemetry

    config = json.loads((ROOT / "bench/tests/data/tiny-dense.json").read_text())
    sv = config["serving"]
    _, _, _, tiers = run.build(config, seed=5)
    sched = run.scheduler(tiers, sv)
    rng = np.random.default_rng(5)

    def queue(first):
        q = run.admission_queue(sv)
        for i in range(2):
            prompt = rng.integers(0, config["vocab_size"], 24 + 8 * i).astype(np.int32)
            q.submit(Request(first + i, prompt, max_new_tokens=8))
        return q

    sched.run(queue(0), theta=2.0)                  # compile and warm up
    tel = Telemetry()
    sched.set_telemetry(tel)
    tmp = tempfile.mkdtemp(prefix="lanes-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    sched.run(queue(10), theta=2.0)
    jax.profiler.stop_trace()
    sched.set_telemetry(None)
    src = next(Path(tmp).rglob("*.xplane.pb"))
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    (out / "tiny-lanes.xplane.pb").write_bytes(trim_xspace(src.read_bytes()))
    lanes = sched.lane_of_ops()
    red = reduce_lanes(ProfileData.from_file(str(out / "tiny-lanes.xplane.pb")), lanes)
    (out / "tiny-lanes.json").write_text(json.dumps({
        "device": jax.devices()[0].device_kind,
        "ticks": len(tel.ticks),
        "segments": sum(len(tk.segments) for tk in tel.ticks),
        "lanes": {k: lanes[k] for k in sorted(red.op_ns) if k in lanes},
    }, indent=0, sort_keys=True))
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(summary(red)))


def serve_cell(argv) -> int:
    """One traced run of a cell through the harness (``bench/run.py``),
    reduced by lane as well: the window's scheduler gives its lane map at
    set-up, and the harness's trace loader is wrapped to reduce the same
    trace here before the harness deletes it."""
    from unittest import mock

    from bench.cache import use_compile_cache
    use_compile_cache()
    import jax
    from jax.profiler import ProfileData

    from bench import run

    args = run.parse(argv)
    if not args.trace:
        raise SystemExit("the lane reduction needs --trace 1")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, _, _ = run.load_cell(spec, args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        run.say(f"needs {cell['chips']} TPU chip(s): no result")
        return 3
    got = {}
    load, readers = trace_reduce.load, run.metric_readers

    def lanes_too(path):
        got["red"] = reduce_lanes(ProfileData.from_file(path), got["lanes"])
        return load(path)

    def keep_run(spec):          # a reader that keeps the run and reports nothing
        return {**readers(spec), "_": (lambda r: got.update(run=r), "")}

    with mock.patch.object(trace_reduce, "load", lanes_too), \
            mock.patch.object(run, "metric_readers", keep_run):
        out = run.run_cell(spec, args, chips=cell["chips"], device_kind=devs[0].device_kind,
                           fault=lambda sched: got.update(lanes=sched.lane_of_ops()))
    red = got["red"]
    lanes = summary(red)
    lanes["l_decode_roofline"] = l_decode_roofline(got["run"], red)
    print(json.dumps({"correct": out["correct"], "metrics": out["metrics"],
                      "device": out["device"], "lanes": lanes,
                      "escalation_ms": escalation_times(got["run"]),
                      "untraced_phase_ms_per_tick": untraced_phases(got["run"]),
                      "top_ops": top_ops(red, got["lanes"]),
                      "breakdown": out["breakdown"]}), flush=True)
    return 0


if __name__ == "__main__":
    if sys.argv[1:2] == ["--fixture"]:
        from bench.cache import use_compile_cache
        use_compile_cache()
        record_fixture(sys.argv[2])
        sys.exit(0)
    sys.exit(serve_cell(sys.argv[1:]))
