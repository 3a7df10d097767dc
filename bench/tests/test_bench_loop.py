"""The due-time queue driving the reduced scheduler open-loop: every request
is answered exactly once, and no answer comes before its due time."""
import json
import time

import pytest

from helpers import DATA
from bench import loop, run, traffic


@pytest.fixture(scope="module")
def served():
    config = json.loads((DATA / "tiny-dense.json").read_text())
    mix = json.loads((DATA / "tiny-chat.json").read_text())
    _, _, _, tiers = run.build(config, seed=3)
    sched = run.scheduler(tiers, config["serving"])
    arr = traffic.generate(mix, config["vocab_size"], 3, (0.0, 1.5))
    q = run.admission_queue(config["serving"])
    for r in run.requests(arr, 0):
        q.submit(r)
    adms = [q.pop() for _ in range(len(arr))]
    t0 = time.monotonic() + 0.1
    for adm, a in zip(adms, arr):
        adm.submit_time = t0 + a.due_s
    sink = loop.Sink()
    sink.sched = sched
    sched.set_telemetry(sink)
    results, late = loop.drive(sched, loop.DueQueue(adms), theta=-1.0)
    return adms, results, sink, late


def test_every_request_answered_once(served):
    adms, results, sink, _ = served
    ids = [a.request.request_id for a in adms]
    assert sorted(results) == sorted(ids)
    assert all(results[i]["status"] == "ok" for i in ids)
    assert all(len(results[a.request.request_id]["tokens"]) == a.request.max_new_tokens
               for a in adms)
    terminals = [sp for tr in sink.traces.values() for sp in tr.spans if sp.kind == "terminal"]
    assert len(terminals) == len(ids)


def test_no_answer_before_its_due_time(served):
    adms, _, sink, late = served
    for a in adms:
        rid = a.request.request_id
        assert sink.done_at[rid] >= a.submit_time
        queued = next(s for s in sink.traces[rid].spans if s.kind == "queued")
        assert queued.t0 == a.submit_time and queued.t1 >= a.submit_time
    assert all(x >= 0 for x in late)


def test_queue_shows_only_due_requests():
    class Adm:
        def __init__(self, t):
            self.submit_time = t

    now = time.monotonic()
    q = loop.DueQueue([Adm(now - 1), Adm(now + 60)])
    assert len(q) == 1 and q.next_due() == pytest.approx(now + 60)
    adm = q.popleft()
    q.appendleft(adm)
    assert q[0] is adm and len(q) == 1
    stopped = loop.DueQueue([Adm(now - 1)], stop=lambda: True)
    assert len(stopped) == 0 and stopped.next_due() is None



def test_calibration_leaves_nothing_cached(monkeypatch):
    """theta's S-only pass over the window's own prompts runs on a scheduler
    of its own, so the window's pools serve none of those prompts from their
    prefix index."""
    from helpers import args, tiny_spec
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", DATA)
    window = []
    run.run_cell(tiny_spec(), args(2**31 + 21, seconds=1.5), chips=1, fault=window.append)
    pools = [window[0].srt.pool, window[0].lrt.pool]
    assert sum(p.stats["tokens_saved"] for p in pools) == 0
