"""The traffic generator: deterministic per seed, within its stated ranges,
and the same amount of work in the window for every seed."""
import json

import numpy as np
import pytest

from helpers import DATA
from bench import traffic

# every mix a cell runs, and the sessions mix the generator also serves
MIXES = sorted(traffic.TRAFFIC_DIR.glob("*.json")) + [DATA / "tiny-sessions.json"]
BOUNDS = (0.0, 5.0, 35.0, 45.0)


def load(path):
    return json.loads(path.read_text())


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_requests(path):
    mix = load(path)
    a = traffic.generate(mix, 1000, 2**31 + 17, BOUNDS)
    b = traffic.generate(mix, 1000, 2**31 + 17, BOUNDS)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))
    c = traffic.generate(mix, 1000, 2**31 + 18, BOUNDS)
    assert [x.due_s for x in a] != [x.due_s for x in c]


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_within_stated_ranges(path):
    mix = load(path)
    arr = traffic.generate(mix, 1000, 3, BOUNDS)
    due = np.asarray([a.due_s for a in arr])
    assert np.all(np.diff(due) >= 0) and due[0] >= 0 and due[-1] < BOUNDS[-1]
    out = [a.max_new for a in arr]
    assert min(out) >= mix["output"]["min"] and max(out) <= mix["output"]["max"]
    plen = [len(a.prompt) for a in arr]
    if "sessions" in mix:
        s = mix["sessions"]
        allowed = {s["system_len"] + s["turn_tokens"] * (j + 1) for j in range(s["turns"])}
        assert set(plen) <= allowed
    else:
        assert min(plen) >= mix["prompt"]["min"] and max(plen) <= mix["prompt"]["max"]
    assert all(a.prompt.dtype == np.int32 and a.prompt.max() < 1000 for a in arr)
    # every segment holds the stated rate exactly
    for a, b in zip(BOUNDS[:-1], BOUNDS[1:]):
        n = sum(a <= t < b for t in due)
        assert n == round(mix["rate_per_s"] * (b - a))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_seed_gets_the_same_window(path):
    mix = load(path)

    def window(seed):
        return [x for x in traffic.generate(mix, 1000, seed, BOUNDS)
                if BOUNDS[1] <= x.due_s < BOUNDS[2]]

    a, b = window(5), window(6)
    assert len(a) == len(b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    if "sessions" not in mix:
        assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    gaps = [np.sort(np.diff([x.due_s for x in w])) for w in (a, b)]
    assert np.allclose(np.median(gaps[0]), np.median(gaps[1]), rtol=0.2)


def test_sessions_turns_follow_each_other():
    mix = load(DATA / "tiny-sessions.json")
    s = mix["sessions"]
    arr = traffic.generate(mix, 1000, 9, BOUNDS)
    heads = {a.prompt[: s["system_len"]].tobytes() for a in arr}
    assert len(heads) <= s["system_prompts"]
    # a later turn's prompt extends an earlier request's prompt
    seen = {a.prompt.tobytes() for a in arr}
    later = [a for a in arr if len(a.prompt) > s["system_len"] + s["turn_tokens"]]
    assert later and all(a.prompt[: -s["turn_tokens"]].tobytes() in seen for a in later)


def test_calibration_stream_shares_the_system_prompts():
    mix = load(DATA / "tiny-sessions.json")
    n = mix["sessions"]["system_len"]
    heads = [{a.prompt[:n].tobytes() for a in traffic.generate(mix, 1000, 4, BOUNDS, stream=s)}
             for s in (0, 1)]
    assert heads[0] == heads[1]
