"""The reader of ``tick.decode_live_share``: occupied slots over decode-lane
rows, from the program's ``decode_rows`` gauges where it records them and
from the configuration's slot counts where it does not."""
import json
from types import SimpleNamespace

import pytest

from helpers import ROOT
from bench import run


def _read():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return run.metric_readers(spec)["tick.decode_live_share"]


def _run(gauges):
    return SimpleNamespace(
        config={"serving": {"s_slots": 128, "l_slots": 64}},
        window_ticks=[SimpleNamespace(gauges=g) for g in gauges])


@pytest.mark.parametrize("with_rows, want", [
    (True, (30 + 7 + 40 + 9) / (32 + 16 + 64 + 16)),
    (False, (30 + 7 + 40 + 9) / (2 * (128 + 64)))],
    ids=["decode_rows_gauge", "slot_counts"])
def test_live_share_reader(with_rows, want):
    read, unit = _read()
    ticks = [{"busy_slots@S": 30, "busy_slots@L": 7},
             {"busy_slots@S": 40, "busy_slots@L": 9}]
    if with_rows:
        for g, (s, l) in zip(ticks, ((32, 16), (64, 16))):
            g.update({"decode_rows@S": s, "decode_rows@L": l})
    assert unit == "ratio" and read(_run(ticks)) == pytest.approx(want)


def test_live_share_reader_without_ticks_or_rows():
    read, _ = _read()
    assert read(_run([])) is None
    idle = {"busy_slots@S": 0, "busy_slots@L": 0,
            "decode_rows@S": 0, "decode_rows@L": 0}
    assert read(_run([idle])) is None
