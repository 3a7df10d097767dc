"""chip_smoke.py without a TPU: the one-chip phases run at the reduced
config on CPU (kernels interpreted), and the script still refuses to
report — the ``"ok": true`` line exists only for a run on a TPU."""
import importlib.util
import pathlib

import pytest

from repro.configs.registry import ARCHS


def _load():
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_run_then_refuse_on_cpu(capsys):
    cs = _load()
    with pytest.raises(cs.SmokeFailure, match="not a TPU"):
        cs.smoke(ARCHS["qwen2-1.5b"].reduced(), n_requests=4,
                 prompt_lens=(8, 30), buckets=(16, 32), max_new=4)
    out = capsys.readouterr().out
    for phase in ("PASS serve: stream_compiles == 1",
                  "PASS reference: S paged prefill + 16 decode logits",
                  "PASS kernels: S Pallas vs jnp read"):
        assert phase in out, out
    assert '"ok"' not in out


def test_chip_smoke_main_exits_nonzero_without_tpu(capsys):
    cs = _load()
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
