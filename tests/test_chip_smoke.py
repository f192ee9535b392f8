"""``chip_smoke.py`` refuses to run anywhere but on a TPU."""

import importlib.util
from pathlib import Path

SMOKE = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def test_smoke_refuses_a_host_without_a_tpu(capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == ""  # no phase ran and no result line was printed
    assert "no TPU found" in err
