"""``chip_smoke.py --reduced``: the chip smoke's phases at the reduced
config on the CPU — the same code path the TPU run takes, so the script
cannot rot between chip calls."""
import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_reduced_rehearsal(capsys, monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, "chip_smoke", smoke)
    spec.loader.exec_module(smoke)
    assert smoke.main(["--reduced"]) == 0
    out = capsys.readouterr().out
    assert "rehearsal passed" in out
    assert '"ok"' not in out                 # no result line off the chip
    for check in ("batch vs alone: 8/8", "spec vs greedy: 8/8"):
        assert check in out                  # f32 on CPU: bit-exact
