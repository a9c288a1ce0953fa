"""The serving tutorial stays runnable: its ``main`` on its tiny preset
(the int8 stack through ``ServingEngine``, the forward comparison, the
LL carry on two devices)."""

import pathlib
import runpy
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_serving_engine_tutorial_runs(capsys, monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT / "tutorials"))
    monkeypatch.setattr(sys, "argv", ["13-serving-engine.py"])
    ns = runpy.run_path(str(ROOT / "tutorials" / "13-serving-engine.py"),
                        run_name="tutorial_13")
    ns["main"]()
    out = capsys.readouterr().out
    assert "tutorial 13 OK" in out and "barrier-free steps" in out
