"""The thirteen set-up metrics of PR 39 (``readers/startup.py``): every
entry has its file, a program without the log reads nothing, the tiny
root's engine reads numbers whose sums hold.

    python -m pytest benchmark/tests/test_startup_metrics.py -q \
        -p no:cacheprovider
"""

import json
import pathlib
import sys
import tempfile

import pytest

from benchmark.harness import metrics, program
from benchmark.harness.spec import Spec
from benchmark.readers import startup

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
BENCH = REPO / "benchmark"
DATA_ROOT = HERE / "data" / "root"

NAMES = [
    "setup_model_s", "setup_engine_s", "setup_state_s",
    "setup_workspaces_s", "setup_step_programs", "setup_step_programs_s",
    "setup_trace_s", "setup_lower_s", "setup_compile_s",
    "setup_step_program_s_max", "setup_rebuilt_programs",
    "setup_cache_misses", "setup_gc_s"]


def _definition(name: str) -> dict:
    return json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())


def test_every_new_entry_has_its_file_and_moves_setup_s():
    doc = json.loads((REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in doc["per_layer"]}
    setup = [m for m in doc["per_layer"] if m["moves"] == "setup_s"]
    assert [m["name"] for m in setup] == NAMES
    # appended: nothing that was there moved
    assert doc["per_layer"][-len(NAMES):] == setup
    for name in NAMES:
        m = entries[name]
        assert m["layer"] == "set-up" and m["better"] == "lower"
        assert "workloads" not in m          # all five cells report it
        assert m["source"] in ("program_span", "program_counter")
        assert m["unit"] == ("programs" if name.endswith("programs")
                             or name == "setup_cache_misses" else "s")
        d = _definition(name)
        module, _, fn = d["reader"].partition(":")
        assert module == "benchmark.readers.startup"
        assert callable(getattr(startup, fn)) and d["reads"]
    # every cell finds them by name
    cell = Spec(REPO).cell(doc["workloads"][0]["name"])
    assert set(NAMES) <= set(cell.layer_metrics)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_log_reads_nothing(monkeypatch, name):
    """The parent of PR 39 has no ``tracing`` module: an ImportError in
    the reader, ``None`` out of it, the metric absent from the line."""
    monkeypatch.setitem(sys.modules, "triton_distributed_tpu.tracing", None)
    assert metrics.read_layer_metric({}, _definition(name)) is None


def test_no_engine_built_reads_nothing(monkeypatch):
    from triton_distributed_tpu import tracing

    monkeypatch.setattr(tracing, "_spans", [])
    monkeypatch.setattr(tracing, "_programs", [])
    assert {metrics.read_layer_metric({}, _definition(n))
            for n in NAMES} == {None}


def test_the_tiny_roots_engine_reads_numbers_whose_sums_hold():
    from triton_distributed_tpu import tracing

    cell = Spec(DATA_ROOT).cell("tiny.chat")
    program.hermetic_tuning(tempfile.mkdtemp())
    program.enable_compile_cache()
    mark = {k: len(v) for k, v in tracing.startup_log().items()}
    eng = program.build(cell.config, cell.mix, 1, seed=5).engine
    warm = program.warm_up(eng, cell.config["as_run"]["vocab"])
    assert eng.stats.programs_built == len(warm["rungs"])
    read = {n: metrics.read_layer_metric({}, _definition(n))
            for n in NAMES}
    assert all(v is not None and v >= 0 for v in read.values()), read
    assert read["setup_step_programs"] >= len(warm["rungs"])
    assert (read["setup_trace_s"] + read["setup_lower_s"]
            + read["setup_compile_s"]) <= read["setup_step_programs_s"]
    assert read["setup_state_s"] + read["setup_workspaces_s"] \
        <= read["setup_engine_s"]
    assert 0 < read["setup_step_program_s_max"] \
        <= read["setup_step_programs_s"]
    # this engine's own share of the log: one span a rung, each with
    # its key; every program under it knows whether the cache held it
    log = tracing.startup_log()
    spans = [s for s in log["spans"][mark["spans"]:]
             if s["name"] == "setup.program"]
    assert [s["block_q"] for s in spans] == warm["rungs"]
    mine = [p for p in log["programs"][mark["programs"]:]
            if p["span"] == "setup.program"]
    assert {p["cache"] for p in mine} <= {"hit", "miss"}
    assert sum(p["fun_name"] == "jit(step)" for p in mine) == len(spans)
    # over a window both counters must stand still
    before = program.stats_snapshot(eng)["numbers"]
    program.warm_up(eng, cell.config["as_run"]["vocab"])
    after = program.stats_snapshot(eng)["numbers"]
    assert after["programs_built"] == before["programs_built"]
    assert after["program_build_s"] == before["program_build_s"]
