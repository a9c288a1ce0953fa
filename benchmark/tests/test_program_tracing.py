"""The names the metric files read and the names the program writes
must not drift apart: host spans, device scopes, ``EngineStats`` fields.
Plus the readers and the ``needs`` function PR 26 adds, on hand-made
records.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import dataclasses
import json
import pathlib
import re

import pytest

from benchmark.harness import metrics, program, trace
from benchmark.harness.spec import Spec
from benchmark.kernel_needs import grouped_matmul
from benchmark.readers import program_trace

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
BENCH = REPO / "benchmark"
DATA_ROOT = HERE / "data" / "root"
DEV = "/device:TPU:0"


def _metric_files() -> dict:
    return {p.name[:-len(".json")]: json.loads(p.read_text())
            for p in sorted((BENCH / "layer_metrics").glob("*.json"))}


# ---------------------------------------------------------- no name drifts

def test_every_engine_span_file_is_a_span_the_program_opens():
    from triton_distributed_tpu.serving.engine import PHASES

    spans = trace.known_spans(BENCH)
    files = {name for name in spans if name.startswith("engine.")}
    assert files == {f"engine.{phase}" for phase in PHASES}
    # the four the driver opens are still there, beside them
    assert {"loadgen", "idle_wait", "engine_step", "stamp"} <= set(spans)
    for name in files:
        assert spans[name]["layer"] and spans[name]["what"]
    # and the metrics that read a span by name read one of these
    for name, definition in _metric_files().items():
        span = definition.get("args", {}).get("span")
        if span is not None and span.startswith("engine."):
            assert span in files, name


def test_every_stats_field_a_metric_names_is_a_field_of_engine_stats():
    from triton_distributed_tpu.serving.engine import EngineStats

    fields = {f.name: f for f in dataclasses.fields(EngineStats)}
    named = {}
    for name, definition in _metric_files().items():
        for value in definition.get("args", {}).values():
            if isinstance(value, str) and value.startswith("stats."):
                named[value[len("stats."):]] = name
    assert {"queue_wait_s", "admissions", "first_token_s", "first_tokens",
            "assemble_times", "upload_times", "fetch_times",
            "advance_times"} <= set(named)
    for field, metric in named.items():
        assert field in fields, f"{metric} reads stats.{field}"
    # a series is a list, a counter a number: as stats_snapshot sorts them
    snap = program.stats_snapshot(type("E", (), {"stats": EngineStats()})())
    for field, metric in named.items():
        where = "lists" if field.endswith("_times") else "numbers"
        assert field in snap[where], (metric, field)


@pytest.fixture(scope="module")
def tiny_step_components():
    """Every component of every operation name in the step program of
    the tiny root's engine (its lowered text with debug info)."""
    import tempfile

    import numpy as np

    from triton_distributed_tpu.kernels.ragged_paged_attention import (
        auto_block_q,
    )

    cell = Spec(DATA_ROOT).cell("tiny.chat")
    program.hermetic_tuning(tempfile.mkdtemp())
    eng = program.build(cell.config, cell.mix, 1, seed=5).engine
    eng.submit(program.new_request(
        0, np.arange(9, dtype=np.int32), 2, eng.step_count))
    eng._admit()
    *arrays, batched, _ = eng._assemble()
    assert batched
    text = eng._step_jit().lower(*eng._step_args(
        tuple(arrays), auto_block_q(1, eng._g))).as_text(debug_info=True)
    parts = set()
    for path in re.findall(r'loc\("([^"]+)"', text):
        parts.update(path.split("/"))
    return parts


def test_every_scope_a_metric_names_is_in_the_lowered_step(
        tiny_step_components):
    files = _metric_files()
    scoped = {name: d["args"]["scope"] for name, d in files.items()
              if "scope" in d.get("args", {})}
    assert len(scoped) >= 8
    for name, scope in scoped.items():
        assert scope in tiny_step_components, f"{name} reads {scope}"
        assert name == f"{scope}_ms_per_step"
    listed = files["unscoped_device_share"]["args"]["scopes"]
    assert len(listed) == len(set(listed)) == 10
    assert set(listed) <= tiny_step_components
    assert set(scoped.values()) <= set(listed)


# -------------------------------------- the readers, on hand-made records

def _record(ops, host, steps, spans=None):
    return {"series": {"traced_steps": list(range(steps))},
            "counters": {}, "chips": 1,
            "trace": trace.TraceSummary({DEV: ops}, host, spans=spans)}


def _two_steps():
    """Two engine steps, 0..100 and 100..200. The device runs 0..40,
    then nothing until 130: one gap of 90 that runs through step 0's
    fetch (30..60) and advance (60..90), the harness between the steps
    (90..100), and step 1's admit (100..105) and upload (105..125); a
    second gap 170..180 lies wholly in step 1's fetch (150..185)."""
    ops = [("k", 0, 40, "jit(step)/attn/dot"),
           ("k", 130, 40, "jit(step)/attn/dot"),
           ("k", 180, 20, "jit(step)/lm_head/dot")]
    host = [("engine_step", "py", 0, 95), ("engine_step", "py", 100, 200),
            ("engine.dispatch", "py", 20, 30),
            ("engine.fetch", "py", 30, 60),
            ("engine.advance", "py", 60, 90),
            ("engine.admit", "py", 100, 105),
            ("engine.upload", "py", 105, 125),
            ("engine.dispatch", "py", 125, 150),
            ("engine.fetch", "py", 150, 185),
            ("engine.advance", "py", 185, 198)]
    return ops, host


def test_a_gap_is_split_among_the_spans_by_overlap():
    ops, host = _two_steps()
    names = sorted({name for name, *_ in host})
    rec = _record(ops, host, 2, spans=names)
    per_step = lambda ns: pytest.approx(ns / 1e6 / 2)
    read = lambda span: program_trace.idle_overlap_ms_per_step(rec, span)
    assert read("engine.fetch") == per_step(20 + 10)
    assert read("engine.advance") == per_step(30)
    assert read("engine.admit") == per_step(5)
    assert read("engine.upload") == per_step(20)
    # dispatch 20..30 lies under a running kernel; 125..150 covers the
    # gap's last 5
    assert read("engine.dispatch") == per_step(5)
    # the parts sum to the gaps less what no phase covers (90..100, the
    # harness between two steps)
    phases = [n for n in names if n.startswith("engine.")]
    assert sum(read(n) for n in phases) == per_step(100 - 10)
    assert read("engine_step") == per_step(100 - 5)
    # by majority, the first gap goes whole to no phase at all
    assert rec["trace"].idle_by_path() == {
        "engine_step": 90, "engine_step/engine.fetch": 10}
    # through a metric file, as a run reads it
    definition = json.loads(
        (BENCH / "layer_metrics" / "fetch_idle_ms_per_step.json")
        .read_text())
    assert metrics.read_layer_metric(rec, definition) == per_step(30)
    # nothing to read: no such span, no traced step, no trace
    assert read("engine.assemble") is None
    rec["series"]["traced_steps"] = []
    assert read("engine.fetch") is None
    rec["trace"] = None
    assert read("engine.fetch") is None


def test_unscoped_share_counts_busy_time_no_scoped_operation_covers():
    scopes = ["attn", "kv_append"]
    ops = [("k.1 bf16[8]", 0, 40, "jit(step)/attn/dot"),
           # a path-less container over a scoped body counts as covered
           ("while.2 (s32[])", 40, 20, ""),
           ("f.3 s32[8]", 42, 16, "jit(step)/kv_append/while/body/add"),
           # a scope that is only PART of a component does not count
           ("f.4 bf16[8]", 60, 10, "jit(step)/attn_out/dot"),
           ("copy-done.5 bf16[8]", 70, 10, ""),
           ("fusion.6 s8[64,128]", 90, 20, "")]
    rec = _record(ops, {}, 1)
    share = lambda **a: program_trace.unscoped_share(rec, scopes, **a)
    # busy 0..80 and 90..110 = 100; named: 0..40 and 42..58 = 56
    assert share() == pytest.approx(44.0)
    assert share(result_types=["s8[64,128]"]) == pytest.approx(24.0)
    assert program_trace.unscoped_share(
        rec, ["attn", "kv_append", "attn_out"]) == pytest.approx(34.0)
    rec["trace"] = None
    assert share() is None


def test_the_fallback_names_path_less_events_by_result_type():
    ops = [("fusion.6 s8[64,128]", 0, 20, ""),
           ("fusion.7 s8[64,128]", 30, 20, ""),
           ("fusion.8 f32[64]", 50, 4, ""),
           # the same type WITH a path is the scope readers' to count
           ("fusion.9 s8[64,128]", 60, 20, "jit(step)/kv_append/scatter"),
           ("copy.1 s8[64,128,2]", 80, 5, "")]
    rec = _record(ops, {}, 2)
    read = lambda types: program_trace.pathless_ms_per_step(rec, types)
    assert read(["s8[64,128]"]) == pytest.approx(40 / 1e6 / 2)
    assert read(["s8[64,128]", "f32[64]"]) == pytest.approx(44 / 1e6 / 2)
    assert read(["bf16[64,128]"]) is None
    rec["series"]["traced_steps"] = []
    assert read(["s8[64,128]"]) is None


def test_grouped_matmul_needs_for_8_experts_at_1_4_and_64_tokens():
    config = {"as_run": {"hidden": 4096, "ffn": 14336, "num_experts": 8,
                         "topk": 2, "moe_layers": [0, 1]},
              "overrides": {"param_dtype": "bfloat16"}}
    expert = 2 * 4096 * 14336 * 2          # up + down of one, bf16
    for tokens, touched in ((1, 2), (4, 8), (64, 8)):
        rows = [(tokens - 1, 900), (1, 17)] if tokens > 1 else [(1, 17)]
        got_bytes, got_ops = grouped_matmul.step_needs(config, rows)
        assigned = tokens * 2
        assert got_bytes == 2 * (
            touched * expert + assigned * 2 * (4096 + 14336) * 2)
        assert got_ops == 2 * 4.0 * assigned * 4096 * 14336
    # a full step of the real configuration: 3.76 GB of weights
    real = json.loads(
        (BENCH / "configs" / "mixtral8x7b-d2.json").read_text())
    got_bytes, _ = grouped_matmul.step_needs(real, [(1, 500)] * 25)
    assert got_bytes == pytest.approx(3.76e9, rel=0.01)
    # quantized experts are another function's to count, not a guess
    with pytest.raises(KeyError):
        grouped_matmul.step_needs({**config, "overrides": {}}, [(1, 1)])
