"""PR 29's additions to the benchmark, as files: the configuration
``kexaone236b-ep8-d5``, the mix ``mixedlen``, the cell
``kexaone236b.mixedlen``, its reference module, two ``needs`` functions
and one reader. CPU, seconds; nothing here measures."""

import copy
import json
import pathlib
import shutil
import time

import jax
import numpy as np
import pytest

from benchmark.harness import (
    cell_run, loadgen, metrics, program, trace, weights,
)
from benchmark.harness.spec import Spec
from benchmark.kernel_needs import (
    grouped_matmul_share,
    ragged_paged_attention,
    ragged_paged_attention_mixed,
)
from benchmark.readers import trace_names

HERE = pathlib.Path(__file__).resolve().parent
DATA_ROOT = HERE / "data" / "root"
REPO = HERE.parents[1]
CELL = "kexaone236b.mixedlen"
CATALOG = pathlib.Path(
    "/opt/skills/guides/model-configs/architectures.jsonl")


def test_the_cell_resolves_and_the_program_builds_the_tree_the_plan_gives():
    """At the published widths, by shapes only: the preset with the
    file's overrides IS the ``as_run`` sizes, and ``Transformer.init``
    gives the tree ``exaone_moe.param_plan`` plans (what
    ``program.build`` checks before it makes a weight)."""
    from jax.sharding import Mesh

    from benchmark.models import exaone_moe
    from triton_distributed_tpu.models import Transformer

    cell = Spec(REPO).cell(CELL)
    assert cell.chips == 1 and cell.mix_name == "mixedlen"
    cfg = program.model_config(cell.config)
    assert cfg.layer_attn.count("full") == 1 and cfg.local_experts == 16
    model = Transformer(
        cfg, Mesh(np.asarray(jax.devices()[:1]), ("x",)), tp_axis="x")
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    have = weights.abstract_params(
        exaone_moe.param_plan(cell.config["as_run"]), cfg.param_dtype)
    assert jax.tree.structure(want) == jax.tree.structure(have)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(want), jax.tree.leaves(have)))
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(have))
    assert 7.3e9 < 2 * n_params < 7.5e9        # 7.42 GB of bf16 weights
    eng = cell.config["engine"]
    assert eng["slots"] * loadgen.worst_case_tokens(cell.mix) \
        <= eng["npages"] * eng["page"]
    assert 0 < cell.load["rate_rps"] <= 0.55 * cell.load["knee_rps"]
    for name in ("attn_window_ms_per_step", "attn_global_ms_per_step",
                 "qk_rope_ms_per_step", "shared_expert_ms_per_step",
                 "window_pages_walked_per_step",
                 "global_pages_walked_per_step",
                 "ragged_paged_attention_mixed_roofline",
                 "grouped_matmul_share_roofline", "moe_gemm_ms_per_step",
                 "dense_ffn_ms_per_step"):
        assert name in cell.layer_metrics
    assert "ragged_paged_attention_roofline" not in cell.layer_metrics


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_configuration_file_keeps_every_number_of_the_catalog():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "K-EXAONE-236B-A23B")
    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "kexaone236b-ep8-d5.json").read_text())
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published"]["num_experts"] == 128
    for key in ("assumed", "not_served", "deployment", "served_as"):
        assert cfg[key]


def test_mixedlen_offers_the_issue_s_lengths():
    mix = Spec(REPO).cell(CELL).mix
    a = loadgen.generate(mix, 2.6, 51.0, 2**31 + 7, 19200)
    lens = np.array([len(x.prompt) for x in a])
    outs = np.array([x.max_new for x in a])
    assert len(a) == 133 and lens.min() >= 64 and lens.max() == 8192
    assert 450 < np.median(lens) < 580 and 900 < lens.mean() < 1150
    assert 0.09 < (lens >= 2048).mean() < 0.15
    assert outs.min() >= 16 and outs.max() <= 512
    assert 110 < np.median(outs) < 150
    assert max(int(x.prompt.max()) for x in a) < 19200


def _twin_root(tmp_path) -> pathlib.Path:
    """``data/root`` + a CPU-sized twin of the new cell, added as a
    later PR adds things: files and entries, no edit."""
    root = tmp_path / "root"
    shutil.copytree(DATA_ROOT, root)
    bench = root / "benchmark"
    real = json.loads((REPO / "benchmark" / "configs"
                       / "kexaone236b-ep8-d5.json").read_text())
    small = dict(hidden=128, ffn=128, dense_ffn=192, n_heads=8,
                 n_kv_heads=4, head_dim=16, vocab=128, num_experts=8,
                 topk=2, window=16)
    cfg = {k: real[k] for k in ("model", "preset", "kv_bytes_per_element")}
    cfg["overrides"] = {**small, "n_layers": 5, "experts_held": 4,
                        "first_expert_held": 2, "dtype": "float32",
                        "param_dtype": "float32"}
    cfg["as_run"] = {**real["as_run"], **small, "experts_published": 8,
                     "experts_held": 4, "first_expert_held": 2}
    cfg["engine"] = {"slots": 4, "token_budget": 64, "chunk": 24,
                     "page": 8, "npages": 64}
    cfg["tolerance"] = {"sample": 3, "control_bits": 4,
                        "limits": {"gap_p99": 0.7, "gap_mean": 0.1}}
    cfg["chips"] = 1
    (bench / "configs" / "tiny-exaone.json").write_text(json.dumps(cfg))
    mix = json.loads((REPO / "benchmark" / "mixes"
                      / "mixedlen.json").read_text())
    mix["prompt"].update(median=24, min=4, max=96)
    mix["output"].update(median=6, min=2, max=12)
    (bench / "mixes" / "tinylen.json").write_text(json.dumps(mix))
    (bench / "cells" / "tiny.mixedlen.json").write_text(
        json.dumps({"rate_rps": 5.0}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "tiny-exaone", "source": "benchmark/tests",
        "file": "benchmark/configs/tiny-exaone.json", "reduced": [],
        "why": "test"})
    doc["workloads"].append({
        "name": "tiny.mixedlen", "config": "tiny-exaone",
        "traffic": "tinylen", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root


def test_a_rehearsal_of_the_cell_s_twin_runs_and_comes_out_correct(
        tmp_path, capsys):
    spec = Spec(_twin_root(tmp_path))
    rc = cell_run.run_cell(spec, "tiny.mixedlen", 2**31 + 29, 1.5, False,
                           t_start=time.perf_counter(), rehearse=True)
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True, out
    assert line["attempted"] == 8 and line["failed"] == 0
    assert line["metrics"] == {} and line["rehearsal"] is True
    window = next(json.loads(ln)["window"] for ln in out.splitlines()
                  if ln.startswith('{"window"'))
    assert 0 < window["stats.window_pages_walked"] \
        < window["stats.global_pages_walked"]


def test_the_two_nested_scopes_are_in_the_twin_s_lowered_step(tmp_path):
    """``qk_rope`` inside ``attn_proj`` and ``shared_expert`` inside
    ``dense_ffn``: what their two metric files read is in the step
    program of a configuration that has the layers, nested as said."""
    import re

    from triton_distributed_tpu.kernels.ragged_paged_attention import (
        auto_block_q,
    )

    cell = Spec(_twin_root(tmp_path)).cell("tiny.mixedlen")
    eng = program.build(cell.config, cell.mix, 1, seed=5).engine
    eng.submit(program.new_request(
        0, np.arange(9, dtype=np.int32), 2, eng.step_count))
    eng._admit()
    *arrays, batched, _ = eng._assemble()
    text = eng._step_jit().lower(*eng._step_args(
        tuple(arrays), auto_block_q(1, eng._g))).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]+)"', text))
    for outer, inner in (("attn_proj", "qk_rope"),
                         ("dense_ffn", "shared_expert")):
        assert any(f"{outer}/{inner}" in p for p in paths), inner
        files = REPO / "benchmark" / "layer_metrics"
        definition = json.loads(
            (files / f"{inner}_ms_per_step.json").read_text())
        assert definition["args"] == {"nested": inner}
    ops = [("fusion.1 bf16[8]", 0, 3_000_000, "jit(step)/attn_proj/qk_rope/mul"),
           ("fusion.2 bf16[8]", 0, 1_000_000, "jit(step)/attn_proj/dot")]
    rec = {"trace": trace.TraceSummary({"/device:TPU:0": ops}, []),
           "series": {"traced_steps": [0, 1]}}
    assert trace_names.nested_scope_ms_per_step(rec, "qk_rope") \
        == pytest.approx(1.5)
    assert trace_names.nested_scope_ms_per_step(rec, "shared_expert") is None


def test_the_control_in_lower_precision_comes_out_not_correct(tmp_path):
    """The reference at ``control_bits`` in the program's place fails
    the limits that the program's own tokens pass (the twin's)."""
    from benchmark.harness import correct, driver

    spec = Spec(_twin_root(tmp_path))
    cell = spec.cell("tiny.mixedlen")
    tol, sizes = cell.config["tolerance"], cell.config["as_run"]
    prog = program.build(cell.config, cell.mix, 1, 5)
    program.warm_up(prog.engine, sizes["vocab"])
    arr = loadgen.generate(cell.mix, 6.0, 1.5, 5, sizes["vocab"])
    win = driver.serve(prog.engine, arr, 1.5, 60.0)
    assert not metrics.failures(win, sizes["vocab"])
    picked = correct.sample(win.arrivals, 5, tol["sample"])
    gaps = correct.served_gaps(
        prog.reference.logits_at, prog.masters(5), sizes, picked,
        cell.mix["output"]["max"], control_bits=tol["control_bits"])
    ok, rows = correct.decide(correct.numbers(gaps["program"]), tol)
    assert ok, rows
    ok, rows = correct.decide(correct.numbers(gaps["control"]), tol)
    assert not ok, rows


# ------------------------------------------------ needs and the reader


def test_mixed_attention_needs_count_each_kind_of_layer_its_own_way():
    cfg = copy.deepcopy(Spec(REPO).cell(CELL).config)
    rows = [(1, 5000), (256, 256), (256, 1024), (1, 100)]
    got_b, got_f = ragged_paged_attention_mixed.step_needs(cfg, rows)
    page_b = 8 * 128 * 128 * 2 * 2
    # full layer: 40 + 2 + 8 + 1 pages; sliding: 2 + 2 + 3 + 1
    assert got_b == (51 + 4 * 8) * page_b
    full_pairs = (5000 + (256 * 257) // 2 + 256 * 768 + (256 * 257) // 2
                  + 100)
    win_pairs = (128 + sum(min(128, p + 1) for p in range(256))
                 + 256 * 128 + 100)
    assert got_f == (full_pairs + 4 * win_pairs) * 4.0 * 64 * 128
    # with every layer full it is the older function, layer for layer
    cfg["as_run"]["layer_attn"] = ["full"] * 5
    assert ragged_paged_attention_mixed.step_needs(cfg, rows) == \
        ragged_paged_attention.step_needs(cfg, rows)


def test_share_needs_expect_what_an_even_router_touches():
    cfg = Spec(REPO).cell(CELL).config
    b1, f1 = grouped_matmul_share.step_needs(cfg, [(1, 10)])
    expert = 3 * 6144 * 2048 * 2
    # one token: 8 of 128 choices, 16 held -> one expert's weights
    assert b1 == pytest.approx(4 * (expert + 1 * (2 * 6144 + 3 * 2048) * 2))
    assert f1 == pytest.approx(4 * 6.0 * 1 * 6144 * 2048)
    big, _ = grouped_matmul_share.step_needs(cfg, [(256, 256)] * 2)
    assert 4 * 16.0 * expert < big < 4 * 16.3 * expert     # all 16 + rows


class _Trace:
    def __init__(self, ops):
        self.device_ops = {"/device:TPU:0": ops}


def test_the_reader_tells_the_global_launch_from_the_windowed_one():
    ops = [("ragged_paged_attention.1 bf16[8]", 0, 2_000_000, "a/attn"),
           ("ragged_paged_attention_w128.3 bf16[8]", 0, 500_000, "a/attn"),
           ("ragged_paged_attention_w128.4 bf16[8]", 0, 500_000, "a/attn"),
           ("fusion.7 f32[2]", 0, 9_000_000, "")]
    rec = {"trace": _Trace(ops), "series": {"traced_steps": [3, 4]}}
    read = trace_names.events_ms_per_step
    assert read(rec, "ragged_paged_attention") == pytest.approx(1.5)
    assert read(rec, "ragged_paged_attention",
                exclude="ragged_paged_attention_w") == pytest.approx(1.0)
    assert read(rec, "ragged_paged_attention_w") == pytest.approx(0.5)
    assert read(rec, "no_such_kernel") is None
    assert read({"trace": None, "series": {}}, "x") is None
    # the metric files name it as the harness resolves names
    definition = json.loads((REPO / "benchmark" / "layer_metrics"
                             / "attn_global_ms_per_step.json").read_text())
    assert metrics.read_layer_metric(rec, definition) == pytest.approx(1.0)
