"""PR 49's additions to the benchmark, as files: the configuration
``keyevl2-ep8-d8``, the mix ``docs16k``, the cell ``keyevl2.docs16k``,
its reference module, two ``needs`` functions and fourteen metric
files. CPU, seconds; nothing here measures."""

import inspect
import json
import pathlib
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import cell_run, loadgen, metrics, program, weights
from benchmark.harness.spec import Spec, named
from benchmark.kernel_needs import dsa_indexer, dsa_walk
from benchmark.models import keye_vl2

HERE = pathlib.Path(__file__).resolve().parent
DATA_ROOT = HERE / "data" / "root"
REPO = HERE.parents[1]
CELL = "keyevl2.docs16k"
CATALOG = pathlib.Path(
    "/opt/skills/guides/model-configs/architectures.jsonl")
NEW_METRICS = (
    "dsa_index_proj_ms_per_step", "dsa_scan_ms_per_step",
    "dsa_select_ms_per_step", "dsa_walk_ms_per_step",
    "index_keys_scanned_per_step", "dsa_selected_tokens_per_step",
    "dsa_sparse_row_share", "dsa_indexer_roofline", "dsa_walk_roofline",
    "keyevl2_moe_route_ms_per_step", "keyevl2_moe_gemm_ms_per_step",
    "keyevl2_packed_rows_per_step", "keyevl2_moe_local_step_share",
    "keyevl2_qk_norm_rope_ms_per_step")

TINY = {
    "vocab": 64, "n_layers": 2, "hidden": 32, "ffn": 16, "n_heads": 4,
    "n_kv_heads": 2, "head_dim": 8, "index_heads": 2, "index_dim": 8,
    "index_topk": 8, "rope_theta": 1e7, "num_experts": 8,
    "experts_held": 4, "first_expert_held": 2, "topk": 2, "norm_eps": 1e-6,
}


def test_the_cell_resolves_and_the_program_builds_the_tree_the_plan_gives():
    """At the published widths, by shapes only: the preset with the
    file's overrides IS the ``as_run`` sizes, ``Transformer.init`` gives
    the tree ``keye_vl2.param_plan`` plans, and the mix's worst case
    fills the pool exactly."""
    from jax.sharding import Mesh

    from triton_distributed_tpu.models import Transformer

    cell = Spec(REPO).cell(CELL)
    assert cell.chips == 1 and cell.mix_name == "docs16k"
    cfg = program.model_config(cell.config)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk) == (16, 64, 2048)
    assert cfg.moe_layers == tuple(range(8)) and cfg.local_experts == 16
    stored = cell.config["index_bytes_per_token"]
    assert stored["needed"] == 2 * cfg.index_dim
    assert stored["stored"] == 2 * cfg.index_stored
    model = Transformer(
        cfg, Mesh(np.asarray(jax.devices()[:1]), ("x",)), tp_axis="x")
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    have = weights.abstract_params(
        keye_vl2.param_plan(cell.config["as_run"]), cfg.param_dtype)
    assert jax.tree.structure(want) == jax.tree.structure(have)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(want), jax.tree.leaves(have)))
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(have))
    assert 1.70e9 < 2 * n_params < 1.72e9      # 1.71 GB of bf16 weights
    eng = cell.config["engine"]
    assert loadgen.worst_case_tokens(cell.mix) == 27136
    assert eng["slots"] * 27136 == eng["npages"] * eng["page"]
    assert cfg.index_topk % eng["page"] == 0


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_file_holds_every_number_of_the_catalogs_config():
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
               if json.loads(line)["name"] == "Keye-VL-2.0-30B-A3B")
    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "keyevl2-ep8-d8.json").read_text())
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (8, 16, 18992)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (48, 128, 151936)
    assert set(cfg["not_served"]) == {"vision_tower"}
    # no width is cut
    as_run, sa = cfg["as_run"], row["config"]["sa_config"]
    assert (as_run["hidden"], as_run["ffn"], as_run["n_heads"],
            as_run["n_kv_heads"], as_run["head_dim"], as_run["topk"]) == (
        row["config"]["hidden_size"], row["config"]["moe_intermediate_size"],
        row["config"]["num_attention_heads"],
        row["config"]["num_key_value_heads"], row["config"]["head_dim"],
        row["config"]["num_experts_per_tok"])
    assert (as_run["index_heads"], as_run["index_dim"],
            as_run["index_topk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    assert as_run["vocab"] * 8 == row["config"]["vocab_size"]


def test_the_mix_gives_the_same_sixteen_documents_all_due_at_once():
    cell = Spec(REPO).cell(CELL)
    vocab = cell.config["as_run"]["vocab"]
    runs = [loadgen.generate(cell.mix, cell.load["rate_rps"], 51.0, seed,
                             vocab) for seed in (1, 2, 4900004901)]
    lengths = []
    for arrivals in runs:
        assert len(arrivals) == 16
        assert all(a.due == 0.0 for a in arrivals)
        assert all(int(a.prompt.max()) < vocab for a in arrivals)
        lengths.append((sorted(len(a.prompt) for a in arrivals),
                        sorted(a.max_new for a in arrivals)))
    assert lengths[0] == lengths[1] == lengths[2]
    prompts, outputs = lengths[0]
    assert (prompts[0], prompts[-1]) == (12390, 21665)
    assert (outputs[0], outputs[-1]) == (3584, 4608)
    assert sum(prompts) == 264880 and sum(outputs) == 65637
    assert sum(-(-n // 256) for n in prompts) == 1041
    assert max(len(a.prompt) + a.max_new for a in runs[0]) <= 27136
    assert [len(a.prompt) for a in runs[0]] != [len(a.prompt) for a in runs[1]]


def test_the_references_blocked_evaluation_is_its_one_shot_evaluation(
        monkeypatch):
    """Queries in blocks: the same logits as one block; the int8
    control is another computation; most positions select."""
    params = weights.make_params(keye_vl2.param_plan(TINY), 3, jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 64, (64,)).astype(np.int32)
    rows = np.arange(64)
    keye_vl2._logits.clear_cache()
    whole = keye_vl2.logits_at(params, TINY, tokens, rows)
    monkeypatch.setattr(keye_vl2, "Q_BLOCK", 16)
    keye_vl2._logits.clear_cache()
    try:
        blocked = keye_vl2.logits_at(params, TINY, tokens, rows)
        low = keye_vl2.logits_at(params, TINY, tokens, rows, bits=8)
        dense = keye_vl2.logits_at(
            params, dict(TINY, index_topk=64), tokens, rows)
    finally:
        keye_vl2._logits.clear_cache()
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               atol=1e-4, rtol=1e-4)
    assert float(jnp.max(jnp.abs(low - whole))) > 1e-2
    # the selection matters from position 8 on, and not before
    np.testing.assert_allclose(np.asarray(dense)[:8], np.asarray(whole)[:8],
                               atol=1e-5, rtol=1e-5)
    assert float(jnp.max(jnp.abs(dense[8:] - whole[8:]))) > 1e-2
    # causal: tokens padded on at the end change nothing before them
    padded = keye_vl2.logits_at(
        params, TINY, np.pad(tokens[:48], (0, 16)), np.arange(48))
    np.testing.assert_allclose(np.asarray(padded), np.asarray(whole)[:48],
                               atol=1e-4, rtol=1e-4)
    # and nothing of the program is imported
    assert "triton_distributed_tpu" not in inspect.getsource(keye_vl2)


def test_needs_of_the_scan_and_of_the_walk_on_a_hand_made_step():
    config = {"as_run": TINY, "engine": {"page": 8},
              "kv_bytes_per_element": 2}
    # 2 layers; a key 8 values x 2 B; a scored pair 2 x 2 x 8; a token's
    # K and V 2 x 2 x 8 values x 2 B; an attended pair 4 x 4 x 8
    rows = [(1, 6), (1, 20), (4, 34)]
    # the scan: the row at 6 <= topk is not scanned; the decode row at
    # 20 reads 20 keys and scores 20 pairs; the chunk of 4 ending at 34
    # reads 34 and scores 31 + 32 + 33 + 34
    by, ops = dsa_indexer.step_needs(config, rows)
    assert by == 2 * (20 + 34) * 16
    assert ops == 2 * (20 + 31 + 32 + 33 + 34) * 32.0
    # the walk: 6, 8 and 8 tokens' K and V; 6 + 8 + 4 x 8 pairs
    by, ops = dsa_walk.step_needs(config, rows)
    assert by == 2 * (6 + 8 + 8) * 64
    assert ops == 2 * (6 + 8 + 32) * 128.0
    # at the published sizes: 128 B a scanned key, 2048 a scored pair;
    # 2048 tokens of 2 KB, 16384 operations an attended pair
    cell = Spec(REPO).cell(CELL)
    by, ops = dsa_indexer.step_needs(cell.config, [(1, 20000)])
    assert by == 8 * 20000 * 128 and ops == 8 * 20000 * 2048.0
    by, ops = dsa_walk.step_needs(cell.config, [(1, 20000)])
    assert by == 8 * 2048 * 2048 and ops == 8 * 2048 * 16384.0


def test_every_new_metric_resolves_its_reader_and_lists_the_cell_alone():
    spec = Spec(REPO)
    cell = spec.cell(CELL)
    entries = {m["name"]: m for m in spec.doc["per_layer"]}
    assert [m["name"] for m in spec.doc["per_layer"]][-14:] == list(
        NEW_METRICS)
    for name in NEW_METRICS:
        assert entries[name]["workloads"] == [CELL], name
        assert entries[name]["moves"] == "itl_p50_ms"
        definition = cell.layer_metrics[name]
        kind = definition["reader"]
        reader = metrics.READERS.get(kind) or named(kind)
        assert set(definition.get("args", {})) <= set(
            inspect.signature(reader).parameters), name
        if "needs" in definition.get("args", {}):
            assert named(definition["args"]["needs"]) in (
                dsa_indexer.step_needs, dsa_walk.step_needs)
    # two accepted metrics gain the cell at the end of their lists
    for name in ("chunk_step_rows", "chunk_narrow_step_share"):
        assert entries[name]["workloads"][-1] == CELL
    # a record with no trace and a program without the counters: every
    # reader returns nothing or 0 and none raises (the parent's side)
    rec = {"series": {}, "counters": {"device_steps": 10}, "trace": None,
           "peaks": {}, "chips": 1, "config": cell.config}
    for name in NEW_METRICS:
        value = metrics.read_layer_metric(rec, cell.layer_metrics[name])
        assert value in (None, 0.0), (name, value)
    assert [m["name"] for m in cell.end_to_end] == [
        "itl_p50_ms", "itl_p95_ms", "out_tok_s", "setup_s"]


def _twin_root(tmp_path) -> pathlib.Path:
    """``data/root`` + a CPU-sized twin of the new cell, added as a
    later PR adds things: files and entries, no edit."""
    root = tmp_path / "root"
    shutil.copytree(DATA_ROOT, root)
    bench = root / "benchmark"
    real = json.loads((REPO / "benchmark" / "configs"
                       / "keyevl2-ep8-d8.json").read_text())
    small = dict(hidden=64, ffn=32, n_heads=4, n_kv_heads=2, head_dim=16,
                 vocab=128, num_experts=8, topk=2, index_heads=2,
                 index_dim=8, index_topk=8)
    cfg = {k: real[k] for k in ("model", "preset", "kv_bytes_per_element")}
    cfg["overrides"] = {**small, "n_layers": 2, "experts_held": 4,
                        "first_expert_held": 2, "dtype": "float32",
                        "param_dtype": "float32"}
    cfg["as_run"] = {**real["as_run"], **small, "n_layers": 2,
                     "moe_layers": [0, 1], "rope_layers": [0, 1],
                     "experts_published": 8, "experts_held": 4,
                     "first_expert_held": 2}
    cfg["engine"] = {"slots": 4, "token_budget": 64, "chunk": 16,
                     "page": 8, "npages": 64}
    cfg["tolerance"] = {"sample": 3, "control_bits": 4,
                        "limits": {"gap_p99": 0.7, "gap_mean": 0.1}}
    cfg["chips"] = 1
    (bench / "configs" / "tiny-keye.json").write_text(json.dumps(cfg))
    mix = json.loads((REPO / "benchmark" / "mixes"
                      / "docs16k.json").read_text())
    mix["prompt"].update(median=40, min=12, max=96)
    mix["output"].update(median=6, min=2, max=12)
    mix["drain_s"] = 120
    (bench / "mixes" / "tinydocs.json").write_text(json.dumps(mix))
    (bench / "cells" / "tiny.docs.json").write_text(
        json.dumps({"rate_rps": 2.0}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "tiny-keye", "source": "benchmark/tests",
        "file": "benchmark/configs/tiny-keye.json", "reduced": [],
        "why": "test"})
    doc["workloads"].append({
        "name": "tiny.docs", "config": "tiny-keye",
        "traffic": "tinydocs", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root


def test_a_rehearsal_of_the_cell_s_twin_runs_and_comes_out_correct(
        tmp_path, capsys):
    """The unchanged harness serves a CPU-sized twin of the cell end to
    end (``--rehearse``): four documents due at once, contexts past the
    twin's ``index_topk``, every served token the reference's."""
    spec = Spec(_twin_root(tmp_path))
    rc = cell_run.run_cell(spec, "tiny.docs", 2**31 + 49, 2.0, False,
                           t_start=time.perf_counter(), rehearse=True)
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True, out
    assert line["attempted"] == 4 and line["failed"] == 0
    assert line["metrics"] == {} and line["rehearsal"] is True
    window = next(json.loads(ln)["window"] for ln in out.splitlines()
                  if ln.startswith('{"window"'))
    assert window["stats.dsa_rows"] >= window["stats.dsa_sparse_rows"] > 0
    assert window["stats.index_keys_scanned"] > 0
    assert window["stats.dsa_selected_tokens"] > 0
