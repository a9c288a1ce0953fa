"""PR 35's additions to the benchmark, as files: the configuration
``dotsvlm1-ep32-d5``, the mix ``docs8k``, the cell ``dotsvlm1.docs8k``,
its reference module, the latent walk's ``needs`` function and ten
metric files. CPU, seconds; nothing here measures."""

import inspect
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import loadgen, metrics, program, weights
from benchmark.harness.spec import Spec, named
from benchmark.kernel_needs import ragged_paged_attention_latent
from benchmark.models import dots_vlm1

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
CELL = "dotsvlm1.docs8k"
CATALOG = pathlib.Path(
    "/opt/skills/guides/model-configs/architectures.jsonl")
NEW_METRICS = (
    "attn_latent_ms_per_step", "mla_absorb_ms_per_step",
    "mla_lowrank_ms_per_step", "latent_pages_walked_per_step",
    "latent_rows_per_step", "chunk_rows_expanded_per_step",
    "ragged_paged_attention_latent_roofline", "router_ms_per_step",
    "expert_gemm_ms_per_step", "ffn_dense_shared_ms_per_step")

TINY = {
    "vocab": 64, "n_layers": 3, "hidden": 32, "ffn": 16, "dense_ffn": 48,
    "n_heads": 4, "kv_latent": 16, "q_latent": 24, "qk_nope_dim": 8,
    "qk_rope_dim": 4, "v_head_dim": 8, "rope_theta": 10000.0,
    "rope_yarn_factor": 4.0, "rope_yarn_original": 16,
    "rope_yarn_beta_fast": 32.0, "rope_yarn_beta_slow": 1.0,
    "rope_mscale_all_dim": 1.0, "num_experts": 16, "experts_held": 4,
    "first_expert_held": 8, "topk": 4, "moe_layers": [1, 2],
    "shared_experts": 1, "routed_scale": 2.5, "router_groups": 4,
    "router_topk_groups": 2, "norm_eps": 1e-6,
}


def test_the_cell_resolves_and_the_program_builds_the_tree_the_plan_gives():
    """At the published widths, by shapes only: the preset with the
    file's overrides IS the ``as_run`` sizes, ``Transformer.init`` gives
    the tree ``dots_vlm1.param_plan`` plans, and the mix's worst case
    fills the pool exactly."""
    from jax.sharding import Mesh

    from triton_distributed_tpu.models import Transformer

    cell = Spec(REPO).cell(CELL)
    assert cell.chips == 1 and cell.mix_name == "docs8k"
    cfg = program.model_config(cell.config)
    assert cfg.kv_latent == 512 and cfg.moe_layers == (1, 2, 3, 4)
    stored = cell.config["latent_bytes_per_token"]
    assert stored["needed"] == 2 * cfg.latent_width == 1152
    assert stored["stored"] == 2 * cfg.latent_stored
    model = Transformer(
        cfg, Mesh(np.asarray(jax.devices()[:1]), ("x",)), tp_axis="x")
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    have = weights.abstract_params(
        dots_vlm1.param_plan(cell.config["as_run"]), cfg.param_dtype)
    assert jax.tree.structure(want) == jax.tree.structure(have)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(want), jax.tree.leaves(have)))
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(have))
    assert 6.30e9 < 2 * n_params < 6.32e9      # 6.31 GB of bf16 weights
    eng = cell.config["engine"]
    assert loadgen.worst_case_tokens(cell.mix) == 19840
    assert eng["slots"] * 19840 == eng["npages"] * eng["page"]


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_file_holds_every_number_of_the_catalogs_config():
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
               if json.loads(line)["name"] == "dots.vlm1.inst")
    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "dotsvlm1-ep32-d5.json").read_text())
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 8, 16160)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (61, 256, 129280)
    assert pub["layers_run"] == [0, 3, 4, 5, 6]
    assert set(cfg["not_served"]) == {"multi_token_prediction",
                                      "vision_encoder"}
    # no width is cut
    as_run = cfg["as_run"]
    assert (as_run["hidden"], as_run["ffn"], as_run["dense_ffn"],
            as_run["q_latent"], as_run["kv_latent"], as_run["topk"]) == (
        row["config"]["hidden_size"], row["config"]["moe_intermediate_size"],
        row["config"]["intermediate_size"], row["config"]["q_lora_rank"],
        row["config"]["kv_lora_rank"],
        row["config"]["num_experts_per_tok"])


def test_the_mix_gives_the_same_sixteen_documents_all_due_at_once():
    cell = Spec(REPO).cell(CELL)
    vocab = cell.config["as_run"]["vocab"]
    runs = [loadgen.generate(cell.mix, cell.load["rate_rps"], 51.0, seed,
                             vocab) for seed in (1, 2, 3500003501)]
    lengths = []
    for arrivals in runs:
        assert len(arrivals) == 16
        assert all(a.due == 0.0 for a in arrivals)
        assert all(int(a.prompt.max()) < vocab for a in arrivals)
        lengths.append((sorted(len(a.prompt) for a in arrivals),
                        sorted(a.max_new for a in arrivals)))
    assert lengths[0] == lengths[1] == lengths[2]
    prompts, outputs = lengths[0]
    assert 4096 <= prompts[0] and prompts[-1] <= 16384
    assert 2688 <= outputs[0] and outputs[-1] <= 3456
    assert sum(prompts) == 136615 and sum(outputs) == 49231
    # the longest sequence of the multiset fits a slot's share of pages
    assert max(len(a.prompt) + a.max_new for a in runs[0]) <= 19840
    # ... in another order, with other tokens
    assert [len(a.prompt) for a in runs[0]] != [len(a.prompt) for a in runs[1]]


def test_the_references_blocked_evaluation_is_its_one_shot_evaluation(
        monkeypatch):
    """Heads in blocks, queries in blocks, rows in blocks: the same
    logits as one block of each; and the int8 control is another
    computation."""
    params = weights.make_params(dots_vlm1.param_plan(TINY), 3, jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 64, (64,)).astype(np.int32)
    rows = np.arange(64)
    dots_vlm1._logits.clear_cache()
    whole = dots_vlm1.logits_at(params, TINY, tokens, rows)
    monkeypatch.setattr(dots_vlm1, "Q_BLOCK", 16)
    monkeypatch.setattr(dots_vlm1, "H_BLOCK", 2)
    dots_vlm1._logits.clear_cache()
    try:
        blocked = dots_vlm1.logits_at(params, TINY, tokens, rows)
        low = dots_vlm1.logits_at(params, TINY, tokens, rows, bits=8)
    finally:
        dots_vlm1._logits.clear_cache()
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               atol=1e-4, rtol=1e-4)
    assert float(jnp.max(jnp.abs(low - whole))) > 1e-2
    # causal: tokens padded on at the end change nothing before them
    padded = dots_vlm1.logits_at(
        params, TINY, np.pad(tokens[:40], (0, 24)), np.arange(40))
    np.testing.assert_allclose(np.asarray(padded), np.asarray(whole)[:40],
                               atol=1e-4, rtol=1e-4)
    # and nothing of the program is imported
    assert "triton_distributed_tpu" not in inspect.getsource(dots_vlm1)


def test_needs_of_the_latent_walk_on_a_hand_made_step():
    config = {"as_run": TINY, "engine": {"page": 16},
              "kv_bytes_per_element": 2}
    # 3 layers; an entry 16 + 4 values x 2 B; a pair 2 x 4 x (8 + 4 + 8)
    token_bytes, pair_ops = 20 * 2, 2.0 * 4 * 20
    # a decode row at cursor 20 (20 entries, 20 pairs) and a chunk of 4
    # ending at 34 (34 entries; 31 + 32 + 33 + 34 pairs)
    by, ops = ragged_paged_attention_latent.step_needs(
        config, [(1, 20), (4, 34)])
    assert by == 3 * (20 + 34) * token_bytes
    assert ops == 3 * (20 + 31 + 32 + 33 + 34) * pair_ops
    # at the published sizes: 1152 B a token and layer, 81920 a pair
    cell = Spec(REPO).cell(CELL)
    by, ops = ragged_paged_attention_latent.step_needs(
        cell.config, [(1, 10000)])
    assert by == 5 * 10000 * 1152 and ops == 5 * 10000 * 81920.0


def test_every_new_metric_resolves_its_reader_and_lists_the_cell_alone():
    spec = Spec(REPO)
    cell = spec.cell(CELL)
    entries = {m["name"]: m for m in spec.doc["per_layer"]}
    for name in NEW_METRICS:
        assert entries[name]["workloads"] == [CELL], name
        assert entries[name]["moves"] == "itl_p50_ms"
        definition = cell.layer_metrics[name]
        kind = definition["reader"]
        reader = metrics.READERS.get(kind) or named(kind)
        assert set(definition.get("args", {})) <= set(
            inspect.signature(reader).parameters), name
        if "needs" in definition.get("args", {}):
            assert named(definition["args"]["needs"]) \
                is ragged_paged_attention_latent.step_needs
    # one accepted metric gains the cell at the end of its list
    assert entries["lookahead_step_share"]["workloads"][-1] == CELL
    # a record with no trace and a program without the counters: every
    # reader returns nothing or 0 and none raises (the parent's side)
    rec = {"series": {}, "counters": {"device_steps": 10}, "trace": None,
           "peaks": {}, "chips": 1, "config": cell.config}
    for name in NEW_METRICS:
        value = metrics.read_layer_metric(rec, cell.layer_metrics[name])
        assert value in (None, 0.0), (name, value)
    # the cell reports setup_s and the three token metrics
    assert [m["name"] for m in cell.end_to_end] == [
        "itl_p50_ms", "itl_p95_ms", "out_tok_s", "setup_s"]
