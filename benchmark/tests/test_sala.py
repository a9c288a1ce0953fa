"""PR 33's additions to the benchmark, as files: the configuration
``minicpmsala9b-d8``, the mix ``docbatch`` with its ``backlog``
arrivals kind, the cell ``minicpmsala9b.docbatch``, its reference
module and two ``needs`` functions. CPU, seconds; nothing here
measures."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.arrivals import backlog
from benchmark.harness import loadgen, program, weights
from benchmark.harness.spec import Spec
from benchmark.kernel_needs import (
    lightning_attention,
    ragged_paged_attention_selected,
)
from benchmark.models import minicpm_sala

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
CELL = "minicpmsala9b.docbatch"
CATALOG = pathlib.Path(
    "/opt/skills/guides/model-configs/architectures.jsonl")

TINY = {
    "vocab": 64, "n_layers": 3, "hidden": 32, "ffn": 48, "n_heads": 4,
    "n_kv_heads": 2, "head_dim": 8,
    "layer_mixer": ["attention", "lightning", "attention"],
    "lightning_heads": 2, "sparse_kernel": 4, "sparse_stride": 2,
    "sparse_block": 8, "sparse_init_blocks": 1, "sparse_window": 16,
    "sparse_topk": 4, "sparse_dense_len": 32, "rope_theta": 10000.0,
    "norm_eps": 1e-6, "embed_scale": 12.0, "residual_scale": 0.25,
    "logit_divisor": 4.0,
}


def test_the_cell_resolves_and_the_program_builds_the_tree_the_plan_gives():
    """At the published widths, by shapes only: the preset with the
    file's overrides IS the ``as_run`` sizes, and ``Transformer.init``
    gives the tree ``minicpm_sala.param_plan`` plans."""
    from jax.sharding import Mesh

    from triton_distributed_tpu.models import Transformer

    cell = Spec(REPO).cell(CELL)
    assert cell.chips == 1 and cell.mix_name == "docbatch"
    cfg = program.model_config(cell.config)
    assert len(cfg.sparse_layers) == 2 and len(cfg.lightning_layers) == 6
    model = Transformer(
        cfg, Mesh(np.asarray(jax.devices()[:1]), ("x",)), tp_axis="x")
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    have = weights.abstract_params(
        minicpm_sala.param_plan(cell.config["as_run"]), cfg.param_dtype)
    assert jax.tree.structure(want) == jax.tree.structure(have)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(want), jax.tree.leaves(have)))
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(have))
    assert 5.6e9 < 2 * n_params < 5.7e9        # 5.64 GB of bf16 weights
    eng = cell.config["engine"]
    assert eng["slots"] * loadgen.worst_case_tokens(cell.mix) \
        == eng["npages"] * eng["page"]


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_file_holds_every_number_of_the_catalogs_config():
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
               if json.loads(line)["name"] == "MiniCPM-SALA")
    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "minicpmsala9b-d8.json").read_text())
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 8
    assert cfg["published"]["num_hidden_layers"] == 32


def test_backlog_gives_the_same_sixteen_requests_all_due_at_once():
    cell = Spec(REPO).cell(CELL)
    assert (backlog.gaps({}, 15, 0.3) == 0).all()
    runs = [loadgen.generate(cell.mix, cell.load["rate_rps"], 51.0, seed,
                             73448) for seed in (1, 2, 3300003301)]
    lengths = []
    for arrivals in runs:
        assert len(arrivals) == 16
        assert all(a.due == 0.0 for a in arrivals)
        lengths.append((sorted(len(a.prompt) for a in arrivals),
                        sorted(a.max_new for a in arrivals)))
        assert min(lengths[-1][0]) > 8192      # every prompt past dense_len
    assert lengths[0] == lengths[1] == lengths[2]
    # ... in another order, with other tokens
    assert [len(a.prompt) for a in runs[0]] != [len(a.prompt) for a in runs[1]]


def test_the_references_blocked_evaluation_is_its_one_shot_evaluation(
        monkeypatch):
    params = weights.make_params(
        minicpm_sala.param_plan(TINY), 3, jnp.float32)
    monkeypatch.setattr(minicpm_sala, "ROW_BLOCK", 16)
    monkeypatch.setattr(minicpm_sala, "Q_BLOCK", 8)
    monkeypatch.setattr(minicpm_sala, "SEQ_BUCKET", 32)
    minicpm_sala._logits.clear_cache()
    tokens = np.random.default_rng(0).integers(0, 64, (77,)).astype(np.int32)
    rows = np.arange(77)
    try:
        blocked = minicpm_sala.logits_at(params, TINY, tokens, rows)
        low = minicpm_sala.logits_at(params, TINY, tokens, rows, bits=8)
        low_state = minicpm_sala.logits_at(
            params, TINY, tokens, rows, bits=minicpm_sala.STATE_BF16)
    finally:
        minicpm_sala._logits.clear_cache()
    whole = minicpm_sala.logits_at(params, TINY, tokens, rows, blocked=False)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               atol=1e-4, rtol=1e-4)
    # the control is another computation, not the same one
    assert float(jnp.max(jnp.abs(low - whole))) > 1e-2
    # the state-only control too, blocked and one-shot alike (it rounds
    # the state after every token in both), and by less than int8 does
    whole_state = minicpm_sala.logits_at(
        params, TINY, tokens, rows, bits=minicpm_sala.STATE_BF16,
        blocked=False)
    np.testing.assert_allclose(np.asarray(low_state),
                               np.asarray(whole_state), atol=1e-4, rtol=1e-4)
    off = float(jnp.max(jnp.abs(low_state - whole)))
    assert 1e-4 < off < float(jnp.max(jnp.abs(low - whole)))


def test_needs_of_the_new_kernels_on_a_hand_made_step():
    config = {"as_run": {**TINY, "n_layers": 3}, "engine": {"page": 16},
              "kv_bytes_per_element": 2}
    # two sparse layers; K and V of one page: 2 heads x 16 x 8 x 2 x 2 B
    page_bytes, pair_ops = 2 * 16 * 8 * 2 * 2, 4.0 * 4 * 8
    # a decode row at cursor 20 (dense: 2 pages, 20 pairs) and one at
    # cursor 100 (sparse: 4 blocks of 8 = 2 pages; 3 x 8 + 99 % 8 + 1)
    by, ops = ragged_paged_attention_selected.step_needs(
        config, [(1, 20), (1, 100)])
    assert by == 2 * (2 + 2) * page_bytes
    assert ops == 2 * (20 + 3 * 8 + 4) * pair_ops
    # a chunk of 4 ending at 34: positions 30, 31 dense, 32, 33 sparse
    by, ops = ragged_paged_attention_selected.step_needs(config, [(4, 34)])
    assert by == 2 * 2 * page_bytes
    assert ops == 2 * (31 + 32 + (24 + 1) + (24 + 2)) * pair_ops
    # one lightning layer, 2 heads of 8: state 2 x 2 x 8 x 8 x 4 B
    by, ops = lightning_attention.step_needs(config, [(1, 20), (4, 34)])
    assert by == 2 * (2 * 2 * 8 * 8 * 4) + 4 * (1 + 4) * 2 * 8 * 4
    assert ops == 2 * (4.0 * 1 * 8 + 4.0 * 1 * 64) \
        + 2 * (4.0 * 16 * 8 + 4.0 * 4 * 64)
